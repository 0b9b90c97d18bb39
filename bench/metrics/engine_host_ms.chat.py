"""Engine host loop: mean over the window's ``engine.step`` spans of the
step's duration less its blocking token reads (``engine.decode_drain``,
``engine.first_token``): the host's own time per step. None where the
trace holds no engine spans."""


def read(run):
    eng = getattr(run, "engine", None)
    steps = (eng or {}).get("step_host_s")
    return 1000.0 * sum(steps) / len(steps) if steps else None
