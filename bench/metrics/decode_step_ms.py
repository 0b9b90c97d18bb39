"""Model step: device time per run of the decode program
(``jit_decode_step`` in the trace's ``XLA Modules`` line)."""


def read(run):
    t = run.trace.get("modules", {}).get("jit_decode_step")
    return 1000.0 * sum(t) / len(t) if t else None
