"""Engine: prefill chunks per engine step in the window, from the engine's
counters. None where the run has no counters."""


def read(run):
    c = getattr(run, "counters", None)
    if not c or not c.get("steps"):
        return None
    return 100.0 * c["prefill_chunks"] / c["steps"]
