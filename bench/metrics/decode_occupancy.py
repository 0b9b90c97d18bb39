"""Scheduler: decoding slots over ``max_slots``, averaged over the decode
steps of the window (the harness's log of each decode call)."""


def read(run):
    log = run.rec.decode_log
    if not log:
        return None
    return 100.0 * sum(len(c) for c in log) / (len(log) * run.max_slots)
