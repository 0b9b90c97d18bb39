"""Scheduler: median wait from a request's due time to its slot admission
(host clock, every request due in the window; one still queued at the
window's end counts with its wait so far)."""


def read(run):
    return run.stats.get("queue_wait_p50_ms")
