"""Scheduler: median wait from a request's submission to its slot
admission, from the engine's own stamps (``engine.request_times``), over
the requests submitted in the window; one still queued at the window's end
counts with its wait so far. None where the run has no such stamps."""
import statistics


def read(run):
    times, window = getattr(run, "request_times", None), getattr(
        run, "window", None)
    if not times or not window:
        return None
    lo, hi = window
    waits = [t.get("admitted", hi) - t["submitted"] for t in times.values()
             if lo <= t["submitted"] < hi]
    return 1000.0 * statistics.median(waits) if waits else None
