"""Scheduler: slots a decode step served over ``max_slots``, from the
engine's counters over the window (``decode_active_slots`` /
(``decode_steps`` x ``max_slots``)). None where the run has no counters."""


def read(run):
    c = getattr(run, "counters", None)
    if not c or not c.get("decode_steps"):
        return None
    return 100.0 * c["decode_active_slots"] / (c["decode_steps"]
                                               * run.max_slots)
