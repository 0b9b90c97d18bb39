"""Device: the share of the traced window in which no op ran on the chip
(1 - union of ``XLA Ops`` intervals / window)."""


def read(run):
    return run.idle_share()
