"""Device: the share of the traced window in which no operation ran,
mean over the cell's chips (1 - union of busy intervals / window)."""


def read(run):
    return None if run.trace is None else run.trace.idle_pct
