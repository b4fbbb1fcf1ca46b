"""The device's idle share of the traced slice (%): 1 - the union of the
intervals in which an op ran, over the slice; the mean over the cell's
chips."""


def read(run):
    return None if run.trace is None else 100.0 * run.trace.idle_share()
