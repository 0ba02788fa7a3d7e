"""Share of the traced window in which no operation ran on the device,
in percent (the union of the device's op intervals, averaged over the
chips)."""


def read(run):
    return None if run.trace is None else run.trace.idle_share
