"""Share of the traced window in which no operation ran on the device:
100 * (1 - union of device op intervals / window)."""


def read(run):
    return run.trace.idle_pct()
