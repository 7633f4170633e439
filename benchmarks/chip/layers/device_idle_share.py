"""Share of the traced window in which no operation ran on the device:
1 - (union of device-busy intervals / window), averaged over the chips."""


def read(run):
    busy, win = run.trace.busy_s(), run.trace.window_s()
    return None if win <= 0 else 100.0 * (1.0 - busy / win)
