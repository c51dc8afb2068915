"""Share of the traced window in which no operation ran on the device (%):
1 − (union of device-busy intervals) / window."""


def read(run):
    red = run.reduced
    return 100.0 * (1.0 - red.busy_s / red.window_s)
