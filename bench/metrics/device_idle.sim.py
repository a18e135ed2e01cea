"""Share of the traced window in which no operation ran on the device:
1 - (union of the device's operation intervals) / window, averaged over
the cell's devices."""


def read(run):
    t = run.trace
    if t is None or t["n_devices"] == 0:
        return None
    return 1.0 - t["busy_s"] / t["window_s"]
