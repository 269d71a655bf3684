"""Device: 1 - union of device-operation intervals over the traced
window, averaged over the chips used. A traced window in which no
operation ran on the device reads 100; only an untraced run has nothing
to read."""


def read(run):
    if run.trace_busy_s is None:
        return None
    lo, hi = run.trace_window
    return 100.0 * (1.0 - run.trace_busy_s / ((hi - lo) / 1e9))
