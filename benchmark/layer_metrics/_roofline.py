from harness.roofline import least_seconds

from ._busy import class_busy_ms


def share_pct(run, cls):
    """Least time of the class (bytes it must read over the peak HBM
    rate) over its mean device-busy time, %. Nothing where the class did
    not run, the device showed no work, or the chip has no peaks."""
    busy_ms = class_busy_ms(run).get(cls)
    if not busy_ms or run.peaks is None:
        return None
    return 100.0 * least_seconds(run.config, cls, run.peaks) * 1e3 / busy_ms
