"""q6 (filter + one sum): share of the HBM roofline, bound by bytes:
the rows its scan delivers (the configuration's ``scan_rows``) times
the lanes it delivers, over the peak HBM rate and the class's device
time."""

from ._roofline import share_pct

CLASS = "q6"


def read(run):
    return share_pct(run, CLASS)
