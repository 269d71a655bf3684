"""TPC-H q18 at SF 10 (60M lineitem rows grouped into 15M orders, an
IN-subquery semi join, three joins): share of the HBM roofline, bound
by bytes: the rows each scan delivers (the configuration's
``scan_rows``: nothing of q18 is pushed) times the lanes it delivers,
lineitem counted ONCE though the plan scans it twice, over the peak HBM
rate and the class's device time. Far under 1% while the grouping is a
scatter and the joins are gathers over 2^26 lanes: the number says how
far the class is from one pass over its lanes."""

from ._roofline import share_pct

CLASS = "q18"


def read(run):
    return share_pct(run, CLASS)
