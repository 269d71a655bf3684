"""TPC-H q3 on one chip (``tpch_sf10_q3_q18.power``: the orders build
side at 2^23, the lineitem probe side at 2^25 lanes): share of the HBM
roofline, bound by bytes: the rows each scan delivers under its pushed
conjuncts (the configuration's ``scan_rows``) times the lanes it
delivers, over the peak HBM rate and the class's device time."""

from ._roofline import share_pct

CLASS = "q3"


def read(run):
    return share_pct(run, CLASS)
