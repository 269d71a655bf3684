"""TPC-DS q96 (count(*) over three joins of store_sales): share of the
HBM roofline, bound by bytes: the rows each scan delivers (the
configuration's ``scan_rows``: nothing is pushed into a tpcds scan)
times the lanes it delivers, over the peak HBM rate and the class's
device time. Far under 1% while the joins are gathers and scatters over
2^25 lanes: the number says how far the star chain is from one pass
over its lanes."""

from ._roofline import share_pct

CLASS = "q96"


def read(run):
    return share_pct(run, CLASS)
