"""TPC-DS q7 (four joins of store_sales, four averages by a varchar
key, TopN): share of the HBM roofline, bound by bytes, counted as
``tpcds_q96_hbm_roofline`` counts them: eight fact lanes and the lanes
of four dimensions, once."""

from ._roofline import share_pct

CLASS = "q7"


def read(run):
    return share_pct(run, CLASS)
