"""The roofline's byte function against a hand count (the rows the scan
delivers, ``scan_rows``, times the lanes it delivers; the table's rows
where a configuration has no ``scan_rows``), and the table of peaks: an
unknown device kind is an error, never a default."""

import json
import os

import pytest

from harness import roofline

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def config(name):
    with open(os.path.join(HERE, "configs", f"{name}.json")) as f:
        return json.load(f)


def test_q6_bytes_by_hand_sf1():
    # the scan delivers the 1,992,515 lineitem rows that `l_shipdate >=
    # 1994-01-01 and l_quantity < 24` leave, in three lanes:
    # l_extendedprice, l_discount (DOUBLE, 8 each), l_shipdate (DATE, 4);
    # l_quantity is consumed by the pushed conjunct
    assert roofline.query_bytes(config("tpch_sf1_1chip"), "q6") == \
        1_992_515 * (8 + 8 + 4)
    assert roofline.query_bytes(config("tpch_sf1_1chip"), "q6") == 39_850_300


def test_q1_and_q3_bytes_by_hand_sf1():
    c = config("tpch_sf1_1chip")
    # q1: nothing pushed, the table: four DOUBLE lanes, a DATE, two
    # dictionary codes
    assert roofline.query_bytes(c, "q1") == 6_002_677 * (4 * 8 + 4 + 4 + 4)
    # q3: lineitem after `l_shipdate > 1995-03-15`: key + 2 DOUBLE;
    # orders before that date: 2 keys + DATE + INTEGER; customer whole:
    # key + dictionary code
    assert roofline.query_bytes(c, "q3") == (
        3_232_552 * (8 + 8 + 8) + 729_205 * (8 + 8 + 4 + 4)
        + 150_000 * (8 + 4))


def test_without_scan_rows_the_table_s_rows_count():
    c = config("tpch_sf1_1chip")
    del c["scan_rows"]
    assert roofline.query_bytes(c, "q6") == 6_002_677 * (8 + 8 + 4)
    c["scan_rows"] = {"q3": {"orders": {"rows": 10, "pushed": "x"}}}
    assert roofline.query_bytes(c, "q6") == 6_002_677 * (8 + 8 + 4)
    assert roofline.query_bytes(c, "q3") == (
        6_002_677 * 24 + 10 * 24 + 150_000 * 12)


def test_least_seconds_v5e():
    c = config("tpch_sf1_1chip")
    peaks = roofline.peaks_for("TPU v5 lite")
    assert peaks["hbm_gb_per_s"] == 819
    assert roofline.least_seconds(c, "q6", peaks) == pytest.approx(
        39_850_300 / 819e9)


def test_sf10_counts_what_its_scan_delivers():
    c = config("tpch_sf10_1chip")
    assert roofline.query_bytes(c, "q6") == 19_928_602 * 20
    assert "q1" not in c["lanes_read"]


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no peaks for device kind"):
        roofline.peaks_for("TPU v9 imaginary")
    with pytest.raises(KeyError):
        roofline.peaks_for("cpu")
