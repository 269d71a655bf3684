"""The roofline's byte function against a hand count, and the table of
peaks: an unknown device kind is an error, never a default."""

import json
import os

import pytest

from harness import roofline

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def config(name):
    with open(os.path.join(HERE, "configs", f"{name}.json")) as f:
        return json.load(f)


def test_q6_bytes_by_hand_sf1():
    # q6 reads l_quantity, l_extendedprice, l_discount (DOUBLE, 8 each)
    # and l_shipdate (DATE, 4) of 6,002,677 live lineitem rows
    assert roofline.query_bytes(config("tpch_sf1_1chip"), "q6") == \
        6_002_677 * (8 + 8 + 8 + 4)
    assert roofline.query_bytes(config("tpch_sf1_1chip"), "q6") == 168_074_956


def test_q1_and_q3_bytes_by_hand_sf1():
    c = config("tpch_sf1_1chip")
    # q1: four DOUBLE lanes, a DATE, two dictionary codes
    assert roofline.query_bytes(c, "q1") == 6_002_677 * (4 * 8 + 4 + 4 + 4)
    # q3: lineitem key + 2 DOUBLE + DATE; orders 2 keys + DATE + INTEGER;
    # customer key + dictionary code
    assert roofline.query_bytes(c, "q3") == (
        6_002_677 * (8 + 8 + 8 + 4) + 1_500_000 * (8 + 8 + 4 + 4)
        + 150_000 * (8 + 4))


def test_least_seconds_v5e():
    c = config("tpch_sf1_1chip")
    peaks = roofline.peaks_for("TPU v5 lite")
    assert peaks["hbm_gb_per_s"] == 819
    assert roofline.least_seconds(c, "q6", peaks) == pytest.approx(
        168_074_956 / 819e9)


def test_sf10_counts_ten_times_the_rows():
    c = config("tpch_sf10_1chip")
    assert roofline.query_bytes(c, "q6") == 60_007_494 * 28
    assert "q1" not in c["lanes_read"]


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no peaks for device kind"):
        roofline.peaks_for("TPU v9 imaginary")
    with pytest.raises(KeyError):
        roofline.peaks_for("cpu")
