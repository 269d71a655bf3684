"""Geomean, rate and percentile on synthetic latencies, with one stall:
all three must move."""

import math

import pytest

from harness import stats
from harness.stats import Record


def window(stall_ms=0.0, streams=1):
    """20 cycles of q1 (50 ms), q3 (1000 ms), q6 (10 ms) per stream, back
    to back; the 7th q6 of stream 0 stalls by ``stall_ms``."""
    out = []
    for s in range(streams):
        t = 100.0
        for cycle in range(20):
            for cls, ms in (("q1", 50.0), ("q3", 1000.0), ("q6", 10.0)):
                if cls == "q6" and cycle == 6 and s == 0:
                    ms += stall_ms
                out.append(Record(cls, s, t, t + ms / 1000.0, True))
                t += ms / 1000.0
    return out


def test_values_without_stall():
    recs = window()
    assert stats.class_means_ms(recs) == pytest.approx(
        {"q1": 50.0, "q3": 1000.0, "q6": 10.0})
    assert stats.geomean_ms(recs) == pytest.approx(
        (50.0 * 1000.0 * 10.0) ** (1 / 3))
    assert stats.rate_per_s(recs, 100.0) == pytest.approx(60 / 21.2)
    # 60 latencies, 20 of each: the 95th percentile lies in the 1000s
    assert stats.percentile_ms(recs, 95) == pytest.approx(1000.0)
    assert stats.percentile_ms(recs, 50) == pytest.approx(50.0)


def test_one_stall_moves_all_three():
    calm, stalled = window(), window(stall_ms=5000.0)
    assert stats.geomean_ms(stalled) > 1.5 * stats.geomean_ms(calm)
    assert stats.rate_per_s(stalled, 100.0) < \
        0.85 * stats.rate_per_s(calm, 100.0)
    assert stats.percentile_ms(stalled, 99) > \
        2 * stats.percentile_ms(calm, 99)
    # ... and a 2x on the short class weighs as a 2x on the long one
    def scaled(cls):
        return [Record(r.cls, r.stream, r.start_s,
                       r.start_s + (2 if r.cls == cls else 1)
                       * (r.end_s - r.start_s), True) for r in calm]
    assert stats.geomean_ms(scaled("q6")) == pytest.approx(
        stats.geomean_ms(scaled("q3")))


def test_class_p50_holds_under_one_stall_and_moves_with_the_class():
    calm, stalled = window(), window(stall_ms=5000.0)
    assert stats.class_p50_ms(calm) == pytest.approx(
        {"q1": 50.0, "q3": 1000.0, "q6": 10.0})
    # the stall is the mean's and the rate's to carry, not the median's
    assert stats.class_p50_ms(stalled) == pytest.approx(
        stats.class_p50_ms(calm))
    assert stats.class_means_ms(stalled)["q6"] > 20 * 10.0
    doubled = [Record(r.cls, r.stream, r.start_s,
                      r.start_s + (2 if r.cls == "q6" else 1)
                      * (r.end_s - r.start_s), True) for r in calm]
    assert stats.class_p50_ms(doubled)["q6"] == pytest.approx(20.0)
    assert stats.class_p50_ms([]) == {}


def test_failed_queries_have_no_latency_and_lower_the_rate():
    recs = window()
    recs[5].ok = False
    assert len(stats.finished(recs)) == 59
    assert stats.rate_per_s(recs, 100.0) == pytest.approx(59 / 21.2)
    assert stats.geomean_ms([]) is None
    assert stats.percentile_ms([], 95) is None


def test_streams_sum():
    two = window(streams=2)
    assert stats.rate_per_s(two, 100.0) == pytest.approx(2 * 60 / 21.2)


def test_open_loop_latency_counts_from_due_time():
    r = Record("q6", 0, 10.0, 10.5, True, due_s=9.0)
    assert r.latency_ms == pytest.approx(1500.0)
    assert math.isclose(Record("q6", 0, 10.0, 10.5, True).latency_ms, 500.0)
