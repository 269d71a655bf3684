"""The trace reduction: busy union, per-query attribution, idle gaps,
top operations. First on a hand-made trace whose numbers can be counted
on paper, then on ``recorded_trace.json``, a few queries cut from a real
run on the v5e (``run.py --trace 1 --dump-trace``)."""

import json
import os

import pytest

from harness import trace as tr

MS = 1_000_000


def hand_made():
    """One device. q6 from 0 to 10 ms, q3 from 12 to 40 ms.
    Ops: fusion.1 1-3, fusion.2 2-5 (overlaps: union 1-5 = 4 ms),
    sort.1 14-24, fusion.1 30-31, and copy.1 50-51 (outside)."""
    ops = [("fusion.1", 1 * MS, 2 * MS), ("fusion.2", 2 * MS, 3 * MS),
           ("sort.1", 14 * MS, 10 * MS), ("fusion.1", 30 * MS, 1 * MS),
           ("copy.1", 50 * MS, 1 * MS)]
    host = [("bench_anchor", 0, 1000),
            ("bench_query:q6:0", 0, 10 * MS),
            ("bench_query:q3:0", 12 * MS, 28 * MS)]
    return {"devices": {"/device:TPU:0": ops}, "host": host, "lines": {}}


def test_union_merges_overlaps():
    assert tr.union([(1, 3), (2, 5), (7, 8), (8, 9), (4, 4)]) == \
        [[1, 5], [7, 9]]
    assert tr.total(tr.clip([[1, 5], [7, 9]], 2, 8)) == 4


def test_window_and_busy():
    t = hand_made()
    lo, hi = tr.window_of(t)
    assert (lo, hi) == (0, 40 * MS)
    busy_s, merged = tr.device_busy(t, lo, hi)
    assert busy_s == pytest.approx((4 + 10 + 1) / 1000)
    assert merged == [[1 * MS, 5 * MS], [14 * MS, 24 * MS],
                      [30 * MS, 31 * MS]]


def test_busy_per_query():
    per = tr.busy_per_query(hand_made())
    assert per == {"q6": [4 * MS], "q3": [11 * MS]}


def test_top_ops_sums_by_name_inside_window():
    ops = tr.top_ops(hand_made(), 0, 40 * MS)
    assert ops[0] == ["sort.1", pytest.approx(0.010)]
    assert dict(map(tuple, ops))["fusion.1"] == pytest.approx(0.003)
    assert "copy.1" not in dict(map(tuple, ops))


def test_idle_gaps_labelled_and_summed():
    t = hand_made()
    qs = tr.queries(t)

    def label(s, e):
        mid = (s + e) // 2
        return next((q[0] for q in qs if q[2] <= mid < q[3]), "between")
    gaps = dict(map(tuple, tr.idle_gaps(t, 0, 40 * MS, label)))
    # q6: 0-1 and 5-10 (the gap 5-14 has its middle at 9.5: q6's);
    # q3: 24-30 and 31-40
    assert gaps["q6"] == pytest.approx((1 + 9) / 1000)
    assert gaps["q3"] == pytest.approx((6 + 9) / 1000)
    assert sum(gaps.values()) + 0.015 == pytest.approx(0.040)


def test_idle_gaps_split_at_cuts():
    t = hand_made()
    qs = tr.queries(t)

    def label(s, e):
        mid = (s + e) // 2
        return next((q[0] for q in qs if q[2] <= mid < q[3]), "between")
    cuts = [x for q in qs for x in q[2:4]]
    gaps = dict(map(tuple, tr.idle_gaps(t, 0, 40 * MS, label, cuts=cuts)))
    # the gap 5-14 is now q6 5-10, between 10-12, q3 12-14
    assert gaps["q6"] == pytest.approx((1 + 5) / 1000)
    assert gaps["between"] == pytest.approx(2 / 1000)
    assert gaps["q3"] == pytest.approx((2 + 6 + 9) / 1000)


def test_two_devices_average():
    t = hand_made()
    t["devices"]["/device:TPU:1"] = [("fusion.9", 0, 40 * MS)]
    busy_s, _ = tr.device_busy(t, 0, 40 * MS)
    assert busy_s == pytest.approx((0.015 + 0.040) / 2)


def test_no_annotation_is_an_error():
    with pytest.raises(ValueError):
        tr.window_of({"devices": {}, "host": [], "lines": {}})


RECORDED = os.path.join(os.path.dirname(__file__), "recorded_trace.json")


@pytest.mark.skipif(not os.path.exists(RECORDED),
                    reason="no recorded trace in this checkout")
def test_recorded_trace_from_the_chip():
    with open(RECORDED) as f:
        doc = json.load(f)
    t, want = doc["trace"], doc["expected"]
    t["host"] = [tuple(e) for e in t["host"]]
    lo, hi = tr.window_of(t)
    busy_s, merged = tr.device_busy(t, lo, hi)
    assert 0 < busy_s <= (hi - lo) / 1e9
    assert busy_s == pytest.approx(want["busy_s"])
    assert (hi - lo) / 1e9 == pytest.approx(want["window_s"])
    per = tr.busy_per_query(t)
    assert {c: len(v) for c, v in per.items()} == want["queries"]
    for cls, ms in want["busy_ms_per_class"].items():
        assert [ns / 1e6 for ns in per[cls]] == pytest.approx(ms)
    # q6's busy time by hand: the union of the operations that start
    # inside its span (none straddles its edges)
    _c, _s, a, b = [q for q in tr.queries(t) if q[0] == "q6"][0]
    inside = [(s, s + d) for _n, s, d in t["devices"]["/device:TPU:0"]
              if a <= s < b]
    assert tr.total(tr.union(inside)) == per["q6"][0]
    # a query's busy time lies inside its own span, and the queries'
    # busy times add up to the window's (one stream, no overlap)
    for cls, _s, a, b in tr.queries(t):
        assert a < b
    assert sum(sum(v) for v in per.values()) / 1e9 == \
        pytest.approx(busy_s, rel=1e-6)
    idle = sum(s for _l, s in tr.idle_gaps(t, lo, hi, lambda s, e: "x",
                                           n=1))
    assert idle + busy_s == pytest.approx((hi - lo) / 1e9)


def test_idle_reads_100_when_the_device_showed_no_work():
    """A path that fell off the device must show, not vanish."""
    from types import SimpleNamespace
    from layer_metrics import device_idle_pct
    run = SimpleNamespace(trace_busy_s=0.0, trace_window=(0, 30 * 10**9))
    assert device_idle_pct.read(run) == 100.0
    run.trace_busy_s = 7.5
    assert device_idle_pct.read(run) == pytest.approx(75.0)
    run.trace_busy_s = None         # untraced run: nothing to read
    assert device_idle_pct.read(run) is None
