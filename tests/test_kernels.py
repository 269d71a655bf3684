"""Unit tests for the M0 kernel substrate (SURVEY.md §7 build order).

Modeled on the reference's operator unit tests
(core/trino-main/src/test/java/io/trino/operator/TestHashAggregationOperator
etc.), but asserting against plain-python recomputation.
"""

import numpy as np
import pytest

from trino_tpu.columnar import (Batch, Column, batch_from_pylist,
                                concat_batches)
from trino_tpu.ops.compact import filter_batch, limit_batch, offset_batch
from trino_tpu.ops.groupby import (AggInput, global_aggregate,
                                   group_aggregate)
from trino_tpu.ops import join as join_ops
from trino_tpu.ops.join import (cross_counts, expand_join, match_counts,
                                semi_join_mask)
from trino_tpu.ops.sort import SortKey, sort_batch, topn_batch
from trino_tpu.types import BIGINT, DOUBLE, INTEGER, VARCHAR, DecimalType

import jax.numpy as jnp


def make_batch():
    return batch_from_pylist(
        {
            "k": [1, 2, 1, 3, 2, 1, None, 3],
            "v": [10.0, 20.0, 30.0, None, 50.0, 60.0, 70.0, 80.0],
            "s": ["a", "b", "a", "c", None, "b", "a", "c"],
        },
        {"k": BIGINT, "v": DOUBLE, "s": VARCHAR},
    )


def test_pylist_roundtrip():
    b = make_batch()
    rows = b.to_pylist()
    assert rows[0] == [1, 10.0, "a"]
    assert rows[6] == [None, 70.0, "a"]
    assert len(rows) == 8


def test_filter_compacts():
    b = make_batch()
    k = jnp.asarray(b.column("k").data)
    kv = b.column("k").valid_mask()
    out = filter_batch(b, (k == 1) & kv)
    rows = out.to_pylist()
    assert rows == [[1, 10.0, "a"], [1, 30.0, "a"], [1, 60.0, "b"]]


def test_limit_offset():
    b = make_batch()
    assert len(limit_batch(b, 3).to_pylist()) == 3
    rows = offset_batch(b, 6).to_pylist()
    assert len(rows) == 2
    assert rows[0][1] == 70.0


def test_group_aggregate_sum_count_min_max():
    b = make_batch()
    out = group_aggregate(
        b, ["k"],
        [AggInput("sum", "v", output="sv"),
         AggInput("count", "v", output="cv"),
         AggInput("count_star", output="cs"),
         AggInput("min", "v", output="mn"),
         AggInput("max", "v", output="mx")])
    rows = {r[0]: r[1:] for r in out.to_pylist()}
    assert len(rows) == 4  # 1, 2, 3, NULL
    assert rows[1] == [100.0, 3, 3, 10.0, 60.0]
    assert rows[2] == [70.0, 2, 2, 20.0, 50.0]
    assert rows[3] == [80.0, 1, 2, 80.0, 80.0]  # one NULL v in group 3
    assert rows[None] == [70.0, 1, 1, 70.0, 70.0]


def test_group_by_string_key():
    b = make_batch()
    out = group_aggregate(b, ["s"], [AggInput("count_star", output="c")])
    rows = {r[0]: r[1] for r in out.to_pylist()}
    assert rows == {"a": 3, "b": 2, "c": 2, None: 1}


def test_group_by_multi_key():
    b = make_batch()
    out = group_aggregate(b, ["k", "s"],
                          [AggInput("count_star", output="c")])
    rows = {(r[0], r[1]): r[2] for r in out.to_pylist()}
    assert rows[(1, "a")] == 2
    assert rows[(1, "b")] == 1
    assert rows[(None, "a")] == 1


def test_global_aggregate():
    b = make_batch()
    out = global_aggregate(
        b, [AggInput("sum", "v", output="s"),
            AggInput("count", "k", output="c"),
            AggInput("count_star", output="cs"),
            AggInput("min", "v", output="mn")])
    assert out.to_pylist() == [[320.0, 7, 8, 10.0]]


def test_global_aggregate_empty():
    b = batch_from_pylist({"v": []}, {"v": DOUBLE})
    out = global_aggregate(b, [AggInput("sum", "v", output="s"),
                               AggInput("count", "v", output="c")])
    assert out.to_pylist() == [[None, 0]]


def test_sort_and_nulls():
    b = make_batch()
    out = sort_batch(b, [SortKey("v", ascending=False)])
    vals = [r[1] for r in out.to_pylist()]
    assert vals == [None, 80.0, 70.0, 60.0, 50.0, 30.0, 20.0, 10.0]
    out2 = sort_batch(b, [SortKey("v", ascending=True)])
    vals2 = [r[1] for r in out2.to_pylist()]
    assert vals2 == [10.0, 20.0, 30.0, 50.0, 60.0, 70.0, 80.0, None]


def test_sort_string_and_multikey():
    b = make_batch()
    out = sort_batch(b, [SortKey("s"), SortKey("v", ascending=False)])
    rows = out.to_pylist()
    assert [r[2] for r in rows[:3]] == ["a", "a", "a"]
    assert [r[1] for r in rows[:3]] == [70.0, 30.0, 10.0]
    assert rows[-1][2] is None  # nulls last


def test_topn():
    b = make_batch()
    out = topn_batch(b, [SortKey("v", ascending=False,
                                 nulls_first=False)], 2)
    assert [r[1] for r in out.to_pylist()] == [80.0, 70.0]


@pytest.mark.parametrize("n", [1, 7, 64, 65, 500])
def test_topn_selection_equals_full_sort(n):
    """Small LIMITs are selected, large ones sorted (ops/sort.py
    TOPN_SELECT_MAX): both must give the rows of the stable full sort,
    in its order — ties, NULLs, NaN, DESC and dead rows included."""
    rng = np.random.default_rng(n)
    rows = 300
    v = rng.integers(0, 12, rows).astype(float)      # many ties
    v[rng.integers(0, rows, 10)] = float("nan")
    vs = [None if i % 17 == 0 else float(x) for i, x in enumerate(v)]
    k = [int(x) for x in rng.integers(-3, 3, rows)]
    s = [None if i % 29 == 0 else "abcdef"[x]
         for i, x in enumerate(rng.integers(0, 6, rows))]
    b = batch_from_pylist({"v": vs, "k": k, "s": s, "id": list(range(rows))},
                          {"v": DOUBLE, "k": BIGINT, "s": VARCHAR,
                           "id": BIGINT})
    keys = [SortKey("v", ascending=False), SortKey("s"),
            SortKey("k", ascending=False, nulls_first=True)]
    want = [r[3] for r in sort_batch(b, keys).to_pylist()][:n]
    got = [r[3] for r in topn_batch(b, keys, n).to_pylist()]
    assert got == want


def _join(probe, build, pk, bk, join_type="inner", prefix="b_"):
    start, count, order = match_counts(probe, build, pk, bk)
    total = int(jnp.maximum(count, 1).sum()) if join_type == "left" \
        else int(count.sum())
    cap = max(8, 1 << max(0, (total - 1).bit_length()))
    return expand_join(probe, build, start, count, order, cap,
                       join_type, prefix)


def _keys(cap, keys, null_at=(), rows=None, more=None):
    """A one- or two-key BIGINT batch of ``cap`` rows' capacity."""
    def col(vals):
        data = np.zeros(cap, np.int64)
        data[:len(vals)] = vals
        valid = None
        if len(null_at):
            valid = np.ones(cap, bool)
            valid[list(null_at)] = False
        return Column(BIGINT, jnp.asarray(data),
                      None if valid is None else jnp.asarray(valid))
    cols = {"k": col(keys)}
    if more is not None:
        cols["k2"] = col(more)
    return Batch(cols, len(keys) if rows is None else rows)


def _probe_unique(rng):
    # 2^16 distinct keys under a bijective hash: a uniform lane, so the
    # directory leaves a handful of entries a bucket
    cap = 1 << 16
    build = rng.permutation(1 << 20)[:cap]
    return (_keys(cap, rng.choice(build, cap)), _keys(cap, build),
            lambda steps, m: steps <= 6)


def _probe_one_key(rng):
    # every build row the same key: one bucket holds them all and the
    # search degrades to the full bisection, still exact
    cap = 1 << 10
    return (_keys(cap, rng.integers(5, 9, cap)),
            _keys(cap, np.full(cap, 7)),
            lambda steps, m: steps == 11)      # log2(cap) + 1


def _probe_lineitem(rng):
    # a build side with 1-7 rows a key, probed by its own distinct keys
    orders = rng.permutation(1 << 16)[:3000]
    build = np.repeat(orders, rng.integers(1, 8, orders.size))
    return (_keys(1 << 12, orders), _keys(1 << 14, build[:1 << 14]),
            lambda steps, m: 3 <= steps <= 7)


def _probe_dead_build(rng):
    return (_keys(64, rng.integers(0, 50, 64)), _keys(32, [], rows=0),
            lambda steps, m: steps == 0 and m == 0)


def _probe_null_keys(rng):
    return (_keys(64, rng.integers(0, 40, 60), null_at=(0, 7, 59)),
            _keys(128, rng.integers(0, 40, 100), null_at=(3, 4, 99)),
            lambda steps, m: m == 97)


def _probe_absent_keys(rng):
    return (_keys(256, rng.integers(1000, 2000, 256)),
            _keys(256, rng.integers(0, 1000, 200)),
            lambda steps, m: steps <= 4)


def _probe_smaller(rng):
    return (_keys(8, rng.integers(0, 300, 8)),
            _keys(1 << 12, rng.integers(0, 300, 4000)),
            lambda steps, m: m == 4000)


def _probe_larger(rng):
    return (_keys(1 << 14, rng.integers(0, 300, 1 << 14)),
            _keys(16, rng.integers(0, 300, 11)),
            lambda steps, m: steps <= 4)


def _probe_u64max_lane(rng):
    # key 0 becomes the lane U64MAX, which dead build rows carry too:
    # they must count into no run (see the patched mix64 below)
    return (_keys(32, [0, 1, 2, 0, 5]), _keys(32, [0, 3, 0, 1, 0, 2]),
            lambda steps, m: m == 6)


def _probe_constant_hash(rng):
    # two key columns under a combined hash that is ONE value: every
    # row's lane is equal, each probe row counts the whole build side
    a, b = rng.integers(0, 9, (2, 100))
    return (_keys(64, a[:50], more=b[:50]), _keys(128, a, more=b),
            lambda steps, m: steps == 7)       # bit_length(100)


@pytest.mark.parametrize("case", [
    _probe_unique, _probe_one_key, _probe_lineitem, _probe_dead_build,
    _probe_null_keys, _probe_absent_keys, _probe_smaller, _probe_larger,
    _probe_u64max_lane, _probe_constant_hash],
    ids=lambda c: c.__name__[7:])
def test_join_probe_equals_searchsorted(case, monkeypatch):
    """The probe (bucket directory, bounded bisection, run lengths)
    against numpy: ``left`` and ``count`` are what ``searchsorted``
    left and right give on the sorted usable build lanes, whatever the
    lane's distribution; the steps counter keeps its bound."""
    if case is _probe_u64max_lane:
        monkeypatch.setattr(
            join_ops, "mix64", lambda x: ~jnp.asarray(x).astype(jnp.uint64))
    if case is _probe_constant_hash:
        monkeypatch.setattr(join_ops, "combine_hashes",
                            lambda hs: jnp.zeros_like(hs[0]) + 7)
    probe, build, steps_ok = case(np.random.default_rng(27))
    keys = list(build.columns)
    left, count, side = join_ops.match_runs(probe, build, keys, keys)
    start, count2, order = match_counts(probe, build, keys, keys)

    lane_b, usable_b = map(np.asarray, join_ops.equality_lane(build, keys))
    lane_p, usable_p = map(np.asarray, join_ops.equality_lane(probe, keys))
    m = int(usable_b.sum())
    want_sorted = np.full(build.capacity, np.uint64(2**64 - 1))
    want_sorted[:m] = np.sort(lane_b[usable_b])
    lo = np.minimum(np.searchsorted(want_sorted, lane_p, "left"), m)
    hi = np.minimum(np.searchsorted(want_sorted, lane_p, "right"), m)

    assert int(side.m) == m
    assert np.array_equal(np.asarray(side.sorted_lane), want_sorted)
    assert np.array_equal(lane_b[np.asarray(order)[:m]], want_sorted[:m])
    assert np.array_equal(np.asarray(left), lo)
    assert np.array_equal(np.asarray(count), np.where(usable_p, hi - lo, 0))
    assert np.array_equal(np.asarray(start), lo)
    assert np.array_equal(np.asarray(count2), np.asarray(count))
    assert left.dtype == count.dtype == jnp.int64
    assert steps_ok(int(side.steps), m), (int(side.steps), m)
    if case is _probe_u64max_lane:
        assert list(np.asarray(count)[:5]) == [3, 1, 1, 3, 0]


def test_inner_join():
    probe = batch_from_pylist({"k": [1, 2, 3, None, 5]},
                              {"k": BIGINT})
    build = batch_from_pylist({"k": [1, 1, 2, None], "w": [7, 8, 9, 10]},
                              {"k": BIGINT, "w": BIGINT})
    out = _join(probe, build, ["k"], ["k"])
    rows = sorted(map(tuple, out.to_pylist()))
    assert rows == [(1, 1, 7), (1, 1, 8), (2, 2, 9)]


def test_left_join():
    probe = batch_from_pylist({"k": [1, 3, None]}, {"k": BIGINT})
    build = batch_from_pylist({"k": [1, 2], "w": [7, 9]},
                              {"k": BIGINT, "w": BIGINT})
    out = _join(probe, build, ["k"], ["k"], "left")
    rows = sorted(map(tuple, out.to_pylist()),
                  key=lambda r: (r[0] is None, r))
    assert rows == [(1, 1, 7), (3, None, None), (None, None, None)]


def test_multikey_join():
    probe = batch_from_pylist({"a": [1, 1, 2], "b": [10, 11, 10]},
                              {"a": BIGINT, "b": BIGINT})
    build = batch_from_pylist({"a": [1, 2], "b": [10, 10],
                               "w": [100, 200]},
                              {"a": BIGINT, "b": BIGINT, "w": BIGINT})
    out = _join(probe, build, ["a", "b"], ["a", "b"])
    rows = sorted(map(tuple, out.to_pylist()))
    assert rows == [(1, 10, 1, 10, 100), (2, 10, 2, 10, 200)]


def test_semi_join_mask():
    probe = batch_from_pylist({"k": [1, 2, None]}, {"k": BIGINT})
    build = batch_from_pylist({"k": [1, None]}, {"k": BIGINT})
    matched, key_null, has_null, nonempty = semi_join_mask(
        probe, build, ["k"], ["k"])
    assert list(np.asarray(matched)[:3]) == [True, False, False]
    assert list(np.asarray(key_null)[:3]) == [False, False, True]
    assert bool(has_null) and bool(nonempty)


def test_cross_join():
    probe = batch_from_pylist({"a": [1, 2]}, {"a": BIGINT})
    build = batch_from_pylist({"b": [10, 20, 30]}, {"b": BIGINT})
    start, count, order = cross_counts(probe, build)
    out = expand_join(probe, build, start, count, order, 8, "inner", "")
    rows = sorted(map(tuple, out.to_pylist()))
    assert len(rows) == 6
    assert rows[0] == (1, 10)


def test_concat_batches_merges_dictionaries():
    b1 = batch_from_pylist({"s": ["x", "y"]}, {"s": VARCHAR})
    b2 = batch_from_pylist({"s": ["y", "z"]}, {"s": VARCHAR})
    out = concat_batches([b1, b2])
    assert [r[0] for r in out.to_pylist()] == ["x", "y", "y", "z"]


def test_decimal_column():
    b = batch_from_pylist({"d": [1.25, 2.50, None]},
                          {"d": DecimalType(10, 2)})
    import decimal
    assert b.to_pylist() == [[decimal.Decimal("1.25")], [decimal.Decimal("2.5")], [None]]


def test_decimal_half_up_rounding():
    # 1.115 * 100 == 111.4999... in binary floats; must store 112
    b = batch_from_pylist({"d": [1.115]}, {"d": DecimalType(10, 2)})
    import decimal
    assert b.to_pylist() == [[decimal.Decimal("1.12")]]


def test_string_join_across_dictionaries():
    probe = batch_from_pylist({"s": ["a", "b"]}, {"s": VARCHAR})
    build = batch_from_pylist({"s": ["b", "c"], "w": [1, 2]},
                              {"s": VARCHAR, "w": BIGINT})
    out = _join(probe, build, ["s"], ["s"], prefix="b_")
    assert out.to_pylist() == [["b", "b", 1]]


def test_string_min_max_uses_collation():
    b = batch_from_pylist({"g": [1, 1], "s": ["b", "a"]},
                          {"g": BIGINT, "s": VARCHAR})
    out = group_aggregate(b, ["g"], [AggInput("min", "s", output="mn"),
                                     AggInput("max", "s", output="mx")])
    assert out.to_pylist() == [[1, "a", "b"]]
    gout = global_aggregate(b, [AggInput("min", "s", output="mn")])
    assert gout.to_pylist() == [["a"]]


def test_long_decimal_int128_roundtrip():
    import decimal
    from trino_tpu.columnar import concat_batches
    big = 12345678901234567890123456789
    b1 = batch_from_pylist({"d": [big, -big]}, {"d": DecimalType(38, 0)})
    assert b1.to_pylist() == [[big], [-big]]
    b2 = batch_from_pylist({"d": [5]}, {"d": DecimalType(38, 0)})
    assert concat_batches([b1, b2]).to_pylist() == [[big], [-big], [5]]
    d = batch_from_pylist({"d": ["12345678901234567.89"]},
                          {"d": DecimalType(38, 2)})
    assert d.to_pylist()[0][0] == decimal.Decimal("12345678901234567.89")


def test_grouped_any_value_skips_nulls():
    from trino_tpu.ops.groupby import AggInput, group_aggregate
    b = batch_from_pylist({"k": [1, 1, 2], "x": [None, 7.0, None]},
                          {"k": BIGINT, "x": DOUBLE})
    out = group_aggregate(b, ["k"],
                          [AggInput("any_value", "x", output="a")])
    assert out.to_pylist() == [[1, 7.0], [2, None]]
