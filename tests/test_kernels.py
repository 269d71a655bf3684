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
from trino_tpu.types import (BIGINT, DATE, DOUBLE, INTEGER, VARCHAR,
                             DecimalType)

import jax
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


def _keys(cap, keys, null_at=(), rows=None, more=None, typ=BIGINT):
    """A one- or two-key batch of ``cap`` rows' capacity (BIGINT, or
    another integer-laned type)."""
    def col(vals):
        data = np.zeros(cap, np.int32 if typ is DATE else np.int64)
        data[:len(vals)] = vals
        valid = None
        if len(null_at):
            valid = np.ones(cap, bool)
            valid[list(null_at)] = False
        return Column(typ, jnp.asarray(data),
                      None if valid is None else jnp.asarray(valid))
    cols = {"k": col(keys)}
    if more is not None:
        cols["k2"] = col(more)
    return Batch(cols, len(keys) if rows is None else rows)


_I64 = np.iinfo(np.int64)
# a stride that takes small keys out of every directory's reach (at
# most 2^26 buckets): the cases that assert a step count stay hashed
_FAR = 1 << 30


def _probe_unique(rng):
    # 2^16 distinct keys under a bijective hash: a uniform lane, so the
    # directory leaves a handful of entries a bucket
    cap = 1 << 16
    build = rng.permutation(1 << 20)[:cap] * _FAR
    return (_keys(cap, rng.choice(build, cap)), _keys(cap, build),
            lambda steps, m, exact, packed:
            packed and not exact and 0 < steps <= 6)


def _probe_one_key(rng):
    # every build row but one the same key (the other keeps the range
    # out of the directory's reach): one bucket holds them all and the
    # search degrades to the full bisection, still exact
    cap = 1 << 10
    return (_keys(cap, rng.integers(5, 9, cap)),
            _keys(2 * cap, np.append(np.full(cap, 7), 7 + _FAR)),
            lambda steps, m, exact, packed:  # log2 + 1
            packed and not exact and steps == 11)


def _probe_lineitem(rng):
    # a build side with 1-7 rows a key, probed by its own distinct keys
    orders = rng.permutation(1 << 16)[:3000] * _FAR
    build = np.repeat(orders, rng.integers(1, 8, orders.size))
    return (_keys(1 << 12, orders), _keys(1 << 14, build[:1 << 14]),
            lambda steps, m, exact, packed:
            packed and not exact and 3 <= steps <= 7)


def _probe_dead_build(rng):
    return (_keys(64, rng.integers(0, 50, 64)), _keys(32, [], rows=0),
            lambda steps, m, exact, packed:
            packed and not exact and steps == 0 and m == 0)


def _probe_null_keys(rng):
    return (_keys(64, rng.integers(0, 40, 60) * _FAR, null_at=(0, 7, 59)),
            _keys(128, rng.integers(0, 40, 100) * _FAR,
                  null_at=(3, 4, 99)),
            lambda steps, m, exact, packed:
            packed and not exact and m == 97 and steps > 0)


def _probe_absent_keys(rng):
    return (_keys(256, rng.integers(1000, 2000, 256) * _FAR),
            _keys(256, rng.integers(0, 1000, 200) * _FAR),
            lambda steps, m, exact, packed:
            packed and not exact and 0 < steps <= 4)


def _probe_smaller(rng):
    return (_keys(8, rng.integers(0, 300, 8)),
            _keys(1 << 12, rng.integers(0, 300, 4000)),
            lambda steps, m, exact, packed: packed and exact and m == 4000)


def _probe_larger(rng):
    return (_keys(1 << 14, rng.integers(0, 300, 1 << 14)),
            _keys(16, rng.integers(0, 300, 11)),
            lambda steps, m, exact, packed: packed and exact and steps == 0)


def _probe_u64max_lane(rng):
    # key 0 becomes the lane U64MAX, which dead build rows carry too:
    # they must count into no run (see the patched mix64 below)
    return (_keys(32, [0, 1, 2, 0, 5]),
            _keys(32, [0, 3, 0, 1, 0, 2, _FAR]),
            lambda steps, m, exact, packed: packed and not exact and m == 7)


def _probe_constant_hash(rng):
    # two key columns under a combined hash that is ONE value: every
    # row's lane is equal, each probe row counts the whole build side
    a, b = rng.integers(0, 9, (2, 100))
    return (_keys(64, a[:50], more=b[:50]), _keys(128, a, more=b),
            # bit_length(100)
            lambda steps, m, exact, packed:
            packed and not exact and steps == 7)


def _probe_dense_unique(rng):
    # a surrogate key: every value of a range once, negatives among
    # them; the probe reaches past both ends
    cap = 1 << 12
    return (_keys(cap, rng.integers(-3000, 3000, cap)),
            _keys(cap, rng.permutation(cap) - 2000),
            lambda steps, m, exact, packed: packed and exact and steps == 0)


def _probe_dense_duplicates(rng):
    # lineitem's shape on a dense key: 1-7 rows a key, gaps between
    orders = rng.permutation(1 << 13)[:3000] + 10**9
    build = np.repeat(orders, rng.integers(1, 8, orders.size))
    return (_keys(1 << 12, np.append(orders, orders[:1000] + 1)),
            _keys(1 << 14, build),
            lambda steps, m, exact, packed: packed and exact and steps == 0)


def _probe_outside_range(rng):
    # probe keys below min and above max, to the ends of int64: they
    # read no bucket
    build = rng.integers(1000, 2000, 100)
    lo, hi = build.min(), build.max()
    return (_keys(32, [lo - 1, hi + 1, lo, hi, 0, -1, _I64.min, _I64.max,
                       lo - 2**40, hi + 2**40, hi + (1 << 13),
                       lo + _I64.min, 1500]),
            _keys(128, build),
            lambda steps, m, exact, packed: packed and exact and steps == 0)


def _edge(span):
    # capacity 32: a directory of 1024 buckets
    build = [5, 5 + span, 5, 700, 5 + span]
    return (_keys(32, [4, 5, 6, 700, 5 + span - 1, 5 + span,
                       5 + span + 1, 5 + 1024, 5 - 1024]),
            _keys(32, build))


def _probe_range_d_minus_1(rng):
    # the widest range that engages: the last bucket holds a key
    return _edge(1023) + (
        lambda steps, m, exact, packed:
        packed and exact and steps == 0,)


def _probe_range_d(rng):
    # one wider: searched, and as exact
    return _edge(1024) + (
        lambda steps, m, exact, packed:
        packed and not exact and steps > 0,)


def _probe_int64_extremes(rng):
    # a span past 2^63 must not wrap into a small one
    build = [_I64.min, _I64.max, 0, _I64.max, -1, _I64.min + 1]
    return (_keys(16, [_I64.max, _I64.min, 0, 1, -1, _I64.min + 1,
                       _I64.max - 1]),
            _keys(16, build),
            lambda steps, m, exact, packed: packed and not exact and m == 6)


def _probe_date_key(rng):
    days = rng.integers(8000, 10500, 300)        # 1992 to 1998, int32
    return (_keys(256, rng.integers(7900, 10600, 256), typ=DATE),
            _keys(512, days, typ=DATE),
            lambda steps, m, exact, packed: packed and exact and steps == 0)


def _probe_dictionary_key(rng):
    # strings under two dictionaries: the codes a probe compares are
    # the merged dictionary's (``align_string_keys``)
    words = ["w%03d" % i for i in range(60)]
    pick = lambda n, lo, hi: [words[i] for i in rng.integers(lo, hi, n)]
    return (batch_from_pylist({"k": pick(40, 0, 60) + [None]},
                              {"k": VARCHAR}),
            batch_from_pylist({"k": pick(90, 20, 50) + [None, None]},
                              {"k": VARCHAR}),
            lambda steps, m, exact, packed: packed and exact and m == 90)


def _wide(rng, span):
    # a build capacity of 2^20: a directory of 2^25 buckets, more than
    # its head of 2^24 (the bounds are read from the head alone unless
    # an exact range reaches past it)
    cap = 1 << 20
    build = rng.integers(0, span, cap - 5, dtype=np.int64) - 7
    build[:2] = -7, span - 7
    probe = np.append(rng.choice(build, 4000),
                      [-8, span - 6, span - 7, (1 << 24) - 7,
                       (1 << 24) - 8, (1 << 25) - 7, (1 << 25) - 8])
    return _keys(1 << 12, probe), _keys(cap, build)


def _probe_wide_directory_head(rng):
    return _wide(rng, (1 << 24) - 1) + (
        lambda steps, m, exact, packed: packed and exact and steps == 0,)


def _probe_wide_directory_past_head(rng):
    return _wide(rng, (1 << 25) - 1) + (
        lambda steps, m, exact, packed: packed and exact and steps == 0,)


def _probe_wide_directory_hashed(rng):
    return _wide(rng, 1 << 25) + (
        lambda steps, m, exact, packed:
        packed and not exact and 0 < steps <= 6,)


def _probe_null_keys_exact(rng):
    return (_keys(64, rng.integers(0, 40, 60), null_at=(0, 7, 59)),
            _keys(128, rng.integers(0, 40, 100), null_at=(3, 4, 99)),
            lambda steps, m, exact, packed:
            packed and exact and m == 97 and steps == 0)


def _heavy(rng, repeats, span, stride=1):
    # a build capacity of 2^20 leaves a directory word 11 bits for a
    # bucket's size: ONE key ``repeats`` times among keys that repeat
    # a few times each (past 2^11 - 1 the two sums are read, as before
    # PR 37, and give the same answers)
    cap, heavy = 1 << 20, 1000
    build = rng.integers(0, span, cap - 5, dtype=np.int64)
    build[build == heavy] += 1
    build[:repeats] = heavy
    build[-2:] = 0, span - 1
    probe = np.append(rng.choice(build, 4000),
                      [heavy, heavy - 1, heavy + 1, -1, span, span - 1, 0])
    return _keys(1 << 12, probe * stride), _keys(cap, build * stride)


def _probe_heavy_key(rng):
    return _heavy(rng, 1 << 11, 1 << 21) + (
        lambda steps, m, exact, packed:
        not packed and exact and steps == 0,)


def _probe_heavy_key_fits(rng):
    # the largest size a word at this capacity holds
    return _heavy(rng, (1 << 11) - 1, 1 << 21) + (
        lambda steps, m, exact, packed:
        packed and exact and steps == 0,)


def _probe_heavy_key_past_head(rng):
    return _heavy(rng, (1 << 11) + 5, (1 << 25) - 1) + (
        lambda steps, m, exact, packed:
        not packed and exact and steps == 0,)


def _probe_heavy_key_hashed(rng):
    return _heavy(rng, 1 << 11, 1 << 21, _FAR) + (
        lambda steps, m, exact, packed:   # bit_length(2^11)
        not packed and not exact and steps == 12,)


def _probe_no_dead_row(rng):
    # every build row usable: ``left`` reaches m = the capacity, the
    # widest position a word holds (2^12 << 19 = 2^31)
    cap = 1 << 12
    build = rng.integers(0, 3000, cap)
    return (_keys(256, np.append(rng.integers(-5, 3005, 250),
                                 [build.max(), build.max() + 1, 1 << 40,
                                  build.min(), build.min() - 1, _I64.max])),
            _keys(cap, build),
            lambda steps, m, exact, packed:
            packed and exact and m == 1 << 12)


def _probe_no_dead_row_hashed(rng):
    cap = 1 << 12
    build = rng.integers(0, 3000, cap) * _FAR
    return (_keys(256, np.append(rng.choice(build, 250),
                                 [build.max(), build.max() + 1, -1,
                                  build.min(), 7, _I64.max])),
            _keys(cap, build),
            lambda steps, m, exact, packed:
            packed and not exact and m == 1 << 12 and steps > 0)


@pytest.mark.parametrize("case", [
    _probe_unique, _probe_one_key, _probe_lineitem, _probe_dead_build,
    _probe_null_keys, _probe_absent_keys, _probe_smaller, _probe_larger,
    _probe_u64max_lane, _probe_constant_hash, _probe_dense_unique,
    _probe_dense_duplicates, _probe_outside_range, _probe_range_d_minus_1,
    _probe_range_d, _probe_int64_extremes, _probe_date_key,
    _probe_dictionary_key, _probe_null_keys_exact,
    _probe_wide_directory_head, _probe_wide_directory_past_head,
    _probe_wide_directory_hashed, _probe_heavy_key, _probe_heavy_key_fits,
    _probe_heavy_key_past_head, _probe_heavy_key_hashed,
    _probe_no_dead_row, _probe_no_dead_row_hashed],
    ids=lambda c: c.__name__[7:])
def test_join_probe_equals_searchsorted(case, monkeypatch):
    """The probe (bucket directory; where it is not exact, bounded
    bisection and run lengths) against numpy: ``left`` and ``count``
    are what ``searchsorted`` left and right give on the sorted usable
    build lanes OF THE MODE THAT ENGAGED (``key - min`` where the
    key is the lane — the directory exact, or searched by the key's
    top bits where duplicates span more than it holds —, the hash
    otherwise; both computed here),
    whatever the lane's distribution, and ``count`` is the number of
    equal build keys; mode and steps are what the case says, and so is
    whether a probe row read its bounds as ONE word (``side.packed``:
    the fullest bucket's size fits the bits a position leaves) or as
    the two adjacent sums."""
    if case is _probe_u64max_lane:
        monkeypatch.setattr(
            join_ops, "mix64", lambda x: ~jnp.asarray(x).astype(jnp.uint64))
        # the jitted probe hashes the probe's keys itself: a program
        # traced under this mix64 must serve this case and no later one
        # (jit caches by the function: a new one)
        inner = join_ops.probe_runs.__wrapped__
        monkeypatch.setattr(join_ops, "probe_runs",
                            jax.jit(lambda *a: inner(*a)))
    if case is _probe_constant_hash:
        monkeypatch.setattr(join_ops, "fold_hashes",
                            lambda hs: jnp.zeros_like(hs[0]) + 7)
    probe, build, mode_ok = case(np.random.default_rng(27))
    keys = list(build.columns)
    left, count, side = join_ops.match_runs(probe, build, keys, keys)
    start, count2, order = match_counts(probe, build, keys, keys)

    probe, build = join_ops.align_string_keys(probe, build, keys, keys)
    key_b, usable_b = map(np.asarray, join_ops.equality_lane(build, keys))
    key_p, usable_p = map(np.asarray, join_ops.equality_lane(probe, keys))
    m = int(usable_b.sum())
    exact = bool(side.exact)
    assert not bool(side.bitmap)
    if bool(side.linear):
        base = key_b[usable_b].astype(np.int64).min().astype(np.uint64)
        assert int(side.base) == int(base)
        lane_b, lane_p = key_b - base, key_p - base     # modulo 2^64
        assert exact == bool(
            int(lane_b[usable_b].max()) < side.directory.shape[0] - 1)
    else:
        assert not exact
        lane_b, lane_p = (np.asarray(join_ops.mix64(k))
                          for k in (key_b, key_p))
    assert side.directory.shape[0] - 1 == min(32 * build.capacity, 1 << 26)
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
    packed = bool(side.packed)
    assert mode_ok(int(side.steps), m, exact, packed), (
        int(side.steps), m, exact, packed)
    # the word: a bucket's first position above its size, where it fits
    k = join_ops._size_bits(build.capacity)
    sums = np.asarray(side.directory).astype(np.int64)
    sizes = np.append(np.diff(sums), 0)
    assert packed == bool(sizes.max() < (1 << k))
    assert side.words.dtype == jnp.uint32
    if packed:
        assert np.array_equal(np.asarray(side.words), (sums << k) | sizes)
    if case is not _probe_constant_hash:
        # no lane at all: the build keys equal to each probe key
        vals, n = np.unique(key_b[usable_b], return_counts=True)
        at = np.minimum(np.searchsorted(vals, key_p), max(len(vals) - 1, 0))
        same = (vals[at] == key_p) if len(vals) else np.zeros(len(key_p), bool)
        assert np.array_equal(
            np.asarray(count),
            np.where(usable_p & same, n[at] if len(vals) else 0, 0))
    if case is _probe_u64max_lane:
        assert list(np.asarray(count)[:5]) == [3, 1, 1, 3, 0]


def _counts_all_zero(rng):
    return np.zeros(64, np.int64), 8


def _counts_all_one(rng):
    return np.ones(64, np.int64), 64


def _counts_zeros_at_head(rng):
    return np.r_[np.zeros(20), rng.integers(1, 3, 44)], 128


def _counts_zeros_inside(rng):
    c = rng.integers(0, 2, 256) * rng.integers(1, 4, 256)
    c[40:90] = 0
    return c, 512


def _counts_zeros_at_tail(rng):
    return np.r_[rng.integers(1, 3, 30), np.zeros(34)], 64


def _counts_above_one(rng):
    # q3's lineitem rows an order, a cross join's nb
    return rng.integers(0, 8, 128), 512


def _counts_left_join_dead_rows(rng):
    # a left join's max(count, 1) over the live prefix, 0 past it
    live = np.arange(128) < 77
    return np.where(live, np.maximum(rng.integers(0, 3, 128), 1), 0), 256


def _counts_total_equals_capacity(rng):
    c = np.zeros(64, np.int64)
    c[rng.permutation(64)[:32]] = 2
    return c, 64


def _counts_total_below_capacity(rng):
    return rng.integers(0, 2, 64), 64


def _counts_total_above_capacity(rng):
    # an output cut short (the oversized join expands in chunks; a
    # streamed chunk that overflows is rerun): positions past the
    # capacity are asked of no row
    return rng.integers(0, 5, 128), 32


def _counts_capacity_one(rng):
    return np.r_[0, 0, 3, 1, np.zeros(4)], 1


def _counts_runs_far_above(rng):
    # a few rows kept of a large probe side: the search's regime
    c = np.zeros(1 << 16, np.int64)
    c[rng.permutation(1 << 16)[:11]] = rng.integers(1, 3, 11)
    return c, 16


def _counts_runs_far_below(rng):
    # every row many times over: the histogram's regime
    return rng.integers(100, 300, 8), 2048


def _counts_sum_past_int32(rng):
    # int64 counts whose running sum passes 2^31 inside and beyond the
    # capacity asked for
    c = rng.integers(0, 3, 64).astype(np.int64)
    c[37] = (1 << 31) + 5
    c[50] = 1 << 40
    return c, 128


@pytest.mark.parametrize("case", [
    _counts_all_zero, _counts_all_one, _counts_zeros_at_head,
    _counts_zeros_inside, _counts_zeros_at_tail, _counts_above_one,
    _counts_left_join_dead_rows, _counts_total_equals_capacity,
    _counts_total_below_capacity, _counts_total_above_capacity,
    _counts_capacity_one, _counts_runs_far_above, _counts_runs_far_below,
    _counts_sum_past_int32], ids=lambda c: c.__name__[8:])
def test_run_positions_equals_searchsorted(case, monkeypatch):
    """``run_positions`` against numpy, in the form its shapes choose
    and in BOTH forms forced (the constant moved under it): the same
    int32 array each time, what ``searchsorted(incl, i, "right")``
    gives for every output position."""
    counts, out_capacity = case(np.random.default_rng(35))
    incl = np.cumsum(np.asarray(counts, np.int64))
    want = np.searchsorted(incl, np.arange(out_capacity), "right")
    chosen = join_ops.expand_form(len(incl), out_capacity)
    if case is _counts_runs_far_above:
        assert chosen == "search"
    if case is _counts_runs_far_below:
        assert chosen == "histogram"
    got = {chosen: join_ops.run_positions(jnp.asarray(incl), out_capacity)}
    for form, k in (("histogram", 1 << 40), ("search", 0)):
        monkeypatch.setattr(join_ops, "_HISTOGRAM_K", k)
        assert join_ops.expand_form(len(incl), out_capacity) == form
        forced = join_ops.run_positions(jnp.asarray(incl), out_capacity)
        assert np.array_equal(np.asarray(forced), np.asarray(got[chosen]))
        got[form] = forced
    for form, p in got.items():
        assert p.dtype == jnp.int32 and p.shape == (out_capacity,), form
        assert np.array_equal(np.asarray(p), want), form


@pytest.fixture(params=["histogram", "search"])
def expand_form(request, monkeypatch):
    """Every expansion of the test in ONE form of ``run_positions``,
    whatever its shapes would choose."""
    monkeypatch.setattr(join_ops, "_HISTOGRAM_K",
                        (1 << 40) if request.param == "histogram" else 0)
    return request.param


def test_inner_join(expand_form):
    probe = batch_from_pylist({"k": [1, 2, 3, None, 5]},
                              {"k": BIGINT})
    build = batch_from_pylist({"k": [1, 1, 2, None], "w": [7, 8, 9, 10]},
                              {"k": BIGINT, "w": BIGINT})
    out = _join(probe, build, ["k"], ["k"])
    rows = sorted(map(tuple, out.to_pylist()))
    assert rows == [(1, 1, 7), (1, 1, 8), (2, 2, 9)]


def test_left_join(expand_form):
    probe = batch_from_pylist({"k": [1, 3, None]}, {"k": BIGINT})
    build = batch_from_pylist({"k": [1, 2], "w": [7, 9]},
                              {"k": BIGINT, "w": BIGINT})
    out = _join(probe, build, ["k"], ["k"], "left")
    rows = sorted(map(tuple, out.to_pylist()),
                  key=lambda r: (r[0] is None, r))
    assert rows == [(1, 1, 7), (3, None, None), (None, None, None)]


def test_multikey_join(expand_form):
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


def test_cross_join(expand_form):
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


def test_string_join_across_dictionaries(expand_form):
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
