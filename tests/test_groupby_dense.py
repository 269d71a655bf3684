"""The dense form of ``group_aggregate`` (ops/groupby.py, ISSUE 36)
against the sort form, on seeded random batches: one integer key whose
live values span less than ``dense_slots(capacity)`` is grouped by
``key - least key`` and scatters; everything else keeps the sort. The
two forms have to give the same groups with the same aggregates for
NULL keys, NULL inputs, negative keys, aggregate masks and an empty
batch, and the form has to follow the span: one under the bound is
dense, one over is the sort."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from trino_tpu import BIGINT, BOOLEAN, DATE, DOUBLE, INTEGER
from trino_tpu.columnar import Batch, Column
from trino_tpu.ops import groupby
from trino_tpu.ops.groupby import (AggInput, DenseKeys, dense_eligible,
                                   dense_fits, dense_group_slots,
                                   dense_key_range, dense_keys, dense_slots,
                                   group_aggregate, noted_forms)

AGGS = [AggInput("sum", "v", output="s"),
        AggInput("count", "v", output="c"),
        AggInput("count_star", None, output="n"),
        AggInput("min", "v", output="lo"),
        AggInput("max", "v", output="hi"),
        AggInput("sum", "i", output="si"),
        AggInput("min", "i", output="li"),
        AggInput("sum", "v", mask="m", output="sm"),
        AggInput("count_star", None, mask="m", output="nm")]


def random_batch(seed: int, cap: int = 256, live: int = 200,
                 key_lo: int = -40, key_hi: int = 40,
                 null_keys: bool = True, key_type=BIGINT) -> Batch:
    rng = np.random.default_rng(seed)
    dtype = np.int32 if key_type in (INTEGER, DATE) else np.int64
    k = rng.integers(key_lo, key_hi + 1, cap).astype(dtype)
    kv = rng.random(cap) > 0.1 if null_keys else None
    # halves and quarters: every sum is exact, whatever the order
    v = rng.integers(-400, 400, cap) / 4.0
    vv = rng.random(cap) > 0.2
    i = rng.integers(-1000, 1000, cap).astype(np.int64)
    m = rng.random(cap) > 0.5
    mv = rng.random(cap) > 0.1
    return Batch({"k": Column(key_type, jnp.asarray(k),
                              None if kv is None else jnp.asarray(kv)),
                  "v": Column(DOUBLE, jnp.asarray(v), jnp.asarray(vv)),
                  "i": Column(BIGINT, jnp.asarray(i), None),
                  "m": Column(BOOLEAN, jnp.asarray(m), jnp.asarray(mv))},
                 live)


def rows_of(b: Batch):
    return sorted(b.to_pylist(), key=repr)


def both_forms(monkeypatch, batch, aggs=AGGS, **kw):
    with noted_forms() as dense_notes:
        dense = group_aggregate(batch, ["k"], aggs, **kw)
    with monkeypatch.context() as mp:
        mp.setattr(groupby, "dense_eligible", lambda *a: False)
        with noted_forms() as sort_notes:
            sort = group_aggregate(batch, ["k"], aggs, **kw)
    return dense, sort, dense_notes, sort_notes


@pytest.mark.parametrize("seed", range(12))
def test_dense_equals_sort_on_random_keys(monkeypatch, seed):
    batch = random_batch(seed, null_keys=seed % 3 != 0,
                         key_type=(BIGINT, INTEGER, DATE)[seed % 3])
    dense, sort, dn, sn = both_forms(monkeypatch, batch)
    assert dn == [("dense", batch.capacity)]
    assert sn == [("sort", batch.capacity)]
    assert rows_of(dense) == rows_of(sort)
    assert dense.num_rows_host() == sort.num_rows_host() > 10


@pytest.mark.parametrize("seed", range(4))
def test_dense_equals_sort_under_a_live_mask(monkeypatch, seed):
    """Selection-vector execution: the rows that live are a mask, not a
    prefix (a fused filter below the aggregation)."""
    batch = random_batch(100 + seed, live=256)
    live = jnp.asarray(np.random.default_rng(seed).random(256) > 0.4)
    dense, sort, dn, _ = both_forms(monkeypatch, batch, live=live)
    assert dn == [("dense", 256)]
    assert rows_of(dense) == rows_of(sort)


def test_an_empty_batch_and_an_all_null_key(monkeypatch):
    empty = random_batch(1, live=0)
    dense, sort, dn, _ = both_forms(monkeypatch, empty)
    # nothing to span: the read says so and the sort form answers
    assert dn == [("sort", 256)] and rows_of(dense) == rows_of(sort) == []
    b = random_batch(2)
    cols = dict(b.columns)
    cols["k"] = Column(BIGINT, cols["k"].data, jnp.zeros((256,), bool))
    nulls = Batch(cols, b.num_rows)
    dense, sort, dn, _ = both_forms(monkeypatch, nulls)
    assert dn == [("sort", 256)]
    assert rows_of(dense) == rows_of(sort) and dense.num_rows_host() == 1


@pytest.mark.parametrize("over", [0, 1])
def test_the_form_follows_the_span(monkeypatch, over):
    """capacity 16 has 2^9 slots, the last the NULL key's: keys 0 and
    510 span one under the bound and are dense, 0 and 511 are one over
    and take the sort. Both answer alike."""
    assert dense_slots(16) == 512
    top = 510 + over
    k = np.array([0, top, 7, top, 0] + [0] * 11, np.int64)
    batch = Batch({"k": Column(BIGINT, jnp.asarray(k),
                               jnp.asarray(np.arange(16) != 2)),
                   "v": Column(DOUBLE, jnp.arange(16.0), None)}, 5)
    aggs = [AggInput("sum", "v", output="s"),
            AggInput("count_star", None, output="n")]
    got = jax.device_get(dense_key_range(batch, ["k"]))
    assert [int(x) for x in got] == [0, top, 4, 0, 0]
    assert dense_fits(got, 16) == (not over)
    dense, sort, dn, _ = both_forms(monkeypatch, batch, aggs)
    assert dn == [("sort" if over else "dense", 16)]
    assert rows_of(dense) == rows_of(sort) == sorted(
        [[0, 4.0, 2], [top, 4.0, 2], [None, 2.0, 1]], key=repr)


def test_a_key_range_past_63_bits_is_no_fit():
    k = np.array([-(2 ** 62) - 5, 2 ** 62 + 5], np.int64)
    batch = Batch({"k": Column(BIGINT, jnp.asarray(np.resize(k, 8)), None)},
                  2)
    assert not dense_fits(jax.device_get(dense_key_range(batch, ["k"])), 8)
    out = group_aggregate(batch, ["k"],
                          [AggInput("count_star", None, output="n")])
    assert rows_of(out) == sorted([[int(k[0]), 1], [int(k[1]), 1]], key=repr)


def test_what_is_eligible():
    b = random_batch(3)
    assert dense_eligible(b, ["k"], AGGS)
    assert not dense_eligible(b, ["k", "i"], AGGS)            # two keys
    assert not dense_eligible(b, ["v"], AGGS)                 # a float key
    assert not dense_eligible(b, ["m"], AGGS)                 # a boolean
    assert not dense_eligible(
        b, ["k"], [AggInput("any_value", "v", output="a")])
    assert not dense_eligible(b, ["k"], [AggInput("max", "m", output="a")])


def test_slots_hold_groups_where_their_keys_are(monkeypatch):
    """The uncompacted form a HAVING filters before the one compaction:
    row g is key base + g, ``exists`` tells the groups that are there."""
    batch = random_batch(5, null_keys=True)
    base = int(jax.device_get(dense_key_range(batch, ["k"]))[0])
    slots, exists = dense_group_slots(
        batch, ["k"], [AggInput("count_star", None, output="n")],
        DenseKeys(jnp.int64(base)))
    assert slots.capacity == slots.num_rows == dense_slots(256)
    n = np.asarray(slots.column("n").data)
    assert (np.asarray(exists) == (n > 0)).all()
    k = np.asarray(batch.column("k").data)[:200]
    kv = np.asarray(batch.column("k").valid)[:200]
    want = np.bincount(k[kv] - base, minlength=dense_slots(256))
    want[-1] = (~kv).sum()
    assert (n == want).all()
    assert not bool(np.asarray(slots.column("k").valid)[-1])


def test_inside_a_program_the_caller_reads_the_range(monkeypatch):
    """A traced ``group_aggregate`` cannot read: without the base it
    keeps the sort form, with it (one counted read before the program)
    it runs dense, and answers the same."""
    batch = random_batch(7)
    aggs = AGGS[:3]
    with noted_forms() as notes:
        plain = jax.jit(lambda b: group_aggregate(b, ["k"], aggs))(batch)
    assert notes == [("sort", 256)]
    key_range = jax.device_get(dense_key_range(batch, ["k"]))
    assert dense_fits(key_range, 256)
    with noted_forms() as notes:
        dense = jax.jit(lambda b, base: group_aggregate(
            b, ["k"], aggs, dense=DenseKeys(base)))(
                batch, jnp.int64(key_range[0]))
    assert notes == [("dense", 256)]
    assert rows_of(dense) == rows_of(plain)


@pytest.mark.parametrize("seed", range(4))
def test_ascending_keys_promise_sorted_indices(monkeypatch, seed):
    """A table whose keys ascend (TPC-H's l_orderkey in lineitem): the
    read says so, the scatters promise sorted indices, and a row that a
    fused filter dropped keeps its slot and adds nothing. The same
    groups as the sort form either way."""
    rng = np.random.default_rng(seed)
    k = np.sort(rng.integers(-30, 30, 256)).astype(np.int64)
    base = random_batch(200 + seed, live=230, null_keys=False)
    cols = dict(base.columns)
    cols["k"] = Column(BIGINT, jnp.asarray(k), None)
    batch = Batch(cols, 230)
    got = jax.device_get(dense_key_range(batch, ["k"]))
    longest = int(np.bincount(k[:230] + 30).max())
    assert int(got[3]) == 1 and int(got[4]) == longest
    keys = dense_keys(got, 256)
    # runs this short are added up row by row: no slot, no scatter
    assert keys.ascending and longest <= keys.run < 2 * longest
    dense, sort, dn, _ = both_forms(monkeypatch, batch)
    assert dn == [("dense", 256)] and rows_of(dense) == rows_of(sort)
    # ... and runs past _RUN_MAX are scattered, with sorted indices
    with monkeypatch.context() as mp:
        mp.setattr(groupby, "_RUN_MAX", 2)
        assert dense_keys(got, 256) == keys._replace(run=0)
        scattered, _, dn, _ = both_forms(mp, batch)
    assert dn == [("dense", 256)] and rows_of(scattered) == rows_of(sort)
    # a filter's mask over the ascending prefix: the caller says which
    # rows the read spanned, the dead among them stay in place
    live = batch.row_valid() & jnp.asarray(rng.random(256) > 0.5)
    from trino_tpu.ops.compact import compact_batch
    with monkeypatch.context() as mp:
        mp.setattr(groupby, "dense_eligible", lambda *a: False)
        want = group_aggregate(batch, ["k"], AGGS, live=live)
    for form in (keys, keys._replace(run=0)):       # runs, then slots
        slots, exists = dense_group_slots(
            batch, ["k"], AGGS, form, live=live, spanned=batch.row_valid())
        assert slots.capacity == (256 if form.run else dense_slots(256))
        assert rows_of(compact_batch(slots, exists, 256)) == rows_of(want)
    # keys out of order, or a NULL among them, do not ascend
    shuffled = Batch(dict(cols, k=Column(BIGINT, jnp.asarray(k[::-1].copy()),
                                         None)), 230)
    assert int(jax.device_get(dense_key_range(shuffled, ["k"]))[3]) == 0
    nullable = Batch(dict(cols, k=Column(
        BIGINT, jnp.asarray(k), jnp.asarray(np.arange(256) != 5))), 230)
    assert int(jax.device_get(dense_key_range(nullable, ["k"]))[3]) == 0
