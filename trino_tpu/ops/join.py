"""Joins — LookupJoinOperator / HashBuilderOperator, TPU style.

Reference parity: operator/HashBuilderOperator.java:51 (build side),
operator/LookupJoinOperator.java:71 + JoinProbe (probe loop),
NestedLoopJoinOperator, HashSemiJoinOperator. Redesign for XLA
(SURVEY.md §7.3): the serial open-addressing probe becomes a vectorized
sort + bucket-directory join:

1. build keys are reduced to a single uint64 equality lane (bijective
   splitmix64 for one integer key column — exact; multi-column and
   float keys are hash-combined, accepting a ~n^2/2^64 collision
   probability with NO re-verification — acknowledged in SURVEY.md §7
   "hard parts"; string keys are first remapped onto a dictionary
   merged across both sides so codes are comparable),
2. the build side is sorted by that lane (nulls/dead rows forced past the
   valid prefix) and indexed ONCE, at O(build capacity): a bucket
   directory over the lane's top bits (one bucket per build row: the
   lane is a hash, so a bucket holds a handful of entries) and the
   length of the run of equal lanes that starts at each position, and
3. every probe row reads its bucket's bounds from the directory,
   bisects inside the bucket for the first entry >= its lane
   (``probe_runs``: as many steps as the FULLEST bucket needs, a device
   value — 3-4 for a uniform lane, log2(capacity)+1 when one key fills
   a bucket) and reads its count from the run lengths. A gather costs
   the chip per element, so the steps are what a probe costs.

Output cardinality is data-dependent: callers run ``match_counts`` first,
read the total on the host, pick a power-of-two capacity bucket, then run
the expansion jit with that static capacity (the two-phase analog of
Trino's incremental JoinProbe yielding pages).
"""

from __future__ import annotations

from typing import List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..columnar import Batch, Column
from .hashing import combine_hashes, lane_to_u64, mix64
from .sort import stable_lexsort

_U64MAX = jnp.uint64(0xFFFFFFFFFFFFFFFF)


def align_string_keys(probe: Batch, build: Batch,
                      probe_keys: Sequence[str],
                      build_keys: Sequence[str]) -> Tuple[Batch, Batch]:
    """Remap string key columns of both sides onto a merged dictionary so
    that code equality == string equality across batches (dictionary
    codes are only meaningful within one dictionary)."""
    pcols = dict(probe.columns)
    bcols = dict(build.columns)
    for pk, bk in zip(probe_keys, build_keys):
        pc, bc = pcols[pk], bcols[bk]
        if pc.dictionary is None or bc.dictionary is None:
            continue
        if pc.dictionary is bc.dictionary:
            continue
        merged, rs, ro = pc.dictionary.merge(bc.dictionary)
        pcols[pk] = pc.with_dictionary(merged, rs)
        bcols[bk] = bc.with_dictionary(merged, ro)
    return (Batch(pcols, probe.num_rows), Batch(bcols, build.num_rows))


def equality_lane(batch: Batch, key_names: Sequence[str]) -> Tuple[
        jax.Array, jax.Array]:
    """(lane, usable) — uint64 equality-preserving key lane; usable is
    False for dead rows and rows with any NULL key (SQL: null join keys
    never match, reference: JoinProbe skips null channels)."""
    usable = batch.row_valid()
    lanes = []
    for name in key_names:
        col = batch.column(name)
        lanes.append(lane_to_u64(col.data))
        if col.valid is not None:
            usable = usable & jnp.asarray(col.valid)
    if len(lanes) == 1:
        lane = mix64(lanes[0])  # bijective -> exact equality
    else:
        lane = combine_hashes([mix64(l) for l in lanes])
    return lane, usable


class BuildSide(NamedTuple):
    """The sorted build side and the index a probe row finds its run
    of equal lanes with (``probe_runs``)."""
    sorted_lane: jax.Array  # uint64[cap]: m usable lanes ascending, then U64MAX
    order: jax.Array        # sorted position -> build row
    m: jax.Array            # usable build rows
    directory: jax.Array    # int32[D+1]: first position in [0, m] whose
    #                         lane's top log2(D) bits are >= b
    run_len: jax.Array      # int32[cap]: entries of [i, m) equal to entry i
    steps: jax.Array        # int32: bisection steps the fullest bucket needs


def build_side(batch: Batch, key_names: Sequence[str]) -> BuildSide:
    """Sort the build side by key lane and index it. The first m
    entries of ``sorted_lane`` are the usable keys, the tail is forced
    to U64MAX (and counted into no bucket and no run)."""
    lane, usable = equality_lane(batch, key_names)
    cap = batch.capacity
    if all(batch.column(k).valid is None for k in key_names):
        # no NULL keys: the usable rows are exactly the live prefix, so
        # they already precede every dead row in input order. ONE
        # stable sort on the lane alone (dead rows forced to the
        # maximum) then leaves the usable rows sorted in [0, m) — ties
        # at the maximum keep input order, usable first. The second
        # sort key below doubles this program's compile time on the
        # chip (v5e compiler, 2^20 rows: 88 s against 46 s).
        order = stable_lexsort([jnp.where(usable, lane, _U64MAX)])
    else:
        order = stable_lexsort([(~usable).astype(jnp.int32), lane])
    m = jnp.sum(usable.astype(jnp.int64))
    pos = jnp.arange(cap, dtype=jnp.int32)
    live = pos < m
    sorted_lane = jnp.where(live, jnp.take(lane, order), _U64MAX)

    # directory, one bucket a build row: a histogram of the live
    # entries' top bits, summed up. Never a search of the D boundaries,
    # which would cost what the directory saves.
    bits = max(1, (cap - 1).bit_length())
    top = (sorted_lane >> jnp.uint64(64 - bits)).astype(jnp.int32)
    directory = jnp.cumsum(
        jnp.zeros(((1 << bits) + 1,), jnp.int32).at[top + 1].add(
            live.astype(jnp.int32), indices_are_sorted=True,
            mode="promise_in_bounds"))
    # a lower-bound bisection over n entries takes bit_length(n) steps
    fullest = jnp.max(directory[1:] - directory[:-1])
    steps = jnp.sum((fullest >> jnp.arange(31, dtype=jnp.int32)) > 0,
                    dtype=jnp.int32)

    # run lengths: entry i's run ends at the first position after it
    # that differs from the one before, or is dead (m at the latest)
    after = pos + 1
    ends = jnp.concatenate([sorted_lane[1:] != sorted_lane[:-1],
                            jnp.ones((1,), bool)]) | (after >= m)
    run_len = jnp.where(
        live, jax.lax.cummin(jnp.where(ends, after, cap), reverse=True)
        - pos, 0)
    return BuildSide(sorted_lane, order, m, directory, run_len, steps)


def _at(lane, index):
    # a gather of indices known to be inside the lane: no clamp, no fill
    return lane.at[index].get(mode="promise_in_bounds")


@jax.jit
def probe_runs(side: BuildSide, lane_p, usable_p):
    """(left, count) per probe row: ``left`` is the first position of
    the sorted build lane that is >= the row's lane (clipped to m),
    ``count`` the entries equal to it — what ``searchsorted`` left and
    right gave, exactly, for every lane whatever its distribution. The
    ONE probe of the engine: joins, semi joins and the streamed probe
    (exec/streamjoin.py) all come through here. Jitted, as
    ``searchsorted`` is: an eager caller reuses one program a shape."""
    bits = (side.directory.shape[0] - 1).bit_length() - 1
    last = side.sorted_lane.shape[0] - 1
    top = (lane_p >> jnp.uint64(64 - bits)).astype(jnp.int32)

    def step(_, state):
        # lower bound on [lo, hi); ``hit`` is whether the entry ``hi``
        # was last moved onto equals the probe lane: where the search
        # ends inside the bucket, that entry is the answer, so no
        # gather is needed to check it
        lo, hi, hit = state
        mid = (lo + hi) >> 1
        v = _at(side.sorted_lane, jnp.minimum(mid, last))
        open_ = lo < hi
        right = open_ & (v < lane_p)
        down = open_ & ~right
        return (jnp.where(right, mid + 1, lo), jnp.where(down, mid, hi),
                jnp.where(down, v == lane_p, hit))

    left, _, hit = jax.lax.fori_loop(
        0, side.steps, step,
        (_at(side.directory, top), _at(side.directory, top + 1),
         jnp.zeros(lane_p.shape, bool)))
    count = jnp.where(hit & usable_p,
                      _at(side.run_len, jnp.minimum(left, last)), 0)
    return left.astype(jnp.int64), count.astype(jnp.int64)


def match_runs(probe: Batch, build: Batch,
               probe_keys: Sequence[str], build_keys: Sequence[str]):
    """(left, count, side): ``match_counts`` with the whole build side."""
    probe, build = align_string_keys(probe, build, probe_keys, build_keys)
    lane_p, usable_p = equality_lane(probe, probe_keys)
    side = build_side(build, build_keys)
    left, count = probe_runs(side, lane_p, usable_p)
    return left, count, side


def match_counts(probe: Batch, build: Batch,
                 probe_keys: Sequence[str], build_keys: Sequence[str]):
    """Per-probe-row (start, count) of the build match run + the build
    permutation.

    start indexes the *sorted* build order; map through perm for payload.
    """
    left, count, side = match_runs(probe, build, probe_keys, build_keys)
    return left, count, side.order


def expand_join(probe: Batch, build: Batch, start, count, order,
                out_capacity: int, join_type: str = "inner",
                build_prefix: str = "") -> Batch:
    """Materialize join output rows given per-probe match runs.

    join_type: inner | left. For 'left', probe rows with no match emit one
    row with NULL build columns (reference: LookupJoinOperator
    outer-position tracking)."""
    outer = join_type == "left"
    live_p = probe.row_valid()
    eff_count = (jnp.where(live_p, jnp.maximum(count, 1), 0)
                 if outer else count)
    no_match = count == 0

    incl = jnp.cumsum(eff_count)
    total = incl[-1]
    offs = incl - eff_count  # exclusive

    i = jnp.arange(out_capacity, dtype=jnp.int64)
    p = jnp.searchsorted(incl, i, side="right")
    p = jnp.clip(p, 0, probe.capacity - 1)
    j = i - jnp.take(offs, p)
    b_sorted = jnp.take(start, p) + j
    b = jnp.take(order, jnp.clip(b_sorted, 0, build.capacity - 1))

    pad_build = (jnp.take(no_match, p) if outer else None)

    cols = {}
    for name, col in probe.columns.items():
        cols[name] = col.gather(p)
    for name, col in build.columns.items():
        out_name = build_prefix + name
        if outer:
            cols[out_name] = col.gather(b, fill_invalid=pad_build)
        else:
            cols[out_name] = col.gather(b)
    return Batch(cols, total)


def semi_join_mask(probe: Batch, build: Batch, probe_keys: Sequence[str],
                   build_keys: Sequence[str]):
    """(matched, probe_key_null, build_has_null, build_nonempty) device
    values for IN / semi-join with full SQL three-valued semantics
    (reference: operator/HashSemiJoinOperator.java — probe null or
    build-side null yields NULL, else TRUE/FALSE)."""
    probe, build = align_string_keys(probe, build, probe_keys, build_keys)
    lane_p, usable_p = equality_lane(probe, probe_keys)
    _, count = probe_runs(build_side(build, build_keys), lane_p, usable_p)
    matched = count > 0
    key_null = probe.row_valid() & ~usable_p

    live_b = build.row_valid()
    any_null_key = jnp.zeros((), dtype=bool)
    for name in build_keys:
        col = build.column(name)
        if col.valid is not None:
            any_null_key = any_null_key | jnp.any(
                live_b & ~jnp.asarray(col.valid))
    nonempty = jnp.sum(live_b.astype(jnp.int64)) > 0
    return matched, key_null, any_null_key, nonempty


def cross_counts(probe: Batch, build: Batch):
    """Nested-loop cross join sizing (reference:
    operator/NestedLoopJoinOperator.java)."""
    nb = build.num_rows_device()
    count = jnp.where(probe.row_valid(), nb, 0)
    start = jnp.zeros((probe.capacity,), dtype=jnp.int64)
    order = jnp.arange(build.capacity, dtype=jnp.int64)
    return start, count, order
