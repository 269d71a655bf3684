"""Joins — LookupJoinOperator / HashBuilderOperator, TPU style.

Reference parity: operator/HashBuilderOperator.java:51 (build side),
operator/LookupJoinOperator.java:71 + JoinProbe (probe loop),
NestedLoopJoinOperator, HashSemiJoinOperator. Redesign for XLA
(SURVEY.md §7.3): the serial open-addressing probe becomes a vectorized
sort + bucket-directory join:

1. build keys are reduced to a single uint64 equality lane, in one of
   two modes the BUILD SIDE chooses on the device (``BuildSide.linear``):
   where the key is one integer column (integers, dates, booleans,
   dictionary codes) whose usable build values span less than one of
   the two directories over the key holds (below), the lane is ``key -
   min`` — the integer compared as the integer it is; otherwise a hash
   (bijective splitmix64 of one column — exact; multi-column and float
   keys are hash-combined, accepting a ~n^2/2^64 collision probability
   with NO re-verification — acknowledged in SURVEY.md §7 "hard
   parts"). String keys are first remapped onto a dictionary merged
   across both sides so codes are comparable,
2. the build side is sorted by that lane (nulls/dead rows forced past the
   valid prefix) and indexed ONCE, in one of three forms the build side
   chooses on the device from the span, and from whether the sort left
   two equal keys side by side:
   - the BITMAP directory (``BuildSide.bitmap``) where the usable keys
     are unique and span more than the exact directory serves from its
     head (32 values a build row, or past 2^24) but less than the
     bitmap holds: W = 2^14 words a build row, at most the head's
     2^24, one bit a key value; a word holds the first sorted position
     of its keys in the ``bit_length(capacity)`` high bits and a bitmap
     of 2^s key values in the low ones, 2^s the largest power of two the
     rest holds (16 values up to 2^15 build rows, 8 up to 2^23: 2^28
     and 2^27 values). ONE sorted scatter-add of the keys' bits and a
     running count of them make it, the cost of the other directory's
     histogram;
   - otherwise a bucket directory of 32 buckets a build row (at most
     2^26) — over the lane itself where the span fits it (exact), ONE
     KEY VALUE a bucket; over the lane's top bits otherwise (at most 24
     of them: the directory's head, a table the chip gathers from at its
     fast rate), a handful of entries a bucket: the hash's, or where
     duplicate keys span wider than the exact directory within the
     bitmap's reach, the key's own (the lane is chosen before the sort
     shows whether the keys are unique) —
   and the length of the run of equal lanes that starts at each
   position, and
3. a probe row against the bitmap reads ONE word: its lower bound is
   the word's position plus the bits set below its own, its count its
   own bit; no search, 0 steps. Against a bucket directory every probe
   row reads its bucket's bounds (``probe_runs``; from its head alone
   unless an exact range reaches past it) in ONE gather: an entry is
   one 32-bit word, the bucket's first position above its SIZE
   (``BuildSide.words``; the chip pays a gather by the index, not by
   the byte), wherever the fullest bucket's size fits the bits the
   position leaves (``BuildSide.packed``, a device value: one key
   repeated past that reads the two adjacent sums instead). Where exact
   the bounds ARE the row's run of equal keys: no search, 0 steps, one
   probe-sized gather in all (dense surrogate keys, what warehouse
   joins join on: the "perfect hash join"); the bitmap is that join for
   a few keys spread wide (q18's 652 orders over 60M order keys).
   Otherwise it bisects inside the bucket for the first entry >= its
   lane (as many steps as the FULLEST bucket needs, a device value —
   2-4 for a uniform hash, log2(capacity)+1 when one key fills a
   bucket; each step gathers both halves of a 64-bit lane) and reads
   its count from the run lengths. A gather costs the chip per
   element, so the steps are what a probe costs.

Output cardinality is data-dependent: callers run ``match_counts`` first,
read the total on the host, pick a power-of-two capacity bucket, then run
the expansion jit with that static capacity (the two-phase analog of
Trino's incremental JoinProbe yielding pages). There

4. every output row finds its probe row (``expand_join`` through
   ``run_positions``: how many running sums of the per-row counts are
   <= its position) the way the directory is made: ONE scatter-add of
   sorted indices into a histogram and an int32 ``cumsum``, no search.
   That costs by the probe side's capacity, a bisection of the running
   sums by output rows x log2(probe capacity): where few rows of a
   large probe side are kept the bisection is cheaper, so the form is
   chosen from the two static capacities (``expand_form``, one
   measured constant) and the host that dispatches the program says
   which (``form`` on its span, ``trino_tpu_join_expands_total``).
   UNNEST and INTERSECT/EXCEPT ALL expand the same way.
"""

from __future__ import annotations

import functools
from typing import List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..columnar import Batch, Column
from .hashing import fold_hashes, lane_to_u64, mix64
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
    """(key, usable) — ``key`` is a uint64 lane whose equality is the
    keys': one column's value as it is (an integer's two's complement,
    exact), several columns' folded hashes. The lane that is sorted and
    compared is ``_lane_of`` it, by the mode the build side chose.
    ``usable`` is False for dead rows and rows with any NULL key (SQL:
    null join keys never match, reference: JoinProbe skips null
    channels)."""
    usable = batch.row_valid()
    lanes = []
    for name in key_names:
        col = batch.column(name)
        lanes.append(lane_to_u64(col.data))
        if col.valid is not None:
            usable = usable & jnp.asarray(col.valid)
    if len(lanes) == 1:
        return lanes[0], usable
    return fold_hashes([mix64(l) for l in lanes]), usable


def _integer_key(batch: Batch, key_names: Sequence[str]) -> bool:
    """Static: the key is ONE column whose whole value is one integer
    lane (integers, dates, booleans, dictionary codes; not floats, not
    an Int128's low half) — the keys an exact directory can serve."""
    if len(key_names) != 1:
        return False
    col = batch.column(key_names[0])
    return col.data2 is None and not jnp.issubdtype(
        jnp.asarray(col.data).dtype, jnp.floating)


def _lane_of(key, linear, base):
    """The lane of a key (``equality_lane``) of either side, by the
    build side's mode: both are bijections of the key, so equality is
    the keys' (``key - min`` modulo 2^64: a key outside the build
    side's range lands past its span). A select here, over the build
    rows; the probe branches (``probe_runs``)."""
    return jnp.where(linear, key - base, mix64(key))


def _directory_bits(capacity: int) -> int:
    """log2 of the directory's size D, static from the build capacity
    alone: 32 buckets a build row (a dense key's range is a small
    multiple of the rows that survive to the build side: TPC-H's
    o_orderkey spans 4 x rows(orders)), at most 2^26 entries (256 MB
    of int32)."""
    return min(max(1, (capacity - 1).bit_length()) + 5, 26)


def _size_bits(capacity: int) -> int:
    """The bits of a directory word that hold a bucket's size, static
    from the build capacity alone: those a position in [0, capacity]
    leaves of 32 (11 at 2^20, 8 at 2^23, 5 at 2^26)."""
    return max(32 - capacity.bit_length(), 0)


# log2 of the directory's HEAD: a table of up to 2^24 int32 entries
# (64 MB) is gathered from at 8.6 ns an element on a v5e, whatever the
# order of the indices; one of 2^25 at 15 (random) to 25 (ascending)
# (PERF.md §6, PR 29). Hashed buckets stay inside the head, and so do
# the bounds' gathers unless an exact range reaches past it, and the
# bitmap directory's words.
_HEAD_BITS = 24


def _bitmap_shift(capacity: int) -> int:
    """log2 of the key values a bitmap word holds, static from the
    build capacity: the largest power of two the bits a position leaves
    hold (16 values up to 2^15, 8 up to 2^23, 4 up to 2^27)."""
    return _size_bits(capacity).bit_length() - 1


def _bitmap_word_bits(capacity: int) -> int:
    """log2 of the bitmap directory's words W, static from the build
    capacity: 2^14 words a build row, at most the head — from 2^10 rows
    up the whole head, 2^28 key values at 16 a word."""
    return min(max(1, (capacity - 1).bit_length()) + 14, _HEAD_BITS)


class BuildSide(NamedTuple):
    """The sorted build side and the index a probe row finds its run
    of equal lanes with (``probe_runs``)."""
    sorted_lane: jax.Array  # uint64[cap]: m usable lanes ascending, then U64MAX
    order: jax.Array        # sorted position -> build row
    m: jax.Array            # usable build rows
    directory: jax.Array    # int32[D+1]: first position in [0, m] whose
    #                         bucket (``lane >> shift``) is >= b
    run_len: jax.Array      # int32[cap]: entries of [i, m) equal to entry i
    steps: jax.Array        # int32: bisection steps the fullest bucket
    #                         needs; 0 where exact or bitmap
    exact: jax.Array        # bool: a bucket is ONE key value, no search
    base: jax.Array         # uint64: the least usable build key
    words: jax.Array        # uint32[D+1]: directory[b] above the bucket's
    #                         size in the low ``_size_bits(cap)`` bits
    packed: jax.Array       # bool: every bucket's size fits those bits
    linear: jax.Array       # bool: the lane is ``key - base``, not a hash
    shift: jax.Array        # uint64: a lane's bucket is ``lane >> shift``
    bitmap: jax.Array       # bool: the bitmap directory serves the probe
    marks: jax.Array        # uint32[W+1]: the bitmap directory, word w
    #                         the first position of its keys above one bit
    #                         a key value of [w << s, (w + 1) << s)


def build_side(batch: Batch, key_names: Sequence[str],
               word_bits: Optional[int] = None) -> BuildSide:
    """Sort the build side by key lane and index it. The first m
    entries of ``sorted_lane`` are the usable keys, the tail is forced
    to U64MAX (and counted into no bucket and no run). ``word_bits``:
    log2 of the bitmap directory's words, ``_bitmap_word_bits`` unless
    the caller holds the side under a budget that cannot spare them (0:
    one word, too few for any span the exact directory cannot serve)."""
    key, usable = equality_lane(batch, key_names)
    cap = batch.capacity
    bits = _directory_bits(cap)
    head_bits = min(bits, _HEAD_BITS)
    s = _bitmap_shift(cap)
    if word_bits is None:
        word_bits = _bitmap_word_bits(cap)
    reach = (1 << word_bits) << s       # the key values the bitmap holds
    m = jnp.sum(usable.astype(jnp.int64))
    if _integer_key(batch, key_names):
        # the range of the usable keys, in the order of the integers
        # they are; the span in unsigned arithmetic, where one past
        # 2^63 cannot wrap into a small number
        signed = key.astype(jnp.int64)
        info = jnp.iinfo(jnp.int64)
        base = jnp.min(jnp.where(usable, signed, info.max))
        span = (jnp.max(jnp.where(usable, signed, info.min))
                .astype(jnp.uint64) - base.astype(jnp.uint64))
        # the key itself is the lane wherever a directory over it can
        # serve: the exact one, or the bitmap
        linear = (m > 0) & (span < jnp.uint64(max(1 << bits, reach)))
        base = base.astype(jnp.uint64)
    else:
        span = jnp.zeros((), jnp.uint64)
        linear, base = jnp.zeros((), bool), jnp.zeros((), jnp.uint64)
    lane = _lane_of(key, linear, base)
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
    pos = jnp.arange(cap, dtype=jnp.int32)
    live = pos < m
    sorted_lane = jnp.where(live, jnp.take(lane, order), _U64MAX)

    # run lengths: entry i's run ends at the first position after it
    # that differs from the one before, or is dead (m at the latest)
    after = pos + 1
    ends = jnp.concatenate([sorted_lane[1:] != sorted_lane[:-1],
                            jnp.ones((1,), bool)]) | (after >= m)
    run_len = jnp.where(
        live, jax.lax.cummin(jnp.where(ends, after, cap), reverse=True)
        - pos, 0)

    # the form: the bitmap where the keys are unique and the exact
    # directory cannot serve them from its head, exact where the range
    # fits the directory, else a search of buckets — the hash's top
    # bits, or the key's where it is the lane (duplicates in a range
    # the exact directory cannot hold)
    bitmap = (linear & (jnp.max(run_len) <= 1)
              & (span >= jnp.uint64(1 << head_bits))
              & (span < jnp.uint64(reach)))
    exact = linear & (span < jnp.uint64(1 << bits)) & ~bitmap
    width = 64 - jax.lax.clz(span).astype(jnp.int32)
    shift = jnp.where(exact, 0, jnp.where(
        linear, jnp.maximum(width - head_bits, 0),
        64 - head_bits)).astype(jnp.uint64)
    # only the form the side uses is built: one scatter of the build rows
    directory, words, fullest, marks = _index(sorted_lane, live, shift,
                                              bitmap, word_bits)
    # a lower-bound bisection over n entries takes bit_length(n) steps
    steps = jnp.where(exact, 0, jnp.sum(
        (fullest >> jnp.arange(31, dtype=jnp.int32)) > 0, dtype=jnp.int32))
    return BuildSide(sorted_lane, order, m, directory, run_len, steps,
                     exact, base, words, fullest < (1 << _size_bits(cap)),
                     linear, shift, bitmap, marks)


@functools.partial(jax.jit, static_argnums=4)
def _index(sorted_lane, live, shift, bitmap, w_bits):
    """(directory, words, the fullest bucket's size, marks) of a sorted
    build lane: the directory of buckets ``lane >> shift`` where
    ``bitmap`` is false, else the bitmap directory of ``2^w_bits``
    words (the other's arrays empty). Jitted: an eager build side
    reuses one program a shape."""
    cap = sorted_lane.shape[0]
    bits, k, s = _directory_bits(cap), _size_bits(cap), _bitmap_shift(cap)

    def directory():
        # a histogram of the live entries' buckets, summed up. Never a
        # search of the D boundaries, which would cost what the
        # directory saves. The bucket rises with the lane
        bucket = jnp.minimum(sorted_lane >> shift,
                             jnp.uint64((1 << bits) - 1)).astype(jnp.int32)
        directory = jnp.cumsum(
            jnp.zeros(((1 << bits) + 1,), jnp.int32).at[bucket + 1].add(
                live.astype(jnp.int32), indices_are_sorted=True,
                mode="promise_in_bounds"))
        # the word a probe row reads both bounds from: entry D, the
        # out-of-range lane of an exact directory, holds (m, 0)
        sizes = jnp.concatenate([directory[1:] - directory[:-1],
                                 jnp.zeros((1,), jnp.int32)])
        words = ((directory.astype(jnp.uint32) << jnp.uint32(k))
                 | sizes.astype(jnp.uint32))
        return (directory, words, jnp.max(sizes),
                jnp.zeros(((1 << w_bits) + 1,), jnp.uint32))

    def marks():
        # the keys are unique: ONE sorted scatter-add of a bit a key
        # makes the bitmaps, and the running count of their bits the
        # positions. Dead entries add nothing to word W, which holds
        # (m, 0): a key past the range reads no match
        at = jnp.where(live, sorted_lane, jnp.uint64((1 << w_bits) << s))
        marks = jnp.zeros(((1 << w_bits) + 1,), jnp.uint32).at[
            (at >> jnp.uint64(s)).astype(jnp.int32)].add(
            jnp.where(live, jnp.uint32(1) << (
                at & jnp.uint64((1 << s) - 1)).astype(jnp.uint32), 0),
            indices_are_sorted=True, mode="promise_in_bounds")
        ones = jax.lax.population_count(marks)
        first = jnp.cumsum(ones) - ones
        empty = jnp.zeros(((1 << bits) + 1,), jnp.int32)
        return (empty, empty.astype(jnp.uint32), jnp.zeros((), jnp.int32),
                (first << jnp.uint32(k)) | marks)

    return jax.lax.cond(bitmap, marks, directory)


def _at(lane, index):
    # a gather of indices known to be inside the lane: no clamp, no fill
    return lane.at[index].get(mode="promise_in_bounds")


@jax.jit
def probe_runs(side: BuildSide, key_p, usable_p):
    """(left, count) per probe row, from its key (``equality_lane``):
    ``left`` is the first position of the sorted build lane that is >=
    the row's lane (clipped to m), ``count`` the entries equal to it —
    what ``searchsorted`` left and right gave, exactly, for every lane
    whatever its distribution. The ONE probe of the engine: joins, semi
    joins and the streamed probe (exec/streamjoin.py) all come through
    here. Jitted, as ``searchsorted`` is: an eager caller reuses one
    program a shape."""
    size = side.directory.shape[0] - 1
    head_bits = min(size.bit_length() - 1, _HEAD_BITS)
    head = 1 << head_bits
    last = side.sorted_lane.shape[0] - 1
    k = _size_bits(last + 1)
    # ``_lane_of`` as a branch: the lane not taken is not computed
    lane_p = jax.lax.cond(side.linear, lambda: key_p - side.base,
                          lambda: mix64(key_p))

    def marks():
        # ONE word: the first position of its keys, and the bit of each
        # key value it holds; the keys below the row's in the word are
        # the bits under its own. Word W, past the range, holds (m, 0)
        s = _bitmap_shift(last + 1)
        w = _at(side.marks, jnp.minimum(
            lane_p >> jnp.uint64(s),
            jnp.uint64(side.marks.shape[0] - 1)).astype(jnp.int32))
        bit = (lane_p & jnp.uint64((1 << s) - 1)).astype(jnp.uint32)
        low = w & jnp.uint32((1 << k) - 1)
        left = (w >> jnp.uint32(k)) + jax.lax.population_count(
            low & ((jnp.uint32(1) << bit) - 1))
        return (left.astype(jnp.int32),
                ((low >> bit) & 1).astype(jnp.int32))

    def bounds(top):
        # the bucket's bounds, from the first ``top + 1`` entries.
        # Where exact, a key outside the build side's range has a lane
        # of D or more and reads [m, m): its lower bound
        bucket = jnp.minimum(lane_p >> side.shift,
                             jnp.uint64(top)).astype(jnp.int32)

        def word():
            w = _at(side.words[:top + 1], bucket)
            lo = (w >> jnp.uint32(k)).astype(jnp.int32)
            return lo, lo + (w & jnp.uint32((1 << k) - 1)).astype(jnp.int32)

        def pair():
            directory = side.directory[:top + 1]
            return (_at(directory, bucket),
                    _at(directory, jnp.minimum(bucket + 1, top)))

        # one gather where the sizes fit the word, else the two sums (a
        # branch, as below: a gather costs per element either way)
        return jax.lax.cond(side.packed, word, pair)

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

    def directory():
        if size > head:
            # every live bucket lies in the head unless an exact range
            # passes it: the head alone is a table the chip gathers
            # from at its fast rate
            lo, hi = jax.lax.cond(
                _at(side.directory, head) < side.m,
                lambda: bounds(size), lambda: bounds(head))
        else:
            lo, hi = bounds(size)

        def search():
            left, _, hit = jax.lax.fori_loop(
                0, side.steps, step,
                (lo, hi, jnp.zeros(lane_p.shape, bool)))
            return left, jnp.where(
                hit, _at(side.run_len, jnp.minimum(left, last)), 0)

        # a bucket of ONE key value is the run itself: neither the
        # search nor the run-length gather runs (a branch, not a
        # select: a gather costs the chip per element whatever becomes
        # of it)
        return jax.lax.cond(side.exact, lambda: (lo, hi - lo), search)

    # the bitmap directory answers from its word alone: no bounds, no
    # search, no run length
    left, count = jax.lax.cond(side.bitmap, marks, directory)
    return (left.astype(jnp.int64),
            jnp.where(usable_p, count, 0).astype(jnp.int64))


def match_runs(probe: Batch, build: Batch,
               probe_keys: Sequence[str], build_keys: Sequence[str]):
    """(left, count, side): ``match_counts`` with the whole build side."""
    probe, build = align_string_keys(probe, build, probe_keys, build_keys)
    key_p, usable_p = equality_lane(probe, probe_keys)
    side = build_side(build, build_keys)
    left, count = probe_runs(side, key_p, usable_p)
    return left, count, side


def total_and_mode(eff, side: BuildSide, probe: Batch):
    """int64[6], [output rows, the probe's bisection steps, whether the
    directory was exact, the probe side's live rows, whether a probe
    row read its bounds as one word, whether the bitmap directory
    served it]: what a count program hands the host in its ONE read
    (the executors' ``join_total``)."""
    return jnp.stack([jnp.sum(eff).astype(jnp.int64),
                      side.steps.astype(jnp.int64),
                      side.exact.astype(jnp.int64),
                      probe.num_rows_device(),
                      side.packed.astype(jnp.int64),
                      side.bitmap.astype(jnp.int64)])


def match_counts(probe: Batch, build: Batch,
                 probe_keys: Sequence[str], build_keys: Sequence[str]):
    """Per-probe-row (start, count) of the build match run + the build
    permutation.

    start indexes the *sorted* build order; map through perm for payload.
    """
    left, count, side = match_runs(probe, build, probe_keys, build_keys)
    return left, count, side.order


# ``run_positions`` by a histogram costs by the RUNS (one scatter update
# each), by a search by the positions asked for times the steps of a
# bisection over the runs: the histogram where
# runs <= _HISTOGRAM_K x positions x bit_length(runs). On a v5e the
# sorted scatter-add takes 8.83 ns an update whatever it is added into
# (296.2 ms for 2^25 runs), a bisection step over 2^25 int32 sums
# 18-25 ns a position: 2^25 runs -> 2^22 positions 297.0 ms by the
# histogram and 2,344 by the search, 2^25 -> 2^15 296.3 and 20.9. They
# cross at runs = 2.3 x positions x 26, between 2^25 -> 2^20 (296 /
# 557) and -> 2^19 (296 / 261): 3 leaves that last shape, a first join
# of the TPC-DS cell, on the form whose cost the data cannot move
# (PERF.md §6, PR 35).
_HISTOGRAM_K = 3


def expand_form(runs: int, positions: int) -> str:
    """``histogram`` or ``search``: the form ``run_positions`` takes
    for ``runs`` counts expanded into ``positions`` output rows, from
    the two static sizes alone — what the host that dispatches an
    expand program says of it (the ``form`` of its span and of
    ``trino_tpu_join_expands_total``)."""
    return ("histogram" if runs <= _HISTOGRAM_K * positions
            * runs.bit_length() else "search")


def run_positions(incl, out_capacity: int):
    """For ``i`` in [0, out_capacity), how many entries of the
    non-decreasing ``incl`` (the running sum of per-row output counts)
    are <= i: the row that output position i belongs to — exactly what
    ``jnp.searchsorted(incl, i, side="right")`` returns, as int32, for
    every input (zero counts anywhere, counts above one, a total below
    or above ``out_capacity``). One algorithm in two forms, chosen from
    the static sizes (``expand_form``): a histogram of ``incl`` summed
    up — ONE scatter-add of sorted indices and one int32 ``cumsum``, no
    loop, the way ``build_side`` makes its directory — or, where a few
    positions are asked of many runs, the bisection."""
    # a sum past the last position answers for none: cut down to
    # out_capacity the sums fit 32 bits (a 64-bit gather is two)
    at = jnp.minimum(incl, out_capacity).astype(jnp.int32)
    if expand_form(incl.shape[0], out_capacity) == "search":
        return jnp.searchsorted(
            at, jnp.arange(out_capacity, dtype=jnp.int32), side="right")
    # those that were cut fall into one slot more, which is left out
    return jnp.cumsum(
        jnp.zeros((out_capacity + 1,), jnp.int32).at[at].add(
            1, indices_are_sorted=True,
            mode="promise_in_bounds")[:out_capacity])


def expand_join(probe: Batch, build: Batch, start, count, order,
                out_capacity: int, join_type: str = "inner",
                build_prefix: str = "") -> Batch:
    """Materialize join output rows given per-probe match runs.

    join_type: inner | left. For 'left', probe rows with no match emit one
    row with NULL build columns (reference: LookupJoinOperator
    outer-position tracking). The capacities are those of ``count``
    (the probe side's) and ``order`` (the build side's): a side may be
    handed with no lane at all, where the plan above reads none of it;
    only the lanes handed are gathered."""
    outer = join_type == "left"
    probe_capacity = count.shape[0]
    live_p = (jnp.arange(probe_capacity, dtype=jnp.int64)
              < probe.num_rows_device())
    eff_count = (jnp.where(live_p, jnp.maximum(count, 1), 0)
                 if outer else count)
    no_match = count == 0

    incl = jnp.cumsum(eff_count)
    total = incl[-1]
    offs = incl - eff_count  # exclusive

    i = jnp.arange(out_capacity, dtype=jnp.int64)
    p = jnp.clip(run_positions(incl, out_capacity), 0, probe_capacity - 1)
    j = i - jnp.take(offs, p)
    b_sorted = jnp.take(start, p) + j
    b = jnp.take(order, jnp.clip(b_sorted, 0, order.shape[0] - 1))

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
    key_p, usable_p = equality_lane(probe, probe_keys)
    _, count = probe_runs(build_side(build, build_keys), key_p, usable_p)
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
