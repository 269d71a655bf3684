"""The bitmap directory of the join probe (ops/join.py): a build side of
UNIQUE values of one integer key whose span the exact directory cannot
serve from its head keeps one bit a key value, ``2^s`` values a 32-bit
word below the first sorted position of the word's keys; a probe row
reads ONE word and searches nothing.

- over build capacities whose words hold 16 and 8 values, ``probe_runs``
  answers what ``searchsorted`` gives on the sorted build lane for every
  probe row — matches, misses inside a word and in empty words, keys
  below the least and past the greatest, NULL and dead probe rows — and
  the words are the layout the docstring gives; an empty build side
  answers no row;
- the form declines where it must, and the answers are those of the
  form taken: one duplicate key (a search over the key's top bits), a
  span past the bitmap's reach (the hash), a span inside the head (the
  exact directory).
"""

import numpy as np
import pytest

import jax.numpy as jnp

from trino_tpu.columnar import Batch, Column
from trino_tpu.ops import join as join_ops
from trino_tpu.types import BIGINT

_I64 = np.iinfo(np.int64)
_U64MAX = np.uint64(2**64 - 1)


def _keys(cap, keys, null_at=(), rows=None):
    """A one-key BIGINT batch of ``cap`` rows' capacity: the first
    ``rows`` of ``keys`` live (all by default), the rest dead but holding
    their keys; NULL at ``null_at``."""
    data = np.zeros(cap, np.int64)
    data[:len(keys)] = keys
    valid = None
    if len(null_at):
        valid = np.ones(cap, bool)
        valid[list(null_at)] = False
    col = Column(BIGINT, jnp.asarray(data),
                 None if valid is None else jnp.asarray(valid))
    return Batch({"k": col}, len(keys) if rows is None else rows)


def _probe_equals_searchsorted(probe, build):
    """The side, after holding ``probe_runs`` to numpy: ``left`` and
    ``count`` are what ``searchsorted`` left and right give on the
    sorted usable build lanes of the mode that engaged, and ``count``
    is the number of equal build keys."""
    key_p, usable_p = join_ops.equality_lane(probe, ["k"])
    side = join_ops.build_side(build, ["k"])
    left, count = map(np.asarray, join_ops.probe_runs(side, key_p,
                                                      usable_p))
    key_b, usable_b = map(np.asarray, join_ops.equality_lane(build, ["k"]))
    key_p, usable_p = np.asarray(key_p), np.asarray(usable_p)
    m = int(usable_b.sum())
    if bool(side.linear):
        base = key_b[usable_b].astype(np.int64).min().astype(np.uint64)
        assert int(side.base) == int(base)
        lane_b, lane_p = key_b - base, key_p - base     # modulo 2^64
    else:
        lane_b, lane_p = (np.asarray(join_ops.mix64(k))
                          for k in (key_b, key_p))
    want = np.full(build.capacity, _U64MAX)
    want[:m] = np.sort(lane_b[usable_b])
    assert np.array_equal(np.asarray(side.sorted_lane), want)
    lo = np.minimum(np.searchsorted(want, lane_p, "left"), m)
    hi = np.minimum(np.searchsorted(want, lane_p, "right"), m)
    assert np.array_equal(left, lo)
    assert np.array_equal(count, np.where(usable_p, hi - lo, 0))
    vals, n = np.unique(key_b[usable_b], return_counts=True)
    at = np.minimum(np.searchsorted(vals, key_p), max(len(vals) - 1, 0))
    same = (vals[at] == key_p) if len(vals) else np.zeros(len(key_p), bool)
    assert np.array_equal(
        count, np.where(usable_p & same, n[at] if len(vals) else 0, 0))
    return side


def _mode(side, probe):
    """(bitmap, exact, steps, packed), the last entry of the one read
    beside it: what ``total_and_mode`` hands the host."""
    read = np.asarray(join_ops.total_and_mode(
        jnp.zeros((probe.capacity,), jnp.int64), side, probe))
    assert read.shape == (6,) and read[5] == int(side.bitmap)
    return (bool(side.bitmap), bool(side.exact), int(side.steps),
            bool(side.packed))


def _wide_unique(rng, cap, span, base=-12_345):
    """``cap - 3`` unique keys over [base, base + span), both ends among
    them; the dead tail holds build keys too. The probe: matches, the
    neighbours of build keys (inside their words), keys of empty words,
    keys below the least and past the greatest, to the ends of int64,
    NULL rows, and a dead tail of build keys."""
    m = cap - 3
    build = base + np.sort(rng.choice(span - 2, m - 2, replace=False) + 1)
    build = np.concatenate([[base], build, [base + span - 1]])
    rng.shuffle(build)
    taken = set(build.tolist())
    reach = (1 << join_ops._bitmap_word_bits(cap)) \
        << join_ops._bitmap_shift(cap)
    hits = rng.choice(build, 200)
    near = np.concatenate([hits - 1, hits + 1, hits + 3])
    far = base + rng.integers(0, span, 200)
    edges = [base - 1, base - 2**40, _I64.min, base + span,
             base + span + 1, base + span + 2**40, _I64.max,
             base + reach - 1, base + reach, base, base + span - 1]
    probe = np.concatenate([hits, near, far, edges])
    probe_cap = 1 << int(np.ceil(np.log2(len(probe) + 16)))
    rows = len(probe)
    probe = np.concatenate([probe, build[:probe_cap - rows]])
    assert any(k not in taken for k in near) and any(
        k not in taken for k in far)
    return (_keys(probe_cap, probe, null_at=(0, 5, 201), rows=rows),
            _keys(cap, np.concatenate([build, build[:3]]), rows=m))


@pytest.mark.parametrize("cap", [1 << 4, 1 << 10, 1 << 12, 1 << 16])
def test_the_bitmap_answers_what_searchsorted_does(cap):
    """A span of 40 key values a build row (more than the exact
    directory's 32): the bitmap, with its words as the layout says."""
    rng = np.random.default_rng(cap)
    probe, build = _wide_unique(rng, cap, 40 * cap)
    side = _probe_equals_searchsorted(probe, build)
    assert _mode(side, probe) == (True, False, 0, True)
    # word w: the first sorted position of its keys above one bit a key
    # value of [w << s, (w + 1) << s); word W holds (m, 0)
    s, k = join_ops._bitmap_shift(cap), join_ops._size_bits(cap)
    assert (1 << s) == {16: 16, 1024: 16, 4096: 16, 65536: 8}[cap]
    marks = np.asarray(side.marks)
    lanes = np.asarray(side.sorted_lane)[:int(side.m)].astype(np.int64)
    word, bit = lanes >> s, lanes & ((1 << s) - 1)
    bits = np.zeros(marks.shape[0], np.uint32)
    np.bitwise_or.at(bits, word, (1 << bit).astype(np.uint32))
    keys = np.zeros(marks.shape[0], np.uint32)
    np.add.at(keys, word, 1)
    first = np.cumsum(keys, dtype=np.uint32) - keys
    assert marks.dtype == np.uint32
    assert np.array_equal(marks, (first << np.uint32(k)) | bits)
    assert int(marks[-1]) == int(side.m) << k


def test_an_empty_build_side_answers_no_row():
    rng = np.random.default_rng(3)
    probe, _ = _wide_unique(rng, 1 << 4, 640)
    side = _probe_equals_searchsorted(probe, _keys(16, [7, 9], rows=0))
    assert int(side.m) == 0
    assert _mode(side, probe)[0] is False


def _duplicate(rng):
    probe, build = _wide_unique(rng, 1 << 10, 40 << 10)
    keys = np.array(build.column("k").data)
    keys[1] = keys[0]
    return probe, _keys(1 << 10, keys, rows=build.num_rows)


def _past_reach(rng):
    # keys up to 2^40: past the 2^28 key values 2^24 words hold
    probe, build = _wide_unique(rng, 1 << 10, 1 << 40)
    return probe, build


def _inside_head(rng):
    # 30 values a build row: the exact directory serves it
    return _wide_unique(rng, 1 << 10, 30 << 10)


@pytest.mark.parametrize("case,mode", [
    (_duplicate, lambda b, e, steps, p: not e and steps > 0),
    (_past_reach, lambda b, e, steps, p: not e and steps > 0),
    (_inside_head, lambda b, e, steps, p: e and steps == 0),
], ids=["duplicate", "past_reach", "inside_head"])
def test_the_bitmap_declines_where_it_must(case, mode):
    probe, build = case(np.random.default_rng(11))
    side = _probe_equals_searchsorted(probe, build)
    bitmap, exact, steps, packed = _mode(side, probe)
    assert not bitmap and packed and mode(bitmap, exact, steps, packed)
    # the lane: the key itself unless the span is past any directory's
    # reach, where it is the hash
    assert bool(side.linear) == (case is not _past_reach)
