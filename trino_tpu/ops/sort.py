"""Device sort / TopN — OrderByOperator and TopNOperator, TPU style.

Reference parity: operator/OrderByOperator.java (PagesIndex sort),
operator/TopNOperator.java, util/MergeSortedPages for distributed sort.
On TPU, multi-key ordering is a single ``jnp.lexsort`` over order-preserving
uint64 key lanes — sorting networks map well onto the VPU, and one fused
sort replaces the row-at-a-time comparator Trino generates via
OrderingCompiler (sql/gen/OrderingCompiler.java).

Per sort key we emit a small tuple of comparable lanes (rather than one
packed uint64 — the TPU backend's x64 emulation cannot bitcast f64 lanes):
a null-ordering lane, for floats a NaN lane, then the value lane (negated /
complemented for DESC). A leading liveness lane pushes dead rows past the
end. ``jnp.lexsort`` over the lane list realizes the full ORDER BY.

Trino default null ordering: nulls are largest (ASC -> last, DESC -> first;
reference: sql/tree/SortItem.java UNDEFINED + SortOrder.ASC_NULLS_LAST).
Float total order: NaN is largest (reference: spi/type/DoubleType.java
comparison via Double.compare).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp

from ..columnar import Batch, Column
from ..types import is_string


@dataclass(frozen=True)
class SortKey:
    column: str
    ascending: bool = True
    nulls_first: Optional[bool] = None  # None -> Trino default (nulls = max)

    def resolved_nulls_first(self) -> bool:
        if self.nulls_first is not None:
            return self.nulls_first
        return not self.ascending  # nulls largest


def stable_lexsort(lanes: Sequence[jax.Array]) -> jax.Array:
    """Stable permutation (int32) ordering rows by ``lanes``, MOST
    significant first — the permutation ``jnp.lexsort(lanes[::-1])``
    gives, built as one stable SINGLE-key sort per lane from the least
    significant lane up, each carrying only an int32 row index.

    Why not the one multi-key sort: the chip's compiler emits a sorting
    network stage by stage with the whole comparator in each, so
    compile time grows with the 32-bit words per row and with every
    extra key (compiled for a v5e, PERF.md: four lanes i32,u64,u64,u64
    at 2^14 rows — jnp.lexsort 79 s, one 4-key sort with an int32 iota
    55 s, these four passes 10 s; u64 key at 2^20 rows with jnp's int64
    iota 82 s, with an int32 iota 46 s). Capacities are far below 2^31,
    so int32 indexes every row."""
    perm = jax.lax.iota(jnp.int32, lanes[0].shape[0])
    for i, lane in enumerate(reversed(lanes)):
        key = lane if i == 0 else jnp.take(lane, perm)
        _, perm = jax.lax.sort((key, perm), dimension=0, is_stable=True,
                               num_keys=1)
    return perm


def _narrow(v: jax.Array) -> jax.Array:
    # signed <=32-bit integer lanes sort as int32: same order, half
    # the words of the int64 every other integer lane is widened to
    small = (jnp.issubdtype(v.dtype, jnp.signedinteger)
             and v.dtype.itemsize <= 4)
    return v.astype(jnp.int32 if small else jnp.int64)


def _key_lanes_for(col: Column, asc: bool, nulls_first: bool,
                   live: jax.Array) -> List[jax.Array]:
    d = jnp.asarray(col.data)
    lanes: List[jax.Array] = []

    # null-ordering lane: 0 sorts first. A column without a validity
    # mask has no NULLs: the lane would be constant, so it is left out
    nullable = col.valid is not None
    if nullable:
        is_null = (~col.valid_mask()) & live
        lanes.append(jnp.where(is_null, 0 if nulls_first else 1,
                               1 if nulls_first else 0)
                     .astype(jnp.int32))
    n_flag_lanes = len(lanes)

    if is_string(col.type):
        ranks = jnp.asarray(col.dictionary.rank_codes())
        v = jnp.take(ranks, jnp.clip(d, 0, max(len(ranks) - 1, 0)),
                     mode="clip").astype(jnp.int32)
        lanes.append(v if asc else -v)
    elif d.dtype in (jnp.float32, jnp.float64):
        f = d.astype(jnp.float64)
        nan = jnp.isnan(f)
        lanes.append(jnp.where(nan, 1 if asc else 0,
                               0 if asc else 1).astype(jnp.int32))
        v = jnp.where(nan, 0.0, f)
        lanes.append(v if asc else -v)
    elif d.dtype == jnp.bool_:
        v = d.astype(jnp.int32)
        lanes.append(v if asc else 1 - v)
    else:
        v = _narrow(d)
        lanes.append(v if asc else jnp.bitwise_not(v))
    # neutralize null rows' value lanes so null ordering is decided solely
    # by the null lane (keeps lexsort stable among nulls)
    if nullable:
        lanes[n_flag_lanes:] = [jnp.where(is_null, jnp.zeros_like(l), l)
                                for l in lanes[n_flag_lanes:]]
    return lanes


def sort_lanes(batch: Batch, keys: Sequence[SortKey]) -> List[jax.Array]:
    """Lane list, most-significant first: liveness, then per-key lanes."""
    live = batch.row_valid()
    lanes: List[jax.Array] = [(~live).astype(jnp.int32)]
    for k in keys:
        col = batch.column(k.column)
        lanes.extend(_key_lanes_for(col, k.ascending,
                                    k.resolved_nulls_first(), live))
    return lanes


def sort_order(batch: Batch, keys: Sequence[SortKey]) -> jax.Array:
    """Stable permutation realizing ORDER BY."""
    return stable_lexsort(sort_lanes(batch, keys))


def sort_batch(batch: Batch, keys: Sequence[SortKey]) -> Batch:
    order = sort_order(batch, keys)
    return batch.gather(order, batch.num_rows)


# LIMITs up to this many rows are picked by selection, not by sorting
TOPN_SELECT_MAX = 128


def _select_first(lanes: Sequence[jax.Array], k: int) -> jax.Array:
    """Row indices of the first ``k`` rows in stable lexicographic
    order of ``lanes`` (most significant first), in that order —
    ``stable_lexsort(lanes)[:k]`` without the sort: ``k`` rounds of a
    masked lexicographic argmin, ties to the lowest row index. Every
    round is a few reductions over the lanes, so the program is tiny
    whatever the key types are."""
    cap = lanes[0].shape[0]
    iota = jax.lax.iota(jnp.int32, cap)
    # rows out of the running take the lane's own maximum: no constant
    # has to survive the chip's float64 (a pair of float32, whose range
    # ends at 3.4e38 and whose infinity is not a plain value)
    tops = [jnp.max(lane) for lane in lanes]

    def pick(i, state):
        taken, out = state
        cand = ~taken
        for lane, top in zip(lanes, tops):
            lo = jnp.min(jnp.where(cand, lane, top))
            cand = cand & (lane == lo)
        row = jnp.min(jnp.where(cand, iota, cap - 1))
        return taken.at[row].set(True), out.at[i].set(row)

    _, out = jax.lax.fori_loop(
        0, k, pick, (jnp.zeros((cap,), bool), jnp.zeros((k,), jnp.int32)))
    return out


def topn_batch(batch: Batch, keys: Sequence[SortKey], n: int) -> Batch:
    """ORDER BY ... LIMIT n (reference: operator/TopNOperator.java).
    The output keeps the input capacity; only its first
    min(num_rows, n) rows are live.

    A small n is SELECTED (``_select_first``), a large one is a full
    device sort then truncate. The same rows in the same order either
    way; the reason for two ways is the chip's compiler: a sorting
    network is emitted stage by stage with the whole multi-lane
    comparator in each, and a float64 key makes that comparator
    enormous (v5e compiler, 2^15 rows, q3's ORDER BY revenue DESC,
    o_orderdate LIMIT 10: 302 s to compile the sort)."""
    count = jnp.minimum(batch.num_rows_device(),
                        jnp.asarray(n, dtype=jnp.int64))
    k = min(int(n), batch.capacity)
    if k > TOPN_SELECT_MAX:
        return Batch(sort_batch(batch, keys).columns, count)
    first = _select_first(sort_lanes(batch, keys), k)
    order = jnp.zeros((batch.capacity,), jnp.int32).at[:k].set(first)
    return batch.gather(order, count)
