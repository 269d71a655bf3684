"""Row selection & compaction — the FilterAndProject inner loop, TPU style.

Reference parity: Trino's compiled PageFilter evaluates a predicate into a
selected-positions array and PageProjection copies survivors
(core/trino-main/.../operator/project/PageProcessor.java,
sql/gen/PageFunctionCompiler.java:101). On TPU the same is a mask +
stable-compaction gather, fused by XLA into the surrounding pipeline.
"""

from __future__ import annotations

from dataclasses import replace
from typing import Tuple, Union

import jax
import jax.numpy as jnp

from ..columnar import Batch


def mask_to_gather(mask: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Turn a boolean row mask into (indices, count).

    indices is capacity-length; the first ``count`` entries are the positions
    of set bits in order; the rest point at position 0 (harmless garbage —
    rows past count are dead by construction).
    """
    cap = mask.shape[0]
    idx = jnp.nonzero(mask, size=cap, fill_value=0)[0]
    count = jnp.sum(mask.astype(jnp.int64))
    return idx, count


def filter_batch(batch: Batch, mask: jax.Array) -> Batch:
    """Keep rows where mask & live; output is compacted with a device
    num_rows (data-dependent cardinality under static shapes)."""
    live = mask & batch.row_valid()
    idx, count = mask_to_gather(live)
    return batch.gather(idx, count)


def compact_batch(batch: Batch, mask: jax.Array,
                  out_capacity: int) -> Batch:
    """``filter_batch`` at a capacity of the caller's choosing: the rows
    where ``mask`` is set (it says which rows live: the batch's own
    prefix is not consulted), in order, in a batch of ``out_capacity``
    lanes. The caller has COUNTED the mask (one host read) and asks for
    ``capacity_for(count)``: after a filter that keeps a thousandth the
    operators above it (a join's build-side sort) run at the capacity
    of what is left, not of what came in. Output row i is the row at
    which the running sum of the mask passes i (``ops/join.py
    run_positions``: a bisection where few rows are asked of many
    lanes, else one scatter-add and a ``cumsum``); no ``nonzero``."""
    from .join import run_positions
    incl = jnp.cumsum(mask.astype(jnp.int32))
    rows = jnp.clip(run_positions(incl, out_capacity), 0,
                    batch.capacity - 1)
    return batch.gather(rows, incl[-1].astype(jnp.int64))


def compact_in_place(batch: Batch, mask: jax.Array) -> Batch:
    """``filter_batch`` at the batch's own capacity WITHOUT a gather: the
    rows where ``mask`` is set (it says which rows live), in order. Row
    i moves left by d_i, the rows dropped before it, one power of two
    at a time: log2(capacity) static shifts, each a select over every
    lane. Kept rows never meet: two kept rows j < k have d_j <= d_k and
    d_k - d_j < k - j, so after the shifts of the low bits of d they
    still lie apart and in order. On the TPU a gather pays for its
    indices one by one (10-20 ns an index at 2^23); a shift streams the
    lanes. Lanes are flat (no nested elements); the slots past the kept
    rows hold stale values."""
    cap = batch.capacity
    cols = batch.columns
    leaves = [(name, part) for name, c in cols.items()
              for part in ("data", "valid", "data2")
              if getattr(c, part) is not None]
    lanes = [jnp.asarray(getattr(cols[name], part)) for name, part in leaves]
    live = mask
    shift = jnp.where(mask, jnp.cumsum((~mask).astype(jnp.int32)), 0)
    step = 1
    while step < cap:
        def up(a, step=step):
            # the value of row i + step, at row i
            return jnp.concatenate([a[step:], jnp.zeros((step,), a.dtype)])
        moving = up(live) & ((up(shift) & step) != 0)
        stay = live & ((shift & step) == 0)
        lanes = [jnp.where(moving, up(x), x) for x in lanes]
        shift = jnp.where(moving, up(shift), shift)
        live = moving | stay
        step <<= 1
    parts: dict = {}
    for (name, part), lane in zip(leaves, lanes):
        parts.setdefault(name, {})[part] = lane
    out = {name: replace(c, **parts[name]) for name, c in cols.items()}
    return Batch(out, jnp.sum(mask.astype(jnp.int64)))


def limit_batch(batch: Batch, limit: Union[int, jax.Array]) -> Batch:
    """LIMIT n without data movement (reference: operator/LimitOperator.java).
    """
    n = jnp.minimum(batch.num_rows_device(),
                    jnp.asarray(limit, dtype=jnp.int64))
    return Batch(batch.columns, n)


def offset_batch(batch: Batch, offset: Union[int, jax.Array]) -> Batch:
    """OFFSET n — shift rows down (reference: sql/planner/plan/OffsetNode)."""
    off = jnp.asarray(offset, dtype=jnp.int64)
    cap = batch.capacity
    idx = jnp.arange(cap, dtype=jnp.int64) + off
    n = jnp.maximum(batch.num_rows_device() - off, 0)
    return batch.gather(jnp.clip(idx, 0, cap - 1), n)
