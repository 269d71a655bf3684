"""Pallas TPU kernel: fused small-domain grouped sums/counts.

Reference parity: the hot loop of HashAggregationOperator
(operator/HashAggregationOperator.java:381-413) for low-cardinality
GROUP BY — the q1 shape. The XLA fallback in ops/groupby.py
(_masked_agg) lowers every (group, aggregate) pair to its own masked
reduction, i.e. up to nseg x K passes over the value lanes. This kernel
does ONE pass over HBM: per row-block, a one-hot [B, G] matrix is
built from the packed group ids and every aggregate lane is reduced
with a single [K, B] x [B, G] matmul on the MXU, accumulating per-block
partials that are combined in f64 outside the kernel.

f64 strategy (the TPU MXU is f32): each f64 lane is split into THREE
f32 lanes — two 12-bit fixed-point digit lanes (integers scaled by the
lane's power-of-2 magnitude, so block sums of <= 512 values stay below
2^24 and are EXACT in f32) plus a tiny residual lane (|r| <= 2^-25 of
the lane magnitude, whose own f32 accumulation error is ~2^-49
relative). The three per-group sums recombine in f64 afterwards, so
the result matches a pure-f64 reduction to ~1e-14 relative — naive
f32 one-hot matmuls lose ~1e-4 at money-like magnitudes (measured),
which SQL aggregate tolerances cannot absorb. Counts are exact.

Gating: used on the TPU backend (or when TRINO_TPU_PALLAS=interpret,
which runs the kernel in interpreter mode — how the CPU test suite
exercises it). Kinds beyond sum/count keep the XLA path; exact-sum
types (DECIMAL, wide ints) also stay on the XLA path.
"""

from __future__ import annotations

import os
from functools import partial
from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp

BLOCK = 512
G_PAD = 128          # one-hot width: MXU-friendly and >= FAST_DOMAIN+1


def mode() -> str:
    """'tpu' (real kernel), 'interpret' (forced, for CPU tests), or
    '' (disabled). On the tpu backend the kernel is always used: a
    Mosaic compile error propagates to the query, it never selects
    the XLA path silently."""
    env = os.environ.get("TRINO_TPU_PALLAS", "auto")
    if env == "interpret":
        return "interpret"
    if env in ("auto", "1") and jax.default_backend() == "tpu":
        return "tpu"
    return ""


def _kernel(gid_ref, vals_ref, out_ref):
    g = gid_ref[:]                                   # [1, B] int32
    b = g.shape[1]
    # one-hot built TRANSPOSED, [G, B]: the row ids stay on the lane
    # axis they arrive on (a [B, G] one-hot needs g[:, None], a
    # lane->sublane relayout per block)
    onehot_t = (g == jax.lax.broadcasted_iota(
        jnp.int32, (G_PAD, b), 0)).astype(jnp.float32)
    out_ref[0] = jax.lax.dot_general(
        vals_ref[:], onehot_t,                       # [K, B] x [G, B]^T
        dimension_numbers=(((1,), (1,)), ((), ())),
        # HIGHEST = true-f32 matmul (bf16 multi-pass decomposition on
        # the MXU); the default TPU bf16 path rounds the 12-bit digit
        # lanes and breaks the exact-sum design (measured 2e-4)
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)          # [K, G_PAD]


def _i0():
    # index maps must return int32: under jax_enable_x64 a literal 0
    # is an int64, which Mosaic cannot legalize
    return jnp.int32(0)


@partial(jax.jit, static_argnames=("interpret",))
def _grouped_sums_impl(gid: jax.Array, vals: jax.Array,
                       interpret: bool) -> jax.Array:
    """gid [cap] int32, vals [K, cap] f32 -> f64 [K, G_PAD] per-group
    sums."""
    from jax.experimental import pallas as pl
    k, cap = vals.shape
    b = min(BLOCK, cap)
    nblocks = cap // b
    partials = pl.pallas_call(
        _kernel,
        grid=(nblocks,),
        in_specs=[
            # gid rides as [1, cap] with (1, b) blocks: a 1-D s32[cap]
            # operand gets XLA tile T(1024) where Mosaic wants T(512)
            pl.BlockSpec((1, b), lambda i: (_i0(), i)),
            pl.BlockSpec((k, b), lambda i: (_i0(), i)),
        ],
        out_specs=pl.BlockSpec((1, k, G_PAD),
                               lambda i: (i, _i0(), _i0())),
        out_shape=jax.ShapeDtypeStruct((nblocks, k, G_PAD),
                                       jnp.float32),
        interpret=interpret,
        name="grouped_sums",    # the kernel's name in the device trace
    )(gid.reshape(1, cap), vals)
    return jnp.sum(partials.astype(jnp.float64), axis=0)


def grouped_sums(gid: jax.Array, lanes: Sequence[jax.Array],
                 nseg: int, interpret: bool = False) -> List[jax.Array]:
    """Per-group f64 sums for every lane.

    ``gid``: int32 [cap] packed group ids; rows to exclude from ALL
    lanes must carry an id >= G_PAD (they one-hot to zero). Per-lane
    exclusion is the caller's job (zero the lane entry — exact for
    sums). Returns one f64 [nseg] array per input lane.
    """
    assert nseg <= G_PAD
    cols: List[jax.Array] = []
    splits: List[Tuple[int, int, jax.Array]] = []  # (a_idx, scale)
    for lane in lanes:
        f = jnp.asarray(lane).astype(jnp.float64)
        # power-of-2 magnitude scale; digits a (top 12 bits), b (next
        # 12), residual r — a/b sums are exact in f32 (<= 2^21 per
        # 512-row block), r is ~2^-25 of the magnitude
        maxabs = jnp.max(jnp.abs(f))
        s = jnp.exp2(jnp.ceil(jnp.log2(jnp.maximum(maxabs, 1e-300))))
        s = jnp.where(maxabs > 0, s, 1.0)
        a = jnp.round(f / s * 4096.0)
        r1 = f - a * (s / 4096.0)
        b = jnp.round(r1 / s * 16777216.0)
        r2 = r1 - b * (s / 16777216.0)
        splits.append((len(cols), s))
        cols.extend([a.astype(jnp.float32), b.astype(jnp.float32),
                     r2.astype(jnp.float32)])
    k8 = max(8, -(-len(cols) // 8) * 8)  # sublane-friendly row count
    while len(cols) < k8:
        cols.append(jnp.zeros_like(cols[0]))
    vals = jnp.stack(cols, axis=0)       # [K8, cap] f32
    if isinstance(vals, jax.core.Tracer):
        # inside a cached program's trace: part of THAT dispatch
        sums = _grouped_sums_impl(jnp.asarray(gid, jnp.int32), vals,
                                  interpret)
    else:
        # called eagerly: a dispatch of its own, so a span and a count
        from ..obs.trace import dispatch_span
        with dispatch_span(None, "grouped_sums:kernel"):
            sums = _grouped_sums_impl(jnp.asarray(gid, jnp.int32),
                                      vals, interpret)
    return [sums[i, :nseg] * (s / 4096.0)
            + sums[i + 1, :nseg] * (s / 16777216.0)
            + sums[i + 2, :nseg]
            for i, s in splits]
