"""64-bit hashing for repartitioning / hash joins / group-by.

Reference parity: Trino computes per-row raw hashes via per-type
XxHash64-based TypeOperators (core/trino-spi/.../type/TypeOperators.java,
operator/InterpretedHashGenerator.java) and combines columns with
CombineHashFunction (31*h1+h2, operator/scalar/CombineHashFunction.java).
Here we use a splitmix64-style finalizer — fully vectorizable on the VPU —
and the same multiply-combine across key columns.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp

_C1 = jnp.uint64(0xBF58476D1CE4E5B9)
_C2 = jnp.uint64(0x94D049BB133111EB)
_GOLDEN = jnp.uint64(0x9E3779B97F4A7C15)


def mix64(x: jax.Array) -> jax.Array:
    """splitmix64 finalizer over a uint64 lane."""
    x = jnp.asarray(x).astype(jnp.uint64)
    x = x ^ (x >> jnp.uint64(30))
    x = x * _C1
    x = x ^ (x >> jnp.uint64(27))
    x = x * _C2
    x = x ^ (x >> jnp.uint64(31))
    return x


def _lanes_by_frexp(safe: jax.Array):
    m, e = jnp.frexp(safe)
    return (m * float(1 << 53)).astype(jnp.int64), e.astype(jnp.int64)


def _lanes_by_float32_pair(safe: jax.Array):
    """The chip's float64 IS a pair of float32 (its value their sum):
    the nearest float32 and what is left of the value are that pair,
    each bitcast at 32 bits, which the chip's compiler implements (the
    64-bit bitcast, and ``jnp.frexp``, which has one inside, it
    refuses). Equal values give equal pairs: the zero of the low word
    is made positive like the value's own."""
    hi = safe.astype(jnp.float32)
    lo = (safe - hi.astype(jnp.float64)).astype(jnp.float32)
    lo = jnp.where(lo == 0.0, jnp.float32(0.0), lo)

    def bits(x):
        return jax.lax.bitcast_convert_type(x, jnp.uint32).astype(
            jnp.int64)
    return bits(hi) * jnp.int64(1 << 32) + bits(lo), \
        jnp.zeros(safe.shape, jnp.int64)


def float_equality_lanes(d: jax.Array):
    """Exact equality-preserving decomposition of a float lane into two
    int64 lanes: (mantissa*2^53, exponent) from ``jnp.frexp``, or on a
    TPU (words of the float32 pair, 0).

    The natural encoding — bitcast f64->u64 — is NOT implemented by the
    TPU compiler's x64 rewrite, and neither is ``jnp.frexp`` on f64 (it
    bitcasts f64->s64 inside: v5e compiler, jax 0.9.0, PR 23), so the
    lanes are made per platform (``lax.platform_dependent``: the
    lowering for a TPU takes ``_lanes_by_float32_pair``, every other
    one ``_lanes_by_frexp``; the lanes are compared within one program,
    never across). Integer, date and dictionary keys never come here.
    Canonicalizes -0.0 == 0.0 and all NaNs equal (SQL distinct-from
    semantics, reference: spi/type/DoubleType.java#hash)."""
    d = jnp.asarray(d).astype(jnp.float64)
    d = jnp.where(d == 0.0, 0.0, d)
    isnan = jnp.isnan(d)
    isinf = jnp.isinf(d)
    special = isnan | isinf
    safe = jnp.where(special, 0.0, d)
    mi, ex = jax.lax.platform_dependent(
        safe, tpu=_lanes_by_float32_pair, default=_lanes_by_frexp)
    code = jnp.where(isnan, 1, jnp.where(d > 0, 2, 3))
    mi = jnp.where(special, code.astype(jnp.int64), mi)
    ex = jnp.where(special, jnp.int64(5000), ex)
    return mi, ex


def equality_lanes(data: jax.Array):
    """List of int64/uint64 lanes whose tuple-equality == SQL equality of
    the value lane. One lane for ints/bools/codes; two for floats."""
    d = jnp.asarray(data)
    if d.dtype in (jnp.float32, jnp.float64):
        mi, ex = float_equality_lanes(d)
        return [mi.astype(jnp.uint64), ex.astype(jnp.uint64)]
    if d.dtype == jnp.bool_:
        return [d.astype(jnp.uint64)]
    return [d.astype(jnp.int64).astype(jnp.uint64)]


def lane_to_u64(data: jax.Array) -> jax.Array:
    """Single uint64 lane for hashing. Exact (bijective cast) for
    ints/bools; for floats, a mix of the two equality lanes (collisions
    ~2^-64, acceptable for hashing)."""
    d = jnp.asarray(data)
    if d.dtype in (jnp.float32, jnp.float64):
        mi, ex = float_equality_lanes(d)
        return mix64(mi.astype(jnp.uint64)) + ex.astype(jnp.uint64)
    if d.dtype == jnp.bool_:
        return d.astype(jnp.uint64)
    return d.astype(jnp.int64).astype(jnp.uint64)


def hash_column(data: jax.Array, valid: Optional[jax.Array]) -> jax.Array:
    """Per-row 64-bit hash of one lane; NULL hashes to 0 (Trino convention:
    AbstractLongType.hash of null position == 0 via mayHaveNull path)."""
    h = mix64(lane_to_u64(data))
    if valid is not None:
        h = jnp.where(jnp.asarray(valid), h, jnp.uint64(0))
    return h


def fold_hashes(hashes: Sequence[jax.Array]) -> jax.Array:
    """CombineHashFunction.getHash: h = 31*h + x, vectorized — before
    the finalizer (``combine_hashes``; ops/join.py applies it itself,
    as to a single key)."""
    acc = jnp.zeros_like(hashes[0]) + _GOLDEN
    for h in hashes:
        acc = acc * jnp.uint64(31) + h
    return acc


def combine_hashes(hashes: Sequence[jax.Array]) -> jax.Array:
    """``fold_hashes`` and the finalizer: one hash of several."""
    return mix64(fold_hashes(hashes))


def hash_columns(cols) -> jax.Array:
    """Hash a list of Columns into one uint64 lane."""
    return combine_hashes([hash_column(c.data, c.valid) for c in cols])


def partition_of(h: jax.Array, num_partitions: int) -> jax.Array:
    """Map a 64-bit hash to [0, num_partitions) — the PagePartitioner hash
    bucket (reference: operator/PartitionedOutputOperator.java:308)."""
    return (h % jnp.uint64(num_partitions)).astype(jnp.int32)
