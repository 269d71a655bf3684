"""Hash repartition: the PartitionedOutput operator.

Reference parity: operator/output/PartitionedOutputOperator.java +
operator/PartitionFunction (hash bucket = raw xxhash of the key columns
mod partition count) and operator/InterpretedHashGenerator combining
key columns. Here the bucketing kernel is a jit-compiled jnp program
over uint64 lanes (ops/hashing.py's splitmix64 finalizer +
multiply-combine), and the row scatter into per-partition pages is a
host gather over the kernel's bucket lane — the same two-phase
"compute on device, pick rows on host" shape as ops/join.py.

Determinism contract (the whole point): the bucket of a row is a pure
function of its key VALUES — never of process-local state. Numeric
lanes cast bijectively to uint64; floats decompose through the
equality-preserving frexp lanes; DICTIONARY string columns hash the
string BYTES per dictionary entry (FNV-1a 64) and gather per-row — two
workers holding the same value under different dictionary codes must
agree on the bucket, or a distributed join silently drops matches.
NULL keys hash to 0 (Trino convention), so all-null-key rows colocate
on partition 0 and outer-join row preservation stays single-copy.

Layout contract: a stage task's spooled attempt holds EXACTLY
``nparts`` frames, frame index == partition index (page_00000.bin is
partition 0). The consumer task for partition p reads frame p of every
upstream task — content-addressed, no manifest needed.
"""

from __future__ import annotations

from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..columnar import Batch, Column
from ..obs.metrics import EXCHANGE_PARTITION_BYTES, EXCHANGE_PARTITIONS
from ..ops.hashing import lane_to_u64, mix64, partition_of

_FNV_OFFSET = 0xCBF29CE484222325
_FNV_PRIME = 0x100000001B3
_MASK64 = (1 << 64) - 1


def _fnv1a64(data: bytes) -> int:
    h = _FNV_OFFSET
    for b in data:
        h = ((h ^ b) * _FNV_PRIME) & _MASK64
    return h


def dictionary_value_hashes(dictionary) -> np.ndarray:
    """Per-entry uint64 value hash of a StringDictionary — a pure
    function of the string bytes, NOT of the (process-local) code
    assignment. Gathered per row by the entry code, this is the string
    key's partition lane."""
    out = np.empty(len(dictionary.values), dtype=np.uint64)
    for i, s in enumerate(dictionary.values):
        out[i] = _fnv1a64(str(s).encode("utf-8"))
    return out


# The jitted bucket kernels live in the program cache's "repartition"
# bucket (exec/progkey.py PROGRAMS). Key lanes are ALWAYS uint64 and
# valids always bool, so (key count, capacity, partition count) is the
# whole jit signature — the one program key in the engine that needs
# no lane-spec walk.

def bucket_program_key(nkeys: int, capacity: int, nparts: int) -> tuple:
    return ("repartition", int(nkeys), int(capacity), int(nparts))


def make_bucket_program(nkeys: int, nparts: int):
    """Per-row partition bucket from pre-extracted uint64 key lanes:
    mix64 each lane (NULL rows -> 0), multiply-combine across key
    columns (CombineHashFunction's 31*h+x), mod the partition count.
    One fused device program per (key count, shape). Module-level
    builder so exec/aot.py rebuilds the EXACT closure this cache
    holds (the "repartition" AOT kind)."""

    def fn(lanes, valids) -> jax.Array:
        hashed = [jnp.where(v, mix64(l), jnp.uint64(0))
                  for l, v in zip(lanes, valids)]
        if nkeys == 1:
            h = hashed[0]
        else:
            acc = jnp.zeros_like(hashed[0]) \
                + jnp.uint64(0x9E3779B97F4A7C15)
            for h1 in hashed:
                acc = acc * jnp.uint64(31) + h1
            h = mix64(acc)
        return partition_of(h, nparts)

    return fn


def _key_lane(col: Column) -> jax.Array:
    """uint64 partition lane of one key column, value-faithful across
    processes (see module docstring)."""
    if col.dictionary is not None:
        entry = dictionary_value_hashes(col.dictionary)
        codes = np.asarray(col.data).astype(np.int64)
        codes = np.clip(codes, 0, len(entry) - 1)
        return jnp.asarray(entry[codes])
    return lane_to_u64(jnp.asarray(col.data))


def partition_buckets(batch: Batch, keys: Sequence[str],
                      nparts: int, session=None) -> np.ndarray:
    """Bucket index in [0, nparts) for each LIVE row of ``batch``."""
    from ..exec.hotshapes import record_program
    from ..exec.progkey import PROGRAMS
    n = batch.num_rows_host()
    lanes, valids = [], []
    for k in keys:
        c = batch.column(k)
        lanes.append(_key_lane(c))
        valids.append(jnp.ones((c.capacity,), bool) if c.valid is None
                      else jnp.asarray(c.valid).astype(bool))
    cap = int(batch.capacity)
    key = bucket_program_key(len(keys), cap, nparts)
    jitted, hit = PROGRAMS.program(
        "repartition", key,
        lambda: make_bucket_program(len(keys), nparts),
        "repartition", key)
    record_program(
        "repartition", key, None, None, session,
        payload_fn=lambda: {"kind": "repartition",
                            "nkeys": len(keys), "capacity": cap,
                            "nparts": int(nparts)})
    from ..obs.trace import dispatch_span
    with dispatch_span(getattr(session, "trace", None),
                       jitted.program, hit):
        bk = jitted(tuple(lanes), tuple(valids))
    return np.asarray(bk)[:n]


def _host_col(c: Column) -> Column:
    """One device->host readback per lane, shared by every partition's
    row gather (np.asarray on an already-host array is free)."""
    data = np.asarray(c.data)
    valid = None if c.valid is None else np.asarray(c.valid)
    d2 = None if c.data2 is None else np.asarray(c.data2)
    children = None if c.children is None else tuple(
        _host_col(ch) for ch in c.children)
    return Column(c.type, data, valid, c.dictionary, d2, c.elements,
                  c.elements2, children)


def _take_rows_col(c: Column, idx: np.ndarray, n: int) -> Column:
    """Row gather of one column's live prefix. Offset lanes and the
    shared elements pools ride whole (ARRAY/MAP semantics, same as
    server/task_worker._slice_batch); ROW children are row-aligned and
    gather recursively."""
    data = np.asarray(c.data)[:n][idx]
    valid = None if c.valid is None else np.asarray(c.valid)[:n][idx]
    d2 = None if c.data2 is None else np.asarray(c.data2)[:n][idx]
    children = None
    if c.children is not None:
        children = tuple(_take_rows_col(ch, idx, n) for ch in c.children)
    return Column(c.type, data, valid, c.dictionary, d2, c.elements,
                  c.elements2, children)


def _take_rows(batch: Batch, idx: np.ndarray, n: int) -> Batch:
    return Batch({s: _take_rows_col(c, idx, n)
                  for s, c in batch.columns.items()}, len(idx))


def partition_batch(batch: Batch, keys: Sequence[str],
                    nparts: int, session=None) -> List[Batch]:
    """Split ``batch`` into exactly ``nparts`` batches by key hash.
    Partitions are complete and disjoint: every live row lands in
    exactly one output, at bucket(partition_buckets). Empty partitions
    are real (zero-row) batches so the frame layout stays dense."""
    n = batch.num_rows_host()
    if not keys:
        # keyless repartition: deterministic round-robin by row index
        # (the reference's round-robin PagePartitioner for
        # FIXED_ARBITRARY distributions)
        bk = np.arange(n, dtype=np.int64) % max(nparts, 1)
    else:
        bk = partition_buckets(batch, keys, nparts, session=session)
    host = Batch({s: _host_col(c) for s, c in batch.columns.items()},
                 n)
    return [_take_rows(host, np.flatnonzero(bk == p), n)
            for p in range(nparts)]


def partition_frames(batch: Batch, keys: Sequence[str], kind: str,
                     nparts: int, codec: Optional[int] = None,
                     session=None) -> List[bytes]:
    """Serialize a stage's output as partition frames: frame i IS
    partition i (one frame per partition — the deterministic layout the
    exchange contract requires; a consumer reads frame index
    == its own partition). kind="gather" (or nparts==1) emits the whole
    batch as the single partition; kind="replicate" does the same on
    the producing side — the REPLICATE semantics live in the consumer
    (stage/exchange.py), where EVERY task reads frame 0 instead of its
    own partition index, so the bytes are spooled once, not once per
    consumer task."""
    from ..serde import serialize_batch
    n = batch.num_rows_host()
    if kind in ("gather", "replicate") or nparts <= 1:
        host = Batch({s: _host_col(c)
                      for s, c in batch.columns.items()}, n)
        parts = [_take_rows(host, np.arange(n, dtype=np.int64), n)]
    else:
        parts = partition_batch(batch, keys, nparts, session=session)
    frames = [serialize_batch(p, codec=codec) for p in parts]
    EXCHANGE_PARTITIONS.inc(len(frames), direction="written")
    EXCHANGE_PARTITION_BYTES.inc(sum(len(f) for f in frames),
                                 direction="written")
    return frames
