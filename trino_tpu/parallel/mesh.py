"""Device mesh management + batch sharding.

Reference parity: the scheduler's node topology — NodeScheduler /
InternalNodeManager (execution/scheduler/NodeScheduler.java) mapped onto
the TPU model: workers == mesh devices along one "workers" axis; a
Trino *task* on node i == the shard-i slice of an SPMD program
(SURVEY.md §2.7 inter-node data parallelism row).

A distributed Batch keeps its columns as global jax.Arrays sharded on the
row axis with NamedSharding(P("workers")); each device owns a
``per_shard_cap`` slice. Row liveness is per shard: shard d's live rows
are the first ``num_rows[d]`` of its slice (num_rows is a replicated
[n_dev] vector — the analog of per-task row counts in TaskStatus).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..columnar import Batch, Column
from ..config import capacity_for

AXIS = "workers"


def get_mesh(n_devices: Optional[int] = None) -> Mesh:
    devs = jax.devices()
    n = n_devices or len(devs)
    return Mesh(np.asarray(devs[:n]), (AXIS,))


def row_spec(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P(AXIS))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


@dataclass(frozen=True)
class ShardedBatch:
    """Row-sharded Batch: every column lane has global shape
    [n_dev * per_shard_cap] with shard d owning
    [d*per_shard_cap, (d+1)*per_shard_cap); ``num_rows`` is an [n_dev]
    replicated vector of per-shard live counts."""
    columns: Dict[str, Column]
    num_rows: jax.Array          # [n_dev] int64, replicated
    mesh: Mesh
    per_shard_cap: int

    @property
    def n_shards(self) -> int:
        return self.mesh.devices.size

    def total_rows_host(self) -> int:
        return int(jnp.sum(self.num_rows))

    def schema(self):
        return {k: c.type for k, c in self.columns.items()}


def row_bytes(cols: Dict[str, Column]) -> int:
    """Bytes one row of these columns occupies in the lanes an exchange
    moves (data, validity, second lane)."""
    return sum(int(np.dtype(lane.dtype).itemsize) for c in cols.values()
               for lane in (c.data, c.valid, c.data2) if lane is not None)


def shard_batch(batch: Batch, mesh: Mesh,
                per_shard_cap: Optional[int] = None) -> ShardedBatch:
    """Round-robin-by-range scatter of a host Batch across the mesh
    (the analog of assigning splits to worker tasks)."""
    n = mesh.devices.size
    total = batch.num_rows_host()
    per = per_shard_cap or capacity_for(
        max((total + n - 1) // n, 1), minimum=8)
    counts = np.zeros(n, dtype=np.int64)
    base = total // n
    rem = total % n
    counts[:] = base
    counts[:rem] += 1
    assert counts.max() <= per
    spec = row_spec(mesh)
    cols = {}
    offs = np.concatenate([[0], np.cumsum(counts)])[:-1]
    gather_idx = np.zeros(n * per, dtype=np.int64)
    for d in range(n):
        gather_idx[d * per: d * per + counts[d]] = np.arange(
            offs[d], offs[d] + counts[d])
    gidx = jnp.asarray(gather_idx)
    for name, c in batch.columns.items():
        data = jax.device_put(jnp.take(jnp.asarray(c.data), gidx,
                                       mode="clip"), spec)
        valid = (None if c.valid is None else jax.device_put(
            jnp.take(jnp.asarray(c.valid), gidx, mode="clip"), spec))
        d2 = (None if c.data2 is None else jax.device_put(
            jnp.take(jnp.asarray(c.data2), gidx, mode="clip"), spec))
        cols[name] = Column(c.type, data, valid, c.dictionary, d2)
    return ShardedBatch(cols, jnp.asarray(counts), mesh, per)


def mesh_by_default() -> bool:
    """Whether a server with no instruction runs queries over the mesh:
    only where the platform is a TPU AND this process sees more than
    one chip. A one-chip host, and the CPU (where a test suite may force
    any number of virtual devices), keep the one-device path."""
    return jax.default_backend() == "tpu" and jax.local_device_count() > 1


def shard_parts(parts: Sequence[Batch], mesh: Mesh) -> ShardedBatch:
    """Place per-worker Batches directly: part i -> device i (splits
    already assigned per node, the SourcePartitionedScheduler path).
    Each part's lanes go to (or already live on) their own device and
    the global lane is assembled from them in place: nothing passes
    through one device, so a part may be as large as a chip holds."""
    n = mesh.devices.size
    assert len(parts) == n
    per = max(capacity_for(max(p.num_rows_host() for p in parts),
                           minimum=8), 8)
    from ..types import is_string
    devices = list(mesh.devices.flat)
    spec = row_spec(mesh)

    def fit(lane, d):
        """The lane at ``per`` rows, committed to device ``d``; a
        device lane is padded where it lives, never through the host."""
        k = lane.shape[0]
        if k != per:
            if isinstance(lane, np.ndarray):
                lane = np.concatenate(
                    [lane[:per], np.zeros(max(per - k, 0), lane.dtype)])
            else:
                with jax.default_device(d):
                    lane = jnp.pad(lane[:per], (0, max(per - k, 0)))
        return jax.device_put(lane, d)

    def glue(lanes):
        return jax.make_array_from_single_device_arrays(
            (n * per,), spec, [fit(l, d) for l, d in zip(lanes, devices)])

    cols = {}
    counts = jax.device_put(
        np.asarray([p.num_rows_host() for p in parts], dtype=np.int64),
        replicated(mesh))
    for name in parts[0].names:
        pcols = [p.column(name) for p in parts]
        typ = pcols[0].type
        dic = pcols[0].dictionary
        lanes = [c.data for c in pcols]
        if is_string(typ) and any(c.dictionary is not dic
                                  for c in pcols[1:]):
            # merge the parts' dictionaries (host tables, tiny) and
            # remap each part's codes
            remaps = [np.arange(len(dic), dtype=np.int32)]
            for c in pcols[1:]:
                dic, _, ro = dic.merge(c.dictionary)
                remaps.append(ro)
            lanes = [np.asarray(rm)[np.asarray(c.data)].astype(np.int32)
                     for c, rm in zip(pcols, remaps)]
        valid = None
        if any(c.valid is not None for c in pcols):
            valid = glue([np.ones(per, bool) if c.valid is None
                          else c.valid for c in pcols])
        cols[name] = Column(typ, glue(lanes), valid,
                            dic if is_string(typ) else None)
    return ShardedBatch(cols, counts, mesh, per)


def unshard_batch(sb: ShardedBatch) -> Batch:
    """GATHER: collect live prefixes of every shard into one host Batch
    (the final exchange to the coordinator)."""
    from ..obs.trace import active_span
    n, per = sb.n_shards, sb.per_shard_cap
    with active_span("host_read", site="gather_rows"):
        counts = np.asarray(sb.num_rows)
    total = int(counts.sum())
    with active_span("exchange", kind="gather", rows=total,
                     bytes=total * row_bytes(sb.columns)):
        return _gather_live(sb, counts, total)


def _gather_live(sb: ShardedBatch, counts, total: int) -> Batch:
    n, per = sb.n_shards, sb.per_shard_cap
    cap = capacity_for(max(total, 1), minimum=8)
    idx_parts = [np.arange(counts[d], dtype=np.int64) + d * per
                 for d in range(n)]
    idx = np.concatenate(idx_parts) if idx_parts else np.zeros(0, np.int64)
    idx = np.pad(idx, (0, cap - len(idx)))
    gidx = jnp.asarray(idx)
    cols = {}
    for name, c in sb.columns.items():
        data = jnp.take(jnp.asarray(c.data), gidx, mode="clip")
        valid = (None if c.valid is None
                 else jnp.take(jnp.asarray(c.valid), gidx, mode="clip"))
        d2 = (None if c.data2 is None
              else jnp.take(jnp.asarray(c.data2), gidx, mode="clip"))
        elements = c.elements
        if elements is not None:
            # array offsets are shard-local; after the gather the flat
            # elements lanes of all shards are stacked, so each row's
            # start shifts by its shard's slice of the elements array
            ecap = int(jnp.asarray(elements.data).shape[0]) // max(n, 1)
            shard_of_row = gidx // per
            data = data + shard_of_row * ecap
        cols[name] = Column(c.type, jax.device_put(data),
                            None if valid is None else jax.device_put(
                                valid), c.dictionary,
                            None if d2 is None else jax.device_put(d2),
                            elements)
    return Batch(cols, total)
