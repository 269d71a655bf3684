"""SPMD collective tests on the virtual 8-device CPU mesh.

Reference parity: the DistributedQueryRunner tier (SURVEY.md §4) — N
"workers" in one process; here N = 8 virtual XLA CPU devices and the
exchange layer is all_to_all/all_gather instead of HTTP page transfer.
"""

import collections

import numpy as np
import pytest

from trino_tpu.columnar import batch_from_pylist
from trino_tpu.ops.groupby import AggInput
from trino_tpu.parallel import (distributed_group_aggregate, get_mesh,
                                repartition_by_hash, shard_batch,
                                unshard_batch)
from trino_tpu.parallel.spmd import broadcast_sharded
from trino_tpu.types import BIGINT, DOUBLE, VARCHAR


@pytest.fixture(scope="module")
def mesh():
    m = get_mesh()
    assert m.devices.size == 8, "conftest must provide 8 virtual devices"
    return m


@pytest.fixture(scope="module")
def batch():
    rng = np.random.default_rng(42)
    n = 3000
    k = rng.integers(0, 23, n)
    v = rng.normal(size=n)
    b = batch_from_pylist(
        {"k": [int(x) for x in k], "v": [float(x) for x in v]},
        {"k": BIGINT, "v": DOUBLE})
    return b, k, v


def test_shard_roundtrip(mesh, batch):
    b, k, v = batch
    sb = shard_batch(b, mesh)
    assert sb.total_rows_host() == len(k)
    back = unshard_batch(sb)
    assert back.num_rows_host() == len(k)
    got = sorted(back.to_pylist())
    want = sorted([int(a), float(x)] for a, x in zip(k, v))
    assert [r[0] for r in got] == [r[0] for r in want]


def test_repartition_collocates_keys(mesh, batch):
    b, k, v = batch
    sb = shard_batch(b, mesh)
    rp = repartition_by_hash(sb, ["k"])
    assert rp.total_rows_host() == len(k)
    # every key must live on exactly one shard
    counts = np.asarray(rp.num_rows)
    per = rp.per_shard_cap
    kk = np.asarray(rp.columns["k"].data)
    key_shards = collections.defaultdict(set)
    for d in range(8):
        for j in range(counts[d]):
            key_shards[int(kk[d * per + j])].add(d)
    assert all(len(s) == 1 for s in key_shards.values())


def test_distributed_groupby_matches_local(mesh, batch):
    b, k, v = batch
    sb = shard_batch(b, mesh)
    out = distributed_group_aggregate(
        sb, ["k"], [AggInput("sum", "v", output="s"),
                    AggInput("count_star", None, output="c"),
                    AggInput("max", "v", output="mx")])
    res = unshard_batch(out)
    n = res.num_rows_host()
    ref_s = collections.defaultdict(float)
    ref_c = collections.Counter()
    ref_m = collections.defaultdict(lambda: -1e18)
    for a, x in zip(k, v):
        ref_s[int(a)] += x
        ref_c[int(a)] += 1
        ref_m[int(a)] = max(ref_m[int(a)], x)
    assert n == len(ref_s)
    kk = np.asarray(res.column("k").data)[:n]
    ss = np.asarray(res.column("s").data)[:n]
    cc = np.asarray(res.column("c").data)[:n]
    mm = np.asarray(res.column("mx").data)[:n]
    for a, s, c, m in zip(kk, ss, cc, mm):
        assert ref_c[int(a)] == int(c)
        assert abs(ref_s[int(a)] - s) < 1e-9
        assert abs(ref_m[int(a)] - m) < 1e-12


@pytest.mark.slow      # ~13s; sibling test_distributed_groupby_matches_local
# keeps the distributed-groupby path tier-1
def test_distributed_groupby_strings(mesh):
    vals = ["apple", "pear", "apple", "fig", "pear", "apple"] * 50
    b = batch_from_pylist({"s": vals, "x": list(range(len(vals)))},
                          {"s": VARCHAR, "x": BIGINT})
    sb = shard_batch(b, get_mesh())
    out = distributed_group_aggregate(
        sb, ["s"], [AggInput("count_star", None, output="c")])
    res = unshard_batch(out)
    got = {r[0]: r[1] for r in
           [dict(zip(res.names, row)).values() and
            [row[res.names.index("s")], row[res.names.index("c")]]
            for row in res.to_pylist()]}
    want = collections.Counter(vals)
    assert got == dict(want)


def test_broadcast(mesh, batch):
    b, k, v = batch
    sb = shard_batch(b, mesh)
    bc = broadcast_sharded(sb)
    counts = np.asarray(bc.num_rows)
    assert (counts == len(k)).all()


@pytest.mark.slow
def test_graft_entry():
    import __graft_entry__ as ge
    import jax
    fn, args = ge.entry()
    out, n = jax.jit(fn)(*args)
    assert int(n) >= 1
    ge.dryrun_multichip(8)


def test_range_repartition_distributed_sort(mesh):
    """Sampled range exchange + per-shard sort == global ORDER BY
    (exec/distributed.py _dexec_SortNode building blocks).
    Ungated in PR 13: the in-slice path rides the stage scheduler now,
    so the collective building blocks are tier-1 load-bearing."""
    from trino_tpu.ops.sort import SortKey, sort_batch
    from trino_tpu.parallel.spmd import (repartition_by_range,
                                         sample_range_splitters,
                                         shard_apply)

    rng = np.random.default_rng(7)
    n = 20000
    a = rng.integers(0, 50, n)
    d = rng.normal(size=n)
    b = batch_from_pylist(
        {"a": [int(x) for x in a], "d": [float(x) for x in d]},
        {"a": BIGINT, "d": DOUBLE})
    keys = [SortKey("a", True, None), SortKey("d", False, None)]
    want = sort_batch(b, keys).to_pylist()

    sb = shard_batch(b, mesh)
    splitters = sample_range_splitters(sb, keys)
    rp = repartition_by_range(sb, keys, splitters)
    assert rp.total_rows_host() == n
    # the splitters are operands: another sample, the same program
    from trino_tpu.obs.metrics import JIT_CACHE_LOOKUPS
    before = JIT_CACHE_LOOKUPS.value(cache="spmd", result="miss")
    repartition_by_range(sb, keys, [l[::-1] for l in splitters])
    assert JIT_CACHE_LOOKUPS.value(cache="spmd", result="miss") == before
    out = shard_apply(rp, lambda x: sort_batch(x, keys))
    got = unshard_batch(out).to_pylist()
    assert got == want


@pytest.mark.slow
def test_distributed_sort_sql_matches_local():
    """End-to-end ORDER BY through the distributed executor (large
    enough to take the range-exchange path, verified ordered)."""
    from trino_tpu.runner import LocalQueryRunner
    q = ("SELECT l_orderkey, l_linenumber, l_extendedprice FROM lineitem "
         "WHERE l_quantity < 30 ORDER BY l_extendedprice DESC, l_orderkey, "
         "l_linenumber")
    local = LocalQueryRunner().execute(q).rows
    dist = LocalQueryRunner(distributed=True, n_devices=8).execute(q).rows
    assert len(local) > 4096  # must exercise the range exchange
    assert dist == local


@pytest.mark.slow      # ~47s: 8-device windowed aggregation equality;
# window correctness stays tier-1 via test_window_frames/test_warmpath_aot
def test_distributed_window_matches_local():
    """q47-style windowed aggregation: hash repartition by partition
    keys + per-shard window == local (round-4 verdict weak #6).
    The partition keys' hash repartition is the mesh executor's sized
    all_to_all exchange (parallel/spmd.py)."""
    q = ("SELECT o_custkey, o_orderkey, "
         "rank() OVER (PARTITION BY o_custkey ORDER BY o_totalprice DESC) "
         "AS r, sum(o_totalprice) OVER (PARTITION BY o_custkey) AS s "
         "FROM orders "
         "ORDER BY o_custkey, r, o_orderkey")
    from trino_tpu.runner import LocalQueryRunner
    loc = LocalQueryRunner().execute(q).rows
    dist = LocalQueryRunner(distributed=True, n_devices=8).execute(q).rows
    # all 15000 tiny orders: above MIN_SHARD_ROWS, so this exercises
    # the real repartition + per-shard window path, not the fallback
    assert len(dist) == len(loc) > 4096
    for d, l in zip(dist, loc):
        assert d[:3] == l[:3]
        assert d[3] == pytest.approx(l[3], rel=1e-9)


@pytest.mark.parametrize("setop", [
    "INTERSECT", "INTERSECT ALL", "EXCEPT", "EXCEPT ALL"])
def test_distributed_setops_match_local(setop):
    # right side drops multiples of 5 so EXCEPT keeps a real remainder
    # (o_custkey is never divisible by 3 by spec — filtering the right
    # on %3 would make EXCEPT legitimately empty)
    q = (f"SELECT o_custkey FROM orders {setop} "
         "SELECT c_custkey FROM customer WHERE c_custkey % 5 != 0 "
         "ORDER BY 1 LIMIT 50")
    from trino_tpu.runner import LocalQueryRunner
    loc = LocalQueryRunner().execute(q).rows
    dist = LocalQueryRunner(distributed=True, n_devices=8).execute(q).rows
    assert dist == loc and len(loc) > 0


@pytest.mark.slow
def test_distributed_setop_strings_match_local():
    """Both sides are sharded scans of DIFFERENT dictionary columns
    (shipmode vs orderpriority), driving _align_setop_dicts + the
    per-shard string set-op — not the coordinator fallback."""
    q = ("SELECT l_shipmode FROM lineitem EXCEPT "
         "SELECT o_orderpriority FROM orders ORDER BY 1")
    from trino_tpu.runner import LocalQueryRunner
    loc = LocalQueryRunner().execute(q).rows
    dist = LocalQueryRunner(distributed=True, n_devices=8).execute(q).rows
    assert dist == loc and len(loc) == 7   # all 7 ship modes survive

    q2 = ("SELECT l_shipmode FROM lineitem INTERSECT "
          "SELECT l_shipmode FROM lineitem WHERE l_orderkey % 2 = 0 "
          "ORDER BY 1")
    loc2 = LocalQueryRunner().execute(q2).rows
    dist2 = LocalQueryRunner(distributed=True,
                             n_devices=8).execute(q2).rows
    assert dist2 == loc2 and len(loc2) == 7
