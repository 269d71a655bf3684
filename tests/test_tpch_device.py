"""Device-side TPC-H generation must be bit-identical to the host leg.

Reference parity: plugin/trino-tpch/.../TpchRecordSet.java:43-51 (the
split-addressable generator contract: any split, any scale, same rows).
"""

import numpy as np
import pytest

from trino_tpu.catalog import Split, TableHandle
from trino_tpu.connectors.tpch import TpchConnector
from trino_tpu.runner import LocalQueryRunner
from trino_tpu.session import Session


def _rows(batch, cols):
    n = batch.num_rows_host()
    out = []
    for c in cols:
        col = batch.column(c)
        data = np.asarray(col.data)[:n]
        if col.dictionary is not None:
            data = col.dictionary.values[
                np.clip(data.astype(np.int64), 0,
                        len(col.dictionary.values) - 1)]
        out.append(data)
    return out


@pytest.mark.parametrize("table,cols", [
    ("lineitem", ["l_orderkey", "l_partkey", "l_suppkey",
                  "l_linenumber", "l_quantity", "l_extendedprice",
                  "l_discount", "l_tax", "l_shipdate", "l_commitdate",
                  "l_receiptdate", "l_returnflag", "l_linestatus",
                  "l_shipinstruct", "l_shipmode"]),
    ("orders", ["o_orderkey", "o_custkey", "o_orderstatus",
                "o_totalprice", "o_orderdate", "o_orderpriority",
                "o_shippriority"]),
])
@pytest.mark.parametrize("part", [0, 1])
def test_device_generation_matches_host(monkeypatch, table, cols, part):
    conn = TpchConnector(rows_per_split=1 << 14)
    h = TableHandle("tpch", "tiny", table)
    split = Split(h, part, 2)
    monkeypatch.setenv("TRINO_TPU_DEVICE_GEN", "0")
    host = conn.read_split(split, cols)
    monkeypatch.setenv("TRINO_TPU_DEVICE_GEN", "1")
    dev = conn.read_split(split, cols)
    assert dev.num_rows_host() == host.num_rows_host()
    for name, hv, dv in zip(cols, _rows(host, cols), _rows(dev, cols)):
        assert np.array_equal(hv, dv), name


def _run(sql, devgen, monkeypatch):
    monkeypatch.setenv("TRINO_TPU_DEVICE_GEN", devgen)
    r = LocalQueryRunner(session=Session(catalog="tpch", schema="tiny"))
    return r.execute(sql).rows


@pytest.mark.parametrize("sql", [
    # q6 shape: date + numeric range pushdown into the device filter
    "SELECT sum(l_extendedprice * l_discount) FROM lineitem "
    "WHERE l_shipdate >= DATE '1994-01-01' "
    "AND l_shipdate < DATE '1995-01-01' "
    "AND l_discount BETWEEN 0.05 AND 0.07 AND l_quantity < 24",
    # dictionary-coded pushdown
    "SELECT count(*) FROM lineitem WHERE l_shipmode IN ('MAIL', 'SHIP')",
    # q18 core: correlated-IN via HAVING over the whole table
    "SELECT o_orderkey, o_totalprice FROM orders WHERE o_orderkey IN "
    "(SELECT l_orderkey FROM lineitem GROUP BY l_orderkey "
    " HAVING sum(l_quantity) > 200) ORDER BY o_totalprice DESC LIMIT 5",
])
def test_engine_results_identical_with_device_generation(monkeypatch,
                                                         sql):
    assert _run(sql, "1", monkeypatch) == _run(sql, "0", monkeypatch)


@pytest.mark.parametrize("table,cols,where", [
    ("lineitem", ["l_orderkey", "l_quantity", "l_extendedprice",
                  "l_discount", "l_tax", "l_shipdate", "l_returnflag",
                  "l_linestatus"], ""),
    ("lineitem", ["l_orderkey", "l_extendedprice", "l_discount"],
     " where l_shipdate > date '1995-03-15'"),
    ("lineitem", ["l_extendedprice", "l_discount", "l_shipdate"],
     " where l_quantity < 24 and l_shipdate >= date '1994-01-01'"),
    ("orders", ["o_orderkey", "o_custkey", "o_orderdate",
                "o_shippriority", "o_totalprice", "o_orderstatus"],
     " where o_orderdate < date '1995-03-15'"),
    ("orders", ["o_custkey"], ""),
])
def test_whole_shards_from_one_program_match_the_host_rows(
        monkeypatch, table, cols, where):
    """The mesh executor's scan fill (exec/executor.py
    ``_generate_sharded`` over tpch_device.ShardGenerator): every shard
    generated on its own device by two programs, split i on shard
    i mod n — the same rows, bit for bit and in the same order, as the
    HOST generator gives split by split with the constraint applied."""
    import jax
    from trino_tpu.exec.executor import _fill_sharded
    from trino_tpu.parallel import get_mesh
    monkeypatch.setenv("TRINO_TPU_DEVICE_GEN", "0")
    r = LocalQueryRunner(session=Session(catalog="tpch", schema="tiny"))
    plan = r.plan_sql(f"select {', '.join(cols)} from {table}{where}")
    scan = plan
    while scan.sources:
        scan = scan.sources[0]
    # small splits: 15 of lineitem, 4 of orders, so every shard has some
    conn, h, n = TpchConnector(rows_per_split=1 << 12), scan.handle, 4
    assert (h.constraint is not None) == bool(where)
    splits = conn.get_splits(h, n)
    assert len(splits) >= n
    want = [[np.concatenate(lanes) for lanes in zip(*[
        _rows(conn.read_split(sp, cols), cols) for sp in splits[d::n]])]
        for d in range(n)]
    monkeypatch.setenv("TRINO_TPU_DEVICE_GEN", "1")
    sb = _fill_sharded(conn, h, cols, get_mesh(n))
    counts = np.asarray(sb.num_rows)
    assert counts.tolist() == [len(w[0]) for w in want]
    assert sb.per_shard_cap < 2 * max(counts.max(), 8)
    for i, name in enumerate(cols):
        col = sb.columns[name]
        lane = np.asarray(col.data)
        for d in range(n):
            got = lane[d * sb.per_shard_cap:][:counts[d]]
            if col.dictionary is not None:
                got = col.dictionary.values[got.astype(np.int64)]
            assert np.array_equal(got, want[d][i]), (name, d)
        shards = {s.device.id: s.data.shape for s in
                  col.data.addressable_shards}
        assert len(shards) == n
