"""Beyond-HBM morsel streaming (exec/streamjoin.py): chunked ==
unchunked bit-exactness across chunk sizes, auto-engagement instead of
the memory error, the one-compiled-program-per-stream contract,
streamed-peak memory governance, hot-shape/AOT pre-warm of chunk
kernels, and the distributed rollup."""

import pytest

from trino_tpu.config import capacity_for
from trino_tpu.obs.metrics import (STREAM_CHUNKS, STREAM_H2D_BYTES,
                                   STREAM_OVERLAPPED)
from trino_tpu.runner import LocalQueryRunner
from trino_tpu.session import Session


def _chunk_total() -> float:
    return sum(v for _, v in STREAM_CHUNKS.samples())


def _runner(schema="tiny", **props):
    s = Session(catalog="tpch", schema=schema)
    for k, v in props.items():
        s.set(k, v)
    return LocalQueryRunner(session=s)


@pytest.fixture(scope="module")
def mem_tables():
    """Small memory-catalog tables with NULL join keys and a decimal
    column — tiny enough that chunk size 1 stays fast."""
    r = LocalQueryRunner(session=Session(catalog="tpch",
                                         schema="tiny"))
    r.execute("CREATE TABLE memory.default.sprobe "
              "(k BIGINT, v BIGINT, d DECIMAL(12,2))")
    rows = ",".join(
        f"({'NULL' if i % 5 == 0 else i % 37},{i},"
        f"CAST({i}.{i % 100:02d} AS DECIMAL(12,2)))"
        for i in range(200))
    r.execute(f"INSERT INTO memory.default.sprobe VALUES {rows}")
    r.execute("CREATE TABLE memory.default.sbuild (bk BIGINT, w BIGINT)")
    rows = ",".join(f"({'NULL' if i % 7 == 0 else i},{i * 10})"
                    for i in range(40))
    r.execute(f"INSERT INTO memory.default.sbuild VALUES {rows}")
    return r


# the property suite: joins (incl. NULL keys + outer), a decimal
# aggregation, and an order-sensitive query over a filter chain
_PROPERTY_QUERIES = (
    "SELECT count(*), sum(v), sum(w) FROM memory.default.sprobe "
    "JOIN memory.default.sbuild ON k = bk",
    "SELECT count(*), sum(v), sum(w) FROM memory.default.sprobe "
    "LEFT JOIN memory.default.sbuild ON k = bk",
    "SELECT sum(d), avg(d), count(k), min(v), max(v) "
    "FROM memory.default.sprobe",
    "SELECT k, v, d FROM memory.default.sprobe WHERE v > 20 "
    "ORDER BY v DESC LIMIT 25",
    "SELECT k, sum(d), count(*) FROM memory.default.sprobe "
    "GROUP BY k ORDER BY k",
    # residual (non-equi conjunct) join through the streamed path
    "SELECT count(*), sum(w) FROM memory.default.sprobe "
    "JOIN memory.default.sbuild ON k = bk WHERE v > w / 10",
    # a few unique keys spread wider than the exact directory holds:
    # the bitmap directory on both paths
    "SELECT count(*), sum(v) FROM memory.default.sprobe "
    "JOIN (VALUES (0), (5), (36), (10000), (-20000)) t(x) ON k = x",
)


@pytest.mark.parametrize("chunk_rows", [1, 7, 64, 100000])
def test_chunked_equals_unchunked(mem_tables, chunk_rows):
    """Bit-exactness across chunk sizes 1 / prime / pow2 / >nrows:
    forcing every streamable operator to chunk must not change a
    single row — NULL join keys, outer repair, decimal (Int128-exact)
    aggregates, and ORDER BY-sensitive output included."""
    base = [mem_tables.execute(q).rows for q in _PROPERTY_QUERIES]
    s = Session(catalog="tpch", schema="tiny")
    s.set("stream_chunk_rows", chunk_rows)
    r = LocalQueryRunner(session=s, catalogs=mem_tables.catalogs)
    c0 = _chunk_total()
    for q, b in zip(_PROPERTY_QUERIES, base):
        assert r.execute(q).rows == b, f"chunk={chunk_rows}: {q}"
    assert _chunk_total() > c0          # the forced path really ran


def test_over_budget_join_streams_instead_of_raising(monkeypatch):
    """The synthetic over-budget join: a budget below the probe
    scan's materialization estimate used to fail with the memory
    error; now the probe streams and the query completes. The
    monkeypatched control proves the SAME budget still raises when
    streaming is disabled — engagement is what saves it."""
    from trino_tpu.exec.executor import QueryError
    sql = ("SELECT count(*), sum(l_quantity) FROM lineitem "
           "JOIN orders ON l_orderkey = o_orderkey")
    expected = _runner().execute(sql).rows

    # lineitem probe estimate ~960KB (60k rows x 2 lanes); orders
    # build state ~400KB -> budget 600KB engages streaming
    budget = 600_000
    c0 = _chunk_total()
    r = _runner(query_max_memory_per_node=budget)
    assert r.execute(sql).rows == expected
    assert _chunk_total() > c0

    import trino_tpu.exec.streamjoin as sj
    monkeypatch.setattr(sj, "maybe_stream_join",
                        lambda ex, node: (None, None))
    monkeypatch.setattr(sj, "maybe_stream_chain",
                        lambda ex, node: None)
    with pytest.raises(QueryError, match="memory limit"):
        _runner(query_max_memory_per_node=budget).execute(sql)


def test_one_compiled_program_per_streamed_join(mem_tables):
    """Acceptance: every chunk of a streamed operator shares ONE
    compiled program — one jit_trace span total inside the stream
    (the first chunk), dispatch for all the rest."""
    sql = ("SELECT count(*), sum(v), sum(w) "
           "FROM memory.default.sprobe "
           "JOIN memory.default.sbuild ON k = bk")
    s = Session(catalog="tpch", schema="tiny")
    s.set("stream_chunk_rows", 16)
    r = LocalQueryRunner(session=s, catalogs=mem_tables.catalogs,
                         collect_node_stats=True)
    res = r.execute(sql)
    assert res.rows == mem_tables.execute(sql).rows

    def stream_kids(span, inside, out):
        inside = inside or span.name == "stream_chunk"
        if inside and span.name in ("jit_trace", "dispatch"):
            out.append(span.name)
        for c in span.children:
            stream_kids(c, inside, out)

    kinds = []
    for root in res.trace.roots:
        stream_kids(root, False, kinds)
    chunks = [sp for sp in _walk(res.trace) if sp.name == "stream_chunk"]
    assert len(chunks) >= 2             # 200 rows / 16 -> 13 chunks
    traces = [k for k in kinds if k == "jit_trace"]
    # warm-up = the first chunk; every later chunk rides the program.
    # A fully pre-warmed process (cache already holds the program from
    # an earlier test) may even trace zero times.
    assert len(traces) <= 1
    assert kinds.count("dispatch") >= len(chunks) - 1


def _walk(trace):
    out = []

    def rec(sp):
        out.append(sp)
        for c in sp.children:
            rec(c)
    for rootsp in trace.roots:
        rec(rootsp)
    return out


def test_streamed_explain_and_metrics(mem_tables):
    """EXPLAIN ANALYZE shows the chunk count + h2d volume per
    operator and the stream_chunk spans; the Prometheus families
    move."""
    c0, b0, o0 = (_chunk_total(), STREAM_H2D_BYTES.value(),
                  STREAM_OVERLAPPED.value())
    s = Session(catalog="tpch", schema="tiny")
    s.set("stream_chunk_rows", 16)
    r = LocalQueryRunner(session=s, catalogs=mem_tables.catalogs)
    res = r.execute(
        "EXPLAIN ANALYZE SELECT count(*), sum(w) "
        "FROM memory.default.sprobe "
        "JOIN memory.default.sbuild ON k = bk")
    text = "\n".join(row[0] for row in res.rows)
    assert "streamed" in text and "chunks" in text
    assert "stream_chunk" in text
    assert _chunk_total() > c0
    assert STREAM_H2D_BYTES.value() > b0
    # double-buffering: all but the first transfer overlap compute
    assert STREAM_OVERLAPPED.value() > o0


def test_streamed_peak_reported_to_cluster_pool(monkeypatch):
    """Memory-governance fix: a query whose materialized join breaches
    the cluster pool (killed with CLUSTER_OUT_OF_MEMORY) completes
    when streaming engages, because the ledger now carries the
    streamed peak (build + chunk buffers), not the full estimate."""
    from trino_tpu.exec.executor import QueryError
    from trino_tpu.server.memory import (ClusterMemoryManager,
                                         ClusterMemoryPool)
    # the plan above the join reads its keys too: the join puts out all
    # four lanes, and its output estimate counts them
    sql = ("SELECT count(*), sum(l_quantity), sum(o_totalprice), "
           "sum(l_orderkey - o_orderkey) "
           "FROM lineitem JOIN orders ON l_orderkey = o_orderkey")
    expected = _runner().execute(sql).rows
    pool_bytes = 1_200_000      # < the ~3.4MB join-output estimate

    def run_under_pool(disable_streaming: bool):
        mgr = ClusterMemoryManager(ClusterMemoryPool(pool_bytes))
        s = Session(catalog="tpch", schema="tiny")
        s.memory = mgr.register("q-stream")
        r = LocalQueryRunner(session=s)
        if disable_streaming:
            import trino_tpu.exec.streamjoin as sj
            monkeypatch.setattr(sj, "maybe_stream_join",
                                lambda ex, node: (None, None))
            monkeypatch.setattr(sj, "maybe_stream_chain",
                                lambda ex, node: None)
            monkeypatch.setattr(sj, "agg_chunk_capacity",
                                lambda ex, scan: None)
        try:
            return r.execute(sql).rows, mgr
        finally:
            if disable_streaming:
                monkeypatch.undo()

    with pytest.raises(QueryError, match="out of memory"):
        run_under_pool(True)

    rows, mgr = run_under_pool(False)
    assert rows == expected
    assert mgr.kills == 0


def test_streamjoin_hot_shape_recorded_and_aot_compiles(mem_tables):
    """Satellite: streamed chunk shapes land in the hot-shape registry
    under their canonical chunk capacity, and the AOT path rebuilds +
    compiles the probe program into the exact cache slot — a
    pre-warmed worker's first streamed chunk is a cache hit."""
    from trino_tpu.exec.aot import compile_entries
    from trino_tpu.exec.hotshapes import HOT_SHAPES
    from trino_tpu.exec.progkey import PROGRAMS
    HOT_SHAPES.clear()
    sql = ("SELECT count(*), sum(w) FROM memory.default.sprobe "
           "JOIN memory.default.sbuild ON k = bk")
    s = Session(catalog="tpch", schema="tiny")
    s.set("stream_chunk_rows", 16)
    LocalQueryRunner(session=s,
                     catalogs=mem_tables.catalogs).execute(sql)
    entries = [e for e in HOT_SHAPES.top(32)
               if e["kind"] == "streamjoin"]
    assert entries, "streamed join shape was not recorded"
    payload = entries[0]["payload"]
    assert payload["chunk_capacity"] == capacity_for(16, minimum=8)

    # wipe the in-process program cache, AOT-compile from the payload,
    # then prove the live query path lands on the pre-warmed program:
    # zero jit_trace spans inside the stream
    PROGRAMS.clear("streamjoin")
    out = compile_entries(entries)
    assert out["compiled"] == 1 and out["errors"] == 0
    r = LocalQueryRunner(session=s, catalogs=mem_tables.catalogs,
                         collect_node_stats=True)
    res = r.execute(sql)
    names = [sp.name for sp in _walk(res.trace)]
    assert "stream_chunk" in names
    kinds = []
    for root in res.trace.roots:
        _collect_stream_kinds(root, False, kinds)
    assert "jit_trace" not in kinds, \
        "pre-warmed streamed join still traced"


def _collect_stream_kinds(span, inside, out):
    inside = inside or span.name == "stream_chunk"
    if inside and span.name in ("jit_trace", "dispatch"):
        out.append(span.name)
    for c in span.children:
        _collect_stream_kinds(c, inside, out)


def test_chunked_agg_shape_recorded_at_chunk_capacity(monkeypatch,
                                                      mem_tables):
    """The chunked streaming aggregation records its (canonical)
    chunk-capacity program shape so workers pre-warm the chunk kernel
    (ROADMAP item 1's lazily-compiled gap, streamed flavor)."""
    monkeypatch.setenv("TRINO_TPU_FRAGMENT_JIT", "1")
    from trino_tpu.exec.hotshapes import HOT_SHAPES
    HOT_SHAPES.clear()
    s = Session(catalog="tpch", schema="tiny")
    s.set("stream_chunk_rows", 32)
    LocalQueryRunner(session=s, catalogs=mem_tables.catalogs).execute(
        "SELECT k, sum(v), count(*) FROM memory.default.sprobe "
        "GROUP BY k")
    entries = [e for e in HOT_SHAPES.top(32) if e["kind"] == "stream"]
    assert entries, "chunked agg shape was not recorded"
    assert any(e["payload"]["capacity"] == capacity_for(32, minimum=8)
               for e in entries)


def test_distributed_stream_rollup():
    """Worker-side streaming: a stage-task/leaf-fragment executor
    streams its split share, the task status ships
    streamChunks/streamH2dBytes, and the scheduler rolls them up."""
    from trino_tpu.exec.remote import DistributedHostQueryRunner
    from trino_tpu.server.task_worker import TaskWorkerServer
    workers = [TaskWorkerServer().start() for _ in range(2)]
    try:
        s = Session(catalog="tpch", schema="tiny")
        s.set("stream_chunk_rows", 4096)
        r = DistributedHostQueryRunner(
            [w.base_uri for w in workers], session=s,
            collect_node_stats=True)
        base = LocalQueryRunner(
            session=Session(catalog="tpch", schema="tiny")).execute(
            "SELECT l_returnflag, sum(l_quantity) FROM lineitem "
            "GROUP BY l_returnflag ORDER BY l_returnflag").rows
        res = r.execute(
            "SELECT l_returnflag, sum(l_quantity) FROM lineitem "
            "GROUP BY l_returnflag ORDER BY l_returnflag")
        assert res.rows == base
        assert res.stream_chunks > 0
        assert res.stream_h2d_bytes > 0
    finally:
        for w in workers:
            w.stop()


@pytest.mark.slow      # ~102s: the single heaviest tier-1 test; the
# chunked==unchunked matrix + streamed-peak governance tests keep the
# fast lane covered
def test_q18_sf1_streams_under_small_budget_matches_oracle():
    """Acceptance: the full q18 pipeline at sf1 completes under a
    memory budget smaller than its probe working set (the lineitem
    probe estimate is ~96MB; the budget leaves only chunk room after
    the orders build state), streaming the probe join and the
    IN-subquery aggregation — row-for-row against the independent
    numpy oracle."""
    import datetime

    from trino_tpu.benchmarks.q18_oracle import q18_oracle
    from trino_tpu.benchmarks.tpch_queries import TPCH_QUERIES
    from trino_tpu.connectors.tpch import table_rows

    build_state = capacity_for(table_rows("orders", 1.0)) * 48
    budget = build_state + (64 << 20)
    probe_est = table_rows("orders", 1.0) * 4 * 2 * 8   # ~96MB
    # working set = probe materialization + the capacity-rounded
    # build state the join holds concurrently (~196MB at sf1)
    assert budget < probe_est + build_state, \
        "budget must sit below the q18 join working set"
    s = Session(catalog="tpch", schema="sf1")
    s.set("query_max_memory_per_node", int(budget))
    r = LocalQueryRunner(session=s)
    c0 = _chunk_total()
    res = r.execute(TPCH_QUERIES[18]).rows
    assert _chunk_total() > c0, "q18 did not stream"
    exp = q18_oracle(1.0)
    assert len(res) == len(exp) > 0
    epoch = datetime.date(1970, 1, 1)
    for g, e in zip(res, exp):
        assert [g[0], g[1], g[2], (g[3] - epoch).days, g[4], g[5]] == e
