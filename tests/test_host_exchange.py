"""Multi-host data plane tests: task workers + serde page exchange.

Reference parity: the DistributedQueryRunner tier with REAL process +
HTTP boundaries (SURVEY.md §4: coordinator + N TestingTrinoServer in
one JVM over ephemeral ports) — here two worker PROCESSES execute
partial fragments and the parent pulls their result pages through the
token-acknowledged exchange (TaskResource results protocol), with every
page passing through serde.py framing (LZ4 + xxh64).
"""

import multiprocessing as mp

import numpy as np
import pytest

from trino_tpu import serde
from trino_tpu.columnar import Batch, batch_from_pylist
from trino_tpu.runner import LocalQueryRunner
from trino_tpu.serde import deserialize_batch, serialize_batch
from trino_tpu.server.task_worker import (RemoteTaskClient,
                                          TaskWorkerServer, paginate,
                                          worker_main)
from trino_tpu.types import BIGINT, DOUBLE, VARCHAR


# --------------------------------------------------------------------------
# serde framing: the test that fails if serde breaks
# --------------------------------------------------------------------------

def _sample_batch():
    return batch_from_pylist(
        {"k": [1, 2, None, 4] * 64,
         "s": ["alpha", None, "beta", "gamma"] * 64,
         "v": [1.5, -2.25, 3.75, None] * 64},
        {"k": BIGINT, "s": VARCHAR, "v": DOUBLE})


@pytest.mark.parametrize("codec",
                         [serde.CODEC_STORE, serde.CODEC_LZ4])
def test_serde_roundtrip(codec):
    if codec == serde.CODEC_LZ4 and not serde.native_available():
        pytest.skip("native lz4 unavailable (g++ missing?)")
    b = _sample_batch()
    frame = serialize_batch(b, codec=codec)
    back = deserialize_batch(frame)
    assert back.to_pylist() == b.to_pylist()
    assert back.schema()["s"].name.startswith("varchar")


def test_serde_native_lz4_builds():
    # the native library is part of the data plane, not optional décor:
    # its absence must be a loud failure on a machine with a toolchain
    assert serde.native_available(), \
        "native/pageserde.cpp failed to build or load"


def test_serde_detects_corruption():
    frame = bytearray(serialize_batch(_sample_batch()))
    frame[len(frame) // 2] ^= 0x40
    with pytest.raises(Exception, match="checksum|corrupt"):
        deserialize_batch(bytes(frame))


def test_paginate_splits_and_preserves_rows():
    b = _sample_batch()
    pages = paginate(b, page_rows=100)
    assert len(pages) == 3            # 256 rows / 100
    rows = []
    for p in pages:
        rows.extend(deserialize_batch(p).to_pylist())
    assert rows == b.to_pylist()


# --------------------------------------------------------------------------
# in-process worker server (protocol mechanics)
# --------------------------------------------------------------------------

def test_task_worker_protocol():
    srv = TaskWorkerServer().start()
    try:
        c = RemoteTaskClient(srv.base_uri)
        c.submit("t1", "SELECT n_regionkey, count(*) AS c "
                       "FROM tpch.tiny.nation GROUP BY n_regionkey")
        pages = c.pages("t1")
        rows = sorted(r for p in pages for r in p.to_pylist())
        assert rows == [[r, 5] for r in range(5)]
        # pulls are idempotent per token (ack/retry semantics)
        again = c.pages("t1")
        assert sorted(r for p in again for r in p.to_pylist()) == rows
        c.abort("t1")
    finally:
        srv.stop()


def test_task_worker_error_propagates():
    srv = TaskWorkerServer().start()
    try:
        c = RemoteTaskClient(srv.base_uri)
        c.submit("bad", "SELECT nosuch FROM tpch.tiny.nation")
        with pytest.raises(Exception, match="500|cannot be resolved"):
            c.pages("bad")
    finally:
        srv.stop()


# --------------------------------------------------------------------------
# two worker PROCESSES: the real DCN leg
# --------------------------------------------------------------------------

def test_two_process_partial_final_aggregation():
    """Partial aggregation on two worker processes, page exchange over
    HTTP through serde, final aggregation in the parent — the
    PushPartialAggregationThroughExchange shape across a genuine
    process boundary."""
    ctx = mp.get_context("spawn")
    workers = []
    try:
        from trino_tpu.server.task_worker import spawn_worker_env
        with spawn_worker_env():
            # spawn children inherit JAX_PLATFORMS=cpu: a child must
            # not reach for a chip its parent holds
            for _ in range(2):
                parent, child = ctx.Pipe()
                p = ctx.Process(target=worker_main,
                                args=(child, "cpu"), daemon=True)
                p.start()
                if not parent.poll(120):
                    raise RuntimeError("worker child did not start")
                port = parent.recv()
                workers.append((p, f"http://127.0.0.1:{port}"))

        partial_sql = ("SELECT o_orderpriority AS pri, "
                       "count(*) AS c, sum(o_totalprice) AS s "
                       "FROM tpch.tiny.orders WHERE o_orderkey % 2 = {k} "
                       "GROUP BY o_orderpriority")
        batches = []
        for k, (_, uri) in enumerate(workers):
            c = RemoteTaskClient(uri)
            c.submit(f"part{k}", partial_sql.format(k=k))
        for k, (_, uri) in enumerate(workers):
            c = RemoteTaskClient(uri)
            batches.extend(c.pages(f"part{k}"))

        # final combine in the parent engine
        from trino_tpu.exec.executor import device_concat
        from trino_tpu.ops.groupby import AggInput, group_aggregate
        merged = device_concat(batches)
        fin = group_aggregate(
            merged, ["pri"],
            [AggInput("sum", "c", output="c"),
             AggInput("sum", "s", output="s")])
        n = fin.num_rows_host()
        got = sorted(fin.to_pylist()[:n])

        direct = LocalQueryRunner().execute(
            "SELECT o_orderpriority, count(*), sum(o_totalprice) "
            "FROM tpch.tiny.orders GROUP BY o_orderpriority "
            "ORDER BY 1").rows
        assert [[g[0], g[1]] for g in got] == \
            [[d[0], d[1]] for d in direct]
        for g, d in zip(got, direct):
            assert g[2] == pytest.approx(d[2], rel=1e-9)
    finally:
        for p, _ in workers:
            p.terminate()
