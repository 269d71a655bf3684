"""Full-plan AOT coverage (exec/aot.py): the materialized hash join's
count+expand pair, window programs, and the repartition bucketing
kernel each record a hot shape, AOT-compile from the JSON payload
alone, and land in the SAME cache slot the executor hits — a fresh
executor's first run shows ZERO jit_trace spans.

Also the enabler: StringDictionary equality is CONTENT-based
(columnar.py), so an AOT-fabricated dictionary matches the live one in
jax's treedef comparison instead of forcing an identity-mismatch
retrace.

NOTE on the file name: these tests call jax.clear_caches(), which
wipes the process-wide trace caches every OTHER suite module keeps
warm — "warmpath" sorts near the end of tests/ on purpose so the
recompile tax lands after the heavy corpus modules, not under them."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from trino_tpu.exec import aot
from trino_tpu.exec.executor import Executor
from trino_tpu.exec.hotshapes import HOT_SHAPES
from trino_tpu.obs.metrics import METRICS
from trino_tpu.obs.trace import QueryTrace
from trino_tpu.planner import LogicalPlanner
from trino_tpu.planner.optimizer import optimize
from trino_tpu.runner import LocalQueryRunner
from trino_tpu.session import Session
from trino_tpu.sql.parser import parse_statement

_JIT_LOOKUPS = METRICS.counter("trino_tpu_jit_cache_total")


@pytest.fixture(autouse=True)
def _fresh_registry():
    """These tests assert on HOT_SHAPES.top(...) contents; hundreds of
    earlier suite tests leave higher-hit entries that would crowd a
    fresh 1-hit recording out of the top-K. Run against an empty
    registry, restore the prior entries afterwards."""
    saved = HOT_SHAPES.top(10 ** 6)
    HOT_SHAPES.clear()
    yield
    HOT_SHAPES.clear()
    HOT_SHAPES.merge(saved)


def _plan(runner, sql):
    stmt = parse_statement(sql)
    return optimize(
        LogicalPlanner(runner.catalogs, runner.session).plan(stmt))


def _span_names(trace):
    names = []

    def walk(sp):
        names.append(sp.name)
        for c in sp.children:
            walk(c)

    for root in trace.roots:
        walk(root)
    return names


def _wipe_program_caches():
    """A fresh worker process: every in-process structural cache AND
    jax's per-callable trace caches are gone — only the AOT path can
    repopulate them."""
    import jax
    from trino_tpu.exec.progkey import PROGRAMS
    PROGRAMS.clear()
    jax.clear_caches()


def _record_wipe_compile_rerun(monkeypatch, sql, needed_kinds):
    """The acceptance loop: run once recording shapes, JSON round-trip
    the registry export, wipe every cache, AOT-compile from payloads
    alone, then run the SAME query through a FRESH executor and return
    its span names (plus the rows, for the correctness check)."""
    monkeypatch.setenv("TRINO_TPU_WHOLE_TABLE", "1")
    r = LocalQueryRunner()
    plan = _plan(r, sql)
    ref = Executor(r.catalogs, r.session,
                   fragment_jit=True).execute(plan).to_pylist()
    entries = json.loads(json.dumps(HOT_SHAPES.top(100)))
    kinds = {e["kind"] for e in entries}
    assert needed_kinds <= kinds, (needed_kinds, kinds)
    _wipe_program_caches()
    summary = aot.compile_entries(entries)
    assert summary["errors"] == 0, summary
    assert summary["compiled"] >= len(needed_kinds)
    session = Session(catalog="tpch", schema="tiny")
    session.trace = QueryTrace("aot-roundtrip")
    ex = Executor(r.catalogs, session, fragment_jit=True)
    with session.trace.span("execute"):
        out = ex.execute(_plan(r, sql)).to_pylist()
    assert out == ref
    return _span_names(session.trace)


def test_stringdictionary_content_equality():
    from trino_tpu.columnar import StringDictionary
    a, _ = StringDictionary.from_strings(["x", "y", "z", "y"])
    b, _ = StringDictionary.from_strings(["x", "y", "z"])
    c, _ = StringDictionary.from_strings(["y", "x", "z"])
    assert a == b and hash(a) == hash(b)    # distinct objects, same pool
    assert a != c                           # order matters: codes index
    assert a != StringDictionary(np.asarray(["x", "y"], dtype=object))
    # merge's identity fast path is untouched by content equality
    m, rs, ro = a.merge(a)
    assert m is a and list(rs) == [0, 1, 2]


def test_stringdictionary_fingerprint_edges():
    """The fingerprint must not collide on byte-stream ambiguities:
    NULL vs the string "None", and entry boundaries (the length prefix
    keeps ["ab","c"] distinct from ["a","bc"])."""
    import numpy as np
    from trino_tpu.columnar import StringDictionary
    null = StringDictionary(np.asarray([None, "x"], dtype=object))
    lit = StringDictionary(np.asarray(["None", "x"], dtype=object))
    assert null != lit and null.fingerprint != lit.fingerprint
    a = StringDictionary(np.asarray(["ab", "c"], dtype=object))
    b = StringDictionary(np.asarray(["a", "bc"], dtype=object))
    assert a != b and a.fingerprint != b.fingerprint
    # cached: the second access returns the same tuple object
    assert a.fingerprint is a.fingerprint


def test_join_aot_zero_retrace(monkeypatch):
    """Materialized hash join (count + expand), with dictionary-carrying
    transported columns: the AOT-fabricated dictionaries must be
    content-equal to the live ones or the first run retraces."""
    names = _record_wipe_compile_rerun(
        monkeypatch,
        "SELECT o_orderstatus, o_orderpriority, c_nationkey FROM orders "
        "JOIN customer ON o_custkey = c_custkey "
        "WHERE o_totalprice < 123000",
        {"join"})
    assert names.count("jit_trace") == 0, names
    assert names.count("dispatch") >= 2


def test_window_aot_zero_retrace(monkeypatch):
    names = _record_wipe_compile_rerun(
        monkeypatch,
        "SELECT o_custkey, row_number() OVER "
        "(PARTITION BY o_custkey ORDER BY o_totalprice) AS rn "
        "FROM orders WHERE o_orderkey < 1777",
        {"window"})
    assert names.count("jit_trace") == 0, names


def test_combined_q3_shaped_plan_zero_retrace(monkeypatch):
    """The combined acceptance corpus: a q3-shaped plan — two hash
    joins, an aggregation, and a window on top — pre-warmed via
    compile_entries alone, executes end-to-end with zero retraces."""
    names = _record_wipe_compile_rerun(
        monkeypatch,
        "SELECT o_orderkey, revenue, "
        "row_number() OVER (ORDER BY revenue DESC) AS rn "
        "FROM (SELECT o_orderkey, "
        "             sum(l_extendedprice * (1 - l_discount)) AS revenue "
        "      FROM customer "
        "      JOIN orders ON c_custkey = o_custkey "
        "      JOIN lineitem ON l_orderkey = o_orderkey "
        "      WHERE c_mktsegment = 'BUILDING' "
        "      GROUP BY o_orderkey) "
        "ORDER BY revenue DESC LIMIT 10",
        {"join", "window"})
    assert names.count("jit_trace") == 0, names


def test_repartition_aot_prewarms_bucket_kernel():
    """The exchange bucketing kernel records a signature-only payload;
    after a wipe, compile_entries alone makes the next partition call
    an in-process cache hit."""
    from trino_tpu.columnar import batch_from_pylist
    from trino_tpu.stage import repartition as rp
    from trino_tpu.types import BIGINT
    b = batch_from_pylist(
        {"k": list(range(90)), "v": list(range(90))},
        {"k": BIGINT, "v": BIGINT})
    sess = Session(catalog="tpch", schema="tiny")
    ref = [p.to_pylist() for p in
           rp.partition_batch(b, ["k"], 4, session=sess)]
    rents = [e for e in HOT_SHAPES.top(100)
             if e["kind"] == "repartition"]
    assert rents
    rents = json.loads(json.dumps(rents))
    _wipe_program_caches()
    summary = aot.compile_entries(rents)
    assert summary["errors"] == 0 and summary["compiled"] >= 1
    h0 = _JIT_LOOKUPS.value(cache="repartition", result="hit")
    out = [p.to_pylist() for p in
           rp.partition_batch(b, ["k"], 4, session=sess)]
    assert out == ref
    assert _JIT_LOOKUPS.value(cache="repartition", result="hit") > h0


@pytest.mark.parametrize("placed_from_outside", [True, False])
def test_xla_cache_placement(tmp_path, placed_from_outside):
    """Compile cache placement (trino_tpu/config.py): where
    JAX_COMPILATION_CACHE_DIR is set jax uses exactly that directory
    and the engine sets none; unset, the cache lives at the one fixed
    path inside the checkout, <repo>/.jax_cache/xla-<machine tag>."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    target = str(tmp_path / "xla_outside")
    code = ("import jax, trino_tpu, jax.numpy as jnp; "
            "jax.jit(lambda x: x * 2 + 1)(jnp.arange(64.0))"
            ".block_until_ready(); "
            "print(jax.config.jax_compilation_cache_dir)")
    env = dict(os.environ)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.pop("TRINO_TPU_XLA_CACHE", None)
    env["TRINO_TPU_XLA_CACHE_MIN_SECS"] = "0"
    env["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "-1"
    env["JAX_PLATFORMS"] = "cpu"
    if placed_from_outside:
        env["JAX_COMPILATION_CACHE_DIR"] = target
    p = subprocess.run([sys.executable, "-c", code],
                       capture_output=True, text=True, timeout=120,
                       env=env, cwd=repo)
    assert p.returncode == 0, p.stderr
    got = p.stdout.strip().splitlines()[-1]
    if placed_from_outside:
        assert got == target
        assert os.listdir(target), "nothing was cached where asked"
    else:
        assert os.path.dirname(got) == os.path.join(repo, ".jax_cache")
        assert os.path.basename(got).startswith("xla-")
        assert os.path.isdir(got)


def test_streamed_join_with_string_probe_columns():
    """Satellite: streamed joins no longer decline dictionary-carrying
    probe columns — each chunk's codes are remapped into ONE stable
    per-stream dictionary space (build-side seeded), so every chunk
    shares one compiled program and the output matches the
    materialized path bit-for-bit."""
    from trino_tpu.obs.metrics import STREAM_CHUNKS
    r = LocalQueryRunner(session=Session(catalog="tpch",
                                         schema="tiny"))
    r.execute("CREATE TABLE memory.default.dprobe (k VARCHAR, v BIGINT)")
    rows = ",".join(f"('key{i % 13}', {i})" for i in range(150))
    r.execute(f"INSERT INTO memory.default.dprobe VALUES {rows}")
    r.execute("CREATE TABLE memory.default.dbuild (bk VARCHAR, w BIGINT)")
    rows = ",".join(f"('key{i}', {i * 100})" for i in range(9))
    r.execute(f"INSERT INTO memory.default.dbuild VALUES {rows}")
    sqls = (
        "SELECT count(*), sum(v), sum(w) FROM memory.default.dprobe "
        "JOIN memory.default.dbuild ON k = bk",
        # string payload transported through the streamed join
        "SELECT k, sum(v), sum(w) FROM memory.default.dprobe "
        "JOIN memory.default.dbuild ON k = bk GROUP BY k ORDER BY k",
        "SELECT count(*), sum(v) FROM memory.default.dprobe "
        "LEFT JOIN memory.default.dbuild ON k = bk",
    )
    base = [r.execute(q).rows for q in sqls]
    s = Session(catalog="tpch", schema="tiny")
    s.set("stream_chunk_rows", 16)
    rc = LocalQueryRunner(session=s, catalogs=r.catalogs)
    c0 = sum(v for _, v in STREAM_CHUNKS.samples())
    for q, b in zip(sqls, base):
        assert rc.execute(q).rows == b, q
    assert sum(v for _, v in STREAM_CHUNKS.samples()) > c0
