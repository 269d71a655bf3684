"""The mesh executor as a server runs it (ISSUE 28): on the suite's
virtual CPU devices, a 4-device mesh, ``tpch.tiny``.

- q1, q3, q6 through a ``Coordinator(distributed=True)`` over HTTP equal
  the benchmark's plain reference at the mesh cell's own limits, with
  the planner's distributions and with both join sides repartitioned
  and the grouped partials exchanged;
- the exchange (no sort, buffers sized by phase 1's counts) delivers
  exactly the rows the old sort-and-pad exchange delivered, in its
  order, on skewed, empty-shard and all-to-one destinations;
- a second execution of a query traces no mesh program and its scans
  are sharded-cache hits;
- the default selection rule, and that an explicit flag wins;
- the new spans and counters at ``/metrics`` and ``/v1/trace/{id}``.
"""

import json
import os
import sys
import urllib.request

import numpy as np
import pytest

import jax

from trino_tpu.columnar import batch_from_pylist
from trino_tpu.obs.metrics import METRICS, parse_exposition
from trino_tpu.parallel import get_mesh, shard_batch
from trino_tpu.types import BIGINT, DOUBLE, VARCHAR

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
CLASSES = ("q1", "q3", "q6")


def sql_of(cls: str) -> str:
    with open(os.path.join(BENCH, "traffic", "queries", f"{cls}.sql")) as f:
        return f.read()


def counter(name: str, **labels) -> float:
    fam = METRICS.counter(name)
    want = tuple(labels.get(n) for n in fam.labelnames)
    return sum(v for k, v in fam.samples()
               if all(w is None or w == x for w, x in zip(want, k)))


@pytest.fixture(scope="module")
def reference():
    """The cell's reference and limits, as benchmark/run.py reads them."""
    sys.path.insert(0, BENCH)
    try:
        from reference.compare import gaps
        from reference.tpch_answers import Answers
    finally:
        sys.path.remove(BENCH)
    with open(os.path.join(BENCH, "configs", "tpch_sf10_mesh4.json")) as f:
        config = json.load(f)
    answers = Answers(config["rehearsal_scale_factor"], list(CLASSES))
    return gaps, {c: answers.answer(c) for c in CLASSES}, config["limits"]


@pytest.fixture(scope="module")
def coordinator(tmp_path_factory):
    from trino_tpu.server import Coordinator
    co = Coordinator(distributed=True, history_dir=str(
        tmp_path_factory.mktemp("history")))
    co._proto.mesh = get_mesh(4)       # four of the suite's eight devices
    co.start()
    yield co
    co.stop()


def execute(co, sql, **props):
    from trino_tpu.client import StatementClient
    res = StatementClient(co.base_uri, catalog="tpch", schema="tiny",
                          session_properties=props or None).execute(sql)
    assert res.state == "FINISHED", res.error
    return res


def spans_of(co, query_id):
    with urllib.request.urlopen(
            f"{co.base_uri}/v1/trace/{query_id}", timeout=30) as resp:
        doc = json.loads(resp.read())
    out = []
    for rs in doc["resourceSpans"]:
        for ss in rs["scopeSpans"]:
            for s in ss["spans"]:
                attrs = {a["key"]: next(iter(a["value"].values()))
                         for a in s.get("attributes", [])}
                out.append((s["name"], attrs))
    return out


@pytest.mark.parametrize("cls", CLASSES)
def test_served_mesh_answers_equal_the_reference(coordinator, reference,
                                                 cls):
    gaps, answers, limits = reference
    res = execute(coordinator, sql_of(cls))
    mismatches, rel = gaps(res.rows, answers[cls])
    assert mismatches <= limits["exact_mismatches"]
    assert rel <= limits["max_rel_err"]
    kinds = {k[0] for k, _ in METRICS.counter(
        "trino_tpu_device_programs_total").samples()}
    assert "spmd_agg" in kinds


@pytest.fixture
def fresh_mesh_programs():
    """No mesh program or refusal from before the test, and none of
    the test's (traced under a patched limit) after it."""
    from trino_tpu.exec.progkey import PROGRAMS
    PROGRAMS.clear("spmd")
    yield
    PROGRAMS.clear("spmd")


def test_q3_with_both_sides_repartitioned_and_partials_exchanged(
        coordinator, reference, monkeypatch, fresh_mesh_programs):
    """sf10's shape at tiny: the PARTITIONED join (both sides through
    the all_to_all exchange) and a grouped aggregation whose partials
    are too large to gather (partial -> exchange -> final)."""
    from trino_tpu.exec import distributed
    gaps, answers, limits = reference
    monkeypatch.setattr(distributed, "FUSED_PARTIAL_ROWS", 4)
    moved = counter("trino_tpu_mesh_exchange_rows_total", kind="repartition")
    res = execute(coordinator, sql_of("q3"),
                  join_distribution_type="PARTITIONED")
    mismatches, rel = gaps(res.rows, answers["q3"])
    assert (mismatches, rel <= limits["max_rel_err"]) == (0, True)
    assert counter("trino_tpu_mesh_exchange_rows_total",
                   kind="repartition") > moved
    programs = [a.get("program", "") for n, a in
                spans_of(coordinator, res.query_id)
                if n in ("dispatch", "jit_trace")]
    for kind in ("spmd_exchange_counts", "spmd_exchange",
                 "spmd_join_count", "spmd_join_expand", "spmd_apply"):
        assert any(p.startswith(kind + ":") for p in programs), kind


@pytest.mark.parametrize("distribution", ["AUTOMATIC", "PARTITIONED"])
def test_a_mesh_join_s_expand_gathers_the_join_s_outputs_alone(
        coordinator, reference, distribution):
    """q3 on the mesh, its build sides broadcast or both sides
    repartitioned: the answers are the reference's, and each
    ``spmd_join_expand`` is handed the lanes its join puts out (the
    plan's ``outputs``) of all its two inputs offer: the ``lanes`` of
    its dispatch span, ``<kept>/<offered>``."""
    from trino_tpu.plan.nodes import JoinNode
    from trino_tpu.runner import LocalQueryRunner
    from trino_tpu.session import Session
    gaps, answers, limits = reference
    res = execute(coordinator, sql_of("q3"),
                  join_distribution_type=distribution)
    mismatches, rel = gaps(res.rows, answers["q3"])
    assert (mismatches, rel <= limits["max_rel_err"]) == (0, True)

    def joins(node):
        if isinstance(node, JoinNode):
            yield node
        for s in node.sources:
            yield from joins(s)

    plan = LocalQueryRunner(session=Session(
        catalog="tpch", schema="tiny")).plan_sql(sql_of("q3"))
    def lanes(j):
        offered = len(j.left.output_schema()) + len(j.right.output_schema())
        return f"{len(j.outputs)}/{offered}"

    want = sorted(lanes(j) for j in joins(plan))
    got = sorted(a["lanes"] for n, a in spans_of(coordinator, res.query_id)
                 if n in ("dispatch", "jit_trace")
                 and a.get("program", "").startswith("spmd_join_expand:"))
    assert got == want == ["5/8", "6/7"]


def test_a_repartition_s_exchange_span_closes_on_its_moved_rows(
        coordinator):
    """A mesh program's span waits for nothing, so an exchange span
    ends in ONE ``host_read[exchange_done]`` on its moving program:
    ``exchange_ms`` still times the exchange. The served query fences
    no node and ends its execute in one ``host_read[node_rows]``."""
    res = execute(coordinator, sql_of("q3"),
                  join_distribution_type="PARTITIONED")
    trace = coordinator.tracker.get(res.query_id).trace
    spans = trace.all_spans()
    exchanges = [s for s in spans if s.name == "exchange"
                 and s.attrs.get("kind") == "repartition"]
    assert exchanges
    for ex in exchanges:
        last = ex.children[-1]
        assert (last.name, last.attrs.get("site")) == ("host_read",
                                                       "exchange_done")
        assert last.end_s <= ex.end_s
    sites = [s.attrs.get("site") for s in spans if s.name == "host_read"]
    assert sites.count("node_rows") == 1
    assert not {"node_fence", "split_rows"} & set(sites)
    assert "device_execute" not in {s.name for s in spans}


def test_a_repeated_query_compiles_nothing_under_fragment_jit(
        coordinator, monkeypatch):
    """What ``window_compile_requests`` counts (the backend compiles
    ``jax.monitoring`` reports), on the chip's default: there every
    chain is one cached program, and the mesh executor's final TopN
    has to be one too (eager, its scan compiled anew on every q3)."""
    monkeypatch.setenv("TRINO_TPU_FRAGMENT_JIT", "1")
    for _ in range(2):
        for cls in CLASSES:
            execute(coordinator, sql_of(cls))
    compiles = []

    def on_duration(event, _secs, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(event)
    jax.monitoring.register_event_duration_secs_listener(on_duration)
    try:
        for cls in CLASSES:
            execute(coordinator, sql_of(cls))
        assert compiles == []
    finally:
        from jax._src import monitoring
        monitoring.unregister_event_duration_listener(
            on_duration)


def test_second_execution_traces_nothing_and_hits_the_sharded_cache(
        coordinator):
    for cls in CLASSES:                 # whatever ran before: warm
        execute(coordinator, sql_of(cls))
    miss = counter("trino_tpu_jit_cache_total", cache="spmd",
                   result="miss")
    scan_miss = counter("trino_tpu_scan_cache_total", cache="sharded",
                        result="miss")
    scan_hit = counter("trino_tpu_scan_cache_total", cache="sharded",
                       result="hit")
    for cls in CLASSES:
        res = execute(coordinator, sql_of(cls))
        names = [n for n, _ in spans_of(coordinator, res.query_id)]
        assert "dispatch" in names and "device_execute" not in names
        assert "jit_trace" not in names and "scan_fill" not in names
    assert counter("trino_tpu_jit_cache_total", cache="spmd",
                   result="miss") == miss
    assert counter("trino_tpu_scan_cache_total", cache="sharded",
                   result="miss") == scan_miss
    # q1 and q6 scan lineitem, q3 lineitem and orders (customer at tiny
    # is one split under MIN_SHARD_ROWS: the coordinator keeps it)
    assert counter("trino_tpu_scan_cache_total", cache="sharded",
                   result="hit") == scan_hit + 4


def test_spans_and_counters_of_a_mesh_query(coordinator):
    res = execute(coordinator, sql_of("q3"))
    spans = spans_of(coordinator, res.query_id)
    exchanges = [a for n, a in spans if n == "exchange"]
    assert {a["kind"] for a in exchanges} >= {"broadcast"}
    for a in exchanges:
        assert int(a["rows"]) >= 0 and int(a["bytes"]) >= int(a["rows"])
    sites = {a.get("site") for n, a in spans if n == "host_read"}
    assert {"join_total", "broadcast_rows"} <= sites
    programs = {a["program"].split(":")[0] for n, a in spans
                if n == "dispatch"}
    assert {"spmd_broadcast", "spmd_join_count",
            "spmd_join_expand"} <= programs
    with urllib.request.urlopen(coordinator.base_uri + "/metrics",
                                timeout=30) as resp:
        samples = parse_exposition(resp.read().decode())
    assert samples["trino_tpu_mesh_exchange_bytes_total"]
    assert samples["trino_tpu_mesh_exchange_rows_total"]
    phases = {dict(k).get("phase") if isinstance(k, dict) else k
              for k in samples["trino_tpu_query_phase_seconds_count"]}
    assert any("exchange" in str(p) for p in phases)
    kinds = str(samples["trino_tpu_device_programs_total"])
    assert "spmd_join_count" in kinds


def test_scan_fill_span_names_its_shards(tmp_path):
    """A fresh connector (nothing cached): the first scan is ONE
    ``scan_fill`` with a ``shards`` attr, the lanes land row-sharded on
    the mesh's own devices, and the second scan is a hit."""
    from trino_tpu.runner import LocalQueryRunner
    r = LocalQueryRunner(distributed=True, n_devices=4,
                         collect_node_stats=True)
    res = r.execute("select count(*), sum(o_custkey) from orders")
    fills = [s for s in res.trace.all_spans() if s.name == "scan_fill"]
    assert [s.attrs["shards"] for s in fills] == [4]
    hit = counter("trino_tpu_scan_cache_total", cache="sharded",
                  result="hit")
    again = r.execute("select count(*), sum(o_custkey) from orders")
    assert again.rows == res.rows and res.rows[0][0] == 15000
    assert not [s for s in again.trace.all_spans() if s.name == "scan_fill"]
    assert counter("trino_tpu_scan_cache_total", cache="sharded",
                   result="hit") == hit + 1


# ---- the exchange against the one it replaces ----------------------------

def old_exchange(shards, dest, n):
    """What the sort-and-pad exchange delivered: destination d gets the
    rows bound for it in (source shard, source position) order."""
    out = [[] for _ in range(n)]
    for rows, dests in zip(shards, dest):
        for row, d in zip(rows, dests):
            out[d].append(row)
    return out


def dest_cases(n, per):
    rng = np.random.default_rng(28)
    uniform = [rng.integers(0, n, per).tolist() for _ in range(n)]
    skewed = [np.where(rng.random(per) < 0.9, 0,
                       rng.integers(0, n, per)).tolist()
              for _ in range(n)]
    return {
        "uniform": (uniform, [per] * n),
        "skewed": (skewed, [per] * n),
        # source shards 1 and 3 hold no rows; nobody sends to shard 2
        "empty_shards": ([[d if d != 2 else 0 for d in row]
                          for row in uniform],
                         [per, 0, per - 3, 0]),
        "all_to_one": ([[3] * per for _ in range(n)], [per] * n),
        "nothing_live": (uniform, [0] * n),
    }


@pytest.mark.parametrize("case", ["uniform", "skewed", "empty_shards",
                                  "all_to_one", "nothing_live"])
def test_exchange_equals_the_old_one_row_for_row(case):
    from trino_tpu.parallel.mesh import ShardedBatch, replicated
    from trino_tpu.parallel.spmd import _two_phase_exchange
    import jax.numpy as jnp
    n, per = 4, 32
    mesh = get_mesh(n)
    dest, live = dest_cases(n, per)[case]
    rng = np.random.default_rng(5)
    ids = np.arange(n * per, dtype=np.int64)
    vals = rng.normal(size=n * per)
    words = ["ab", "c", None, "dddd"]
    b = batch_from_pylist(
        {"id": ids.tolist(), "v": vals.tolist(),
         "s": [words[i % 4] for i in range(n * per)],
         "dest": [d for row in dest for d in row]},
        {"id": BIGINT, "v": DOUBLE, "s": VARCHAR, "dest": BIGINT})
    sb = shard_batch(b, mesh, per_shard_cap=per)
    sb = ShardedBatch(sb.columns, jax.device_put(
        np.asarray(live, np.int64), replicated(mesh)), mesh, per)
    out = _two_phase_exchange(
        sb, ("test_dest", case),
        lambda cols, _n: jnp.asarray(cols["dest"].data).astype(jnp.int32))
    rows = [[(int(ids[s * per + j]), float(vals[s * per + j]),
              words[(s * per + j) % 4]) for j in range(live[s])]
            for s in range(n)]
    want = old_exchange(rows, [d[:k] for d, k in zip(dest, live)], n)
    counts = np.asarray(out.num_rows)
    assert counts.tolist() == [len(w) for w in want]
    cap = out.per_shard_cap
    assert cap <= max(8, 2 * max(counts.max(), 1))      # sized, not 4x
    got_id = np.asarray(out.columns["id"].data)
    got_v = np.asarray(out.columns["v"].data)
    sc = out.columns["s"]
    got_s, got_sv = np.asarray(sc.data), np.asarray(sc.valid)
    for d in range(n):
        for j, (i, v, s) in enumerate(want[d]):
            at = d * cap + j
            assert (got_id[at], got_v[at]) == (i, v)
            assert bool(got_sv[at]) == (s is not None)
            if s is not None:
                assert sc.dictionary.values[got_s[at]] == s


# ---- who runs over the mesh ------------------------------------------------

def test_default_rule_keeps_a_cpu_host_with_eight_devices_local():
    from trino_tpu.parallel.mesh import mesh_by_default
    from trino_tpu.server import Coordinator
    assert jax.local_device_count() == 8 and not mesh_by_default()
    co = Coordinator()
    assert co._distributed is False and co._proto.mesh is None


@pytest.mark.parametrize("backend,devices,want", [
    ("tpu", 4, True), ("tpu", 1, False), ("cpu", 4, False),
    ("gpu", 4, False)])
def test_default_rule_reads_platform_and_device_count(monkeypatch, backend,
                                                      devices, want):
    from trino_tpu.parallel import mesh
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    monkeypatch.setattr(jax, "local_device_count", lambda: devices)
    assert mesh.mesh_by_default() is want


@pytest.mark.parametrize("flag", [True, False])
def test_an_explicit_flag_wins(monkeypatch, flag):
    from trino_tpu.parallel import mesh
    from trino_tpu.server import Coordinator
    monkeypatch.setattr(mesh, "mesh_by_default", lambda: not flag)
    co = Coordinator(distributed=flag)
    assert co._distributed is flag
    assert (co._proto.mesh is not None) is flag


def deployment_pin():
    """The new cell's first pin: the configuration's own words."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "tpch_sf10_mesh4.json")) as f:
        config = json.load(f)
    table, spec = next(iter(config["tables"].items()))
    assert table == "nodes"     # asked before any table is read
    return spec


def test_the_deployment_pin_reads_the_mesh_s_size(coordinator):
    spec = deployment_pin()
    assert (spec["rows"], spec["pin_sum"]) == (1, 4)
    assert execute(coordinator, spec["pin_sql"]).rows == [[1, 4]]


def test_the_deployment_pin_reads_one_device_on_the_local_path():
    from trino_tpu.server import Coordinator
    co = Coordinator(distributed=False).start()
    try:
        assert execute(co, deployment_pin()["pin_sql"]).rows == [[1, 1]]
    finally:
        co.stop()


def test_the_server_has_no_distributed_flag():
    from trino_tpu.server import main
    with pytest.raises(SystemExit):
        main.main(["--distributed", "--port", "0"])
