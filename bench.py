"""Benchmark: TPC-H q1 engine throughput, rows/sec/chip, vs 1 CPU worker.

Two legs per backend (the reference's HandTpchQuery1.java micro vs the
full-engine operator path):
  engine — SQL TPC-H q1 @ sf1 through the FULL path
           (parse -> plan -> optimize -> execute), BASELINE.json configs[1]
  micro  — the hand-fused jitted q1 stage program over raw sf1 lanes

Harness contract (round-4 postmortem: rc=124, nothing printed — the old
harness ran up to 6 subprocesses x 1200s each):
  * HARD overall wall-clock budget: env BENCH_BUDGET, default 540s.
    Every subprocess timeout derives from the remaining budget; a
    SIGALRM net guarantees the JSON line prints even if bookkeeping is
    wrong.
  * ONE device subprocess runs BOTH device legs (backend init is a
    fixed cost per process — pay it once), then ONE
    CPU subprocess runs both baseline legs. Probes print each leg's
    result as its own JSON line the moment the leg finishes, so a
    timeout mid-probe still yields the completed legs (TimeoutExpired
    carries the captured stdout).
  * sf1 q1 lanes are generated once and cached as npz under
    ~/.cache/trino_tpu/ (generate: ~7s, load: ~0.3s on this 1-core host).
  * CPU micro baseline runs on a 10% row sample (rows/sec normalizes);
    CPU engine runs sf1 (measured ~3s/iteration — affordable).

  * The device side runs as SUB-PROBES — device_init (backend contact
    only), device_first_compile (pays the q1 compile, populating the
    persistent XLA cache), device_steady (engine/micro/telemetry over
    the warm cache), device_q18 (streamed q18 at scale) — each its own
    subprocess under its OWN cap, each checkpointed to
    ~/.cache/trino_tpu/bench_subprobes.json the moment it lands. A
    rerun of a timed-out round resumes past completed sub-probes; one
    sub-probe's blowout zeroes ONLY its own keys (round-5 verdict: a
    single 360s device hang zeroed every device number).
  * Every probe subprocess shares one persistent XLA cache directory
    (trino_tpu/config.py: JAX_COMPILATION_CACHE_DIR where set, else the
    fixed <checkout>/.jax_cache), so the first_compile sub-probe's XLA
    artifacts carry into device_steady (a different process): warm
    numbers measure the cache, not a lucky process lifetime.
  * BENCH_FORCE_SUBPROBE_TIMEOUT=<name[,name]> caps the named
    sub-probes at ~1s — the resumability/blowout drill.

Whatever happens, exactly ONE final JSON line is printed:
{"metric", "value", "unit", "vs_baseline", ...}.
"""

import json
import os
import signal
import subprocess
import sys
import time

import numpy as np

BUDGET = float(os.environ.get("BENCH_BUDGET", "540"))
_T0 = time.monotonic()
CACHE_DIR = os.path.expanduser(os.environ.get(
    "TRINO_TPU_BENCH_CACHE", "~/.cache/trino_tpu"))
# the persistent XLA cache is placed by trino_tpu/config.py alone
# (JAX_COMPILATION_CACHE_DIR where set, else <checkout>/.jax_cache):
# every probe subprocess inherits the environment and runs from this
# checkout, so all of them share one directory without help from here


def _remaining() -> float:
    return BUDGET - (time.monotonic() - _T0)


# --------------------------------------------------------------------------
# sub-probe checkpoint: a timed-out/crashed round resumes where it died
# --------------------------------------------------------------------------

_CKPT_PATH = os.path.join(CACHE_DIR, "bench_subprobes.json")
_CKPT_TTL = float(os.environ.get("BENCH_CHECKPOINT_TTL", "7200"))
_ROUND_ID = os.environ.get("BENCH_ROUND_ID", "")


def _ckpt_load() -> dict:
    """Completed sub-probes of THIS round (same BENCH_ROUND_ID, within
    TTL) — anything else is a different round's history, ignored."""
    try:
        with open(_CKPT_PATH) as f:
            d = json.load(f)
        if d.get("round") != _ROUND_ID:
            return {}
        if time.time() - float(d.get("ts", 0.0)) > _CKPT_TTL:
            return {}
        return dict(d.get("subprobes", {}))
    except Exception:
        return {}


def _ckpt_save(subprobes: dict) -> None:
    try:
        os.makedirs(CACHE_DIR, exist_ok=True)
        tmp = _CKPT_PATH + f".{os.getpid()}.tmp"
        with open(tmp, "w") as f:
            json.dump({"round": _ROUND_ID, "ts": time.time(),
                       "subprobes": subprobes}, f)
        os.replace(tmp, _CKPT_PATH)
    except Exception:
        pass


# --------------------------------------------------------------------------
# data: sf1 q1 lanes, generated once, npz-cached across probes/rounds
# --------------------------------------------------------------------------

def _gen_q1_columns(sf: float):
    """q1's 7 lineitem lanes straight from the generator's vectorized
    field functions (no host string materialization)."""
    from trino_tpu.connectors.tpch import (_LineFields, _line_counts,
                                           CURRENTDATE, table_rows)
    orders = table_rows("orders", sf)
    order_idx = np.arange(1, orders + 1, dtype=np.int64)
    counts = _line_counts(order_idx)
    order_rep = np.repeat(order_idx, counts)
    line_no = np.concatenate([np.arange(1, c + 1) for c in counts])
    lf = _LineFields(order_rep, line_no.astype(np.int64), sf)
    returned = lf.receiptdate <= CURRENTDATE
    from trino_tpu.connectors.tpch import _u64, _SEED
    ra = (_u64(_SEED["lineitem"] + 20, lf.rid) % np.uint64(2)).astype(
        np.int64)
    rflag = np.where(returned, ra, 2).astype(np.int32)
    lstatus = (lf.shipdate > CURRENTDATE).astype(np.int32)
    return (lf.quantity, lf.extendedprice, lf.discount, lf.tax,
            lf.shipdate.astype(np.int32), rflag, lstatus)


def _q1_columns_cached(sf: float):
    tag = str(sf).replace(".", "_")
    path = os.path.join(CACHE_DIR, f"bench_q1_sf{tag}.npz")
    if os.path.exists(path):
        try:
            d = np.load(path)
            return [d[f"c{i}"] for i in range(7)]
        except Exception:
            pass
    cols = _gen_q1_columns(sf)
    try:
        os.makedirs(CACHE_DIR, exist_ok=True)
        # np.savez appends .npz when missing — name the temp file with
        # the suffix or os.replace never finds it
        tmp = path + f".{os.getpid()}.tmp.npz"
        np.savez(tmp, **{f"c{i}": c for i, c in enumerate(cols)})
        os.replace(tmp, path)
    except Exception:
        pass
    return cols


# --------------------------------------------------------------------------
# probe legs (run inside the probe subprocess)
# --------------------------------------------------------------------------

def _cold_warm(run_once, iters: int):
    """(cold wall, best warm wall) of ``run_once``: the first call pays
    trace + XLA compile (or proves the persistent cache absorbed
    them), the best of ``iters`` repeats is steady state. Splitting
    the two is the whole point of the compile-amortization work —
    every leg reports both."""
    t0 = time.perf_counter()
    run_once()
    cold = time.perf_counter() - t0
    best = float("inf")
    for _ in range(max(iters, 1)):
        t0 = time.perf_counter()
        run_once()
        best = min(best, time.perf_counter() - t0)
    return cold, best


def _cw_keys(cold: float, warm: float) -> dict:
    """The per-leg compile/warm scoreboard keys: compile_s is the
    cold-minus-warm wall (trace + XLA compile + cache population),
    warm_speedup the cold/warm ratio (ROADMAP item 1's success
    metric: how much the second run gains)."""
    return {"cold_s": round(cold, 4), "warm_s": round(warm, 4),
            "compile_s": round(max(cold - warm, 0.0), 4),
            "warm_speedup": round(cold / warm, 2) if warm > 0 else 0.0}


def _leg_micro(sf: float, iters: int) -> dict:
    """rows/sec of the jitted q1 stage program on this backend."""
    import jax
    import jax.numpy as jnp
    import trino_tpu  # noqa: F401  (x64)
    from __graft_entry__ import _q1_step

    cols = _q1_columns_cached(sf)
    rows = len(cols[0])
    cap = 1
    while cap < rows:
        cap <<= 1
    padded = [np.pad(c, (0, cap - rows)) for c in cols]
    dev = [jax.device_put(jnp.asarray(c)) for c in padded]
    n = jnp.asarray(rows, jnp.int64)

    def fetch(out, ng):
        # the timed unit ends with results ON HOST: a real host
        # readback is the fence a client of the engine would see
        return {k: np.asarray(v) for k, v in out.items()}, int(ng)

    step = jax.jit(_q1_step)
    cold, best = _cold_warm(lambda: fetch(*step(*dev, n)), iters)
    return dict({"rows_per_sec": rows / best}, **_cw_keys(cold, best))


def _leg_engine(schema: str, iters: int) -> dict:
    """rows/sec of SQL TPC-H q1 through the FULL engine path."""
    import trino_tpu  # noqa: F401
    from trino_tpu.benchmarks.tpch_queries import TPCH_QUERIES
    from trino_tpu.runner import LocalQueryRunner
    from trino_tpu.session import Session

    r = LocalQueryRunner(session=Session(catalog="tpch", schema=schema))
    rows = int(r.execute("SELECT count(*) FROM lineitem").rows[0][0])

    def once():
        res = r.execute(TPCH_QUERIES[1])
        assert len(res.rows) >= 4

    cold, best = _cold_warm(once, iters)
    return dict({"rows_per_sec": rows / best}, **_cw_keys(cold, best))


def _leg_warm(schema: str) -> dict:
    """The explicit cold-vs-warm leg: the SAME query through two FRESH
    LocalQueryRunners (fresh planner, fresh Executor per run). The
    second runner's first execution rides the canonical-key structural
    caches (exec/progkey.py) — its "cold" wall is what a repeated
    query costs after the compile tax is paid once, and warm_speedup
    = runner1-cold / runner2-first is the amortization factor the
    whole subsystem exists to maximize.

    Runs FIRST in the probe (before the engine leg, which executes the
    same query): the cold wall must genuinely pay the q1 compile, not
    ride programs an earlier leg cached. Data generation is hoisted
    out of the timed walls through a query whose programs DON'T
    overlap q1's (count(*) — different canonical keys), and
    fragment-jit is forced on for the leg's runners so the CPU probe
    measures the same amortization machinery the device path uses."""
    import trino_tpu  # noqa: F401
    from trino_tpu.benchmarks.tpch_queries import TPCH_QUERIES
    from trino_tpu.runner import LocalQueryRunner
    from trino_tpu.session import Session

    def once():
        r = LocalQueryRunner(
            session=Session(catalog="tpch", schema=schema))
        res = r.execute(TPCH_QUERIES[1])
        assert len(res.rows) >= 4

    prev = os.environ.get("TRINO_TPU_FRAGMENT_JIT")
    os.environ["TRINO_TPU_FRAGMENT_JIT"] = "1"
    try:
        # generate the tables without compiling any q1 program
        LocalQueryRunner(
            session=Session(catalog="tpch", schema=schema)).execute(
                "SELECT count(*) FROM lineitem")
        cold, warm = _cold_warm(once, 1)
    finally:
        if prev is None:
            os.environ.pop("TRINO_TPU_FRAGMENT_JIT", None)
        else:
            os.environ["TRINO_TPU_FRAGMENT_JIT"] = prev
    return dict({"fresh_runner": True}, **_cw_keys(cold, warm))


def _leg_q18(schema: str) -> dict:
    """rows/sec of TPC-H q18 (BASELINE configs[3] shape: large
    build-side join + IN-subquery semi-join) through the full engine,
    under a per-node memory budget deliberately SMALLER than the q18
    probe working set — the beyond-HBM morsel-streaming path
    (exec/streamjoin.py) engages every round: probe chunks stream
    through double-buffered host->device transfers instead of the
    query dying on the materialization estimate. The budget covers
    the orders build state plus 64MB of chunk room — far below the
    lineitem probe estimate, so the build materializes and the probe
    streams; BENCH_Q18_BUDGET_BYTES overrides."""
    import trino_tpu  # noqa: F401
    from trino_tpu.benchmarks.tpch_queries import TPCH_QUERIES
    from trino_tpu.config import capacity_for
    from trino_tpu.connectors.tpch import SCHEMAS, table_rows
    from trino_tpu.obs.metrics import METRICS
    from trino_tpu.runner import LocalQueryRunner
    from trino_tpu.session import Session

    n_orders = table_rows("orders", SCHEMAS[schema])
    rows = n_orders * 4                 # ~lineitem rows
    # budget = the orders build state (4 lanes + sorted hash-table
    # lanes at its capacity bucket) + 64MB of chunk room — well below
    # the ~16B/row lineitem probe estimate, so the probe streams
    budget = int(os.environ.get("BENCH_Q18_BUDGET_BYTES",
                                capacity_for(n_orders) * 48
                                + (64 << 20)))
    session = Session(catalog="tpch", schema=schema)
    session.set("query_max_memory_per_node", budget)
    r = LocalQueryRunner(session=session)

    # hoist the bulk of data generation out of the timed walls (scale
    # probes run in a fresh subprocess — untimed, cold_s would report
    # sf10 table generation as compile tax). Column generation is
    # lazy, so a residual sliver can still land in cold_s; datagen_s
    # makes the split auditable in the artifact.
    t0 = time.perf_counter()
    for t in ("lineitem", "orders", "customer"):
        r.execute(f"SELECT count(*) FROM {t}")
    datagen_s = time.perf_counter() - t0

    def once():
        res = r.execute(TPCH_QUERIES[18])
        # tiny legitimately has zero orders over the HAVING>300 bar
        assert len(res.rows) > 0 or schema == "tiny"

    chunks = METRICS.counter("trino_tpu_stream_chunks_total")
    h2d = METRICS.counter("trino_tpu_stream_bytes_h2d_total")
    over = METRICS.counter(
        "trino_tpu_stream_transfers_overlapped_total")

    def stream_totals():
        return (sum(v for _, v in chunks.samples()),
                h2d.value(), over.value())

    c0, b0, o0 = stream_totals()
    cold, warm = _cold_warm(once, 1)
    c1, b1, o1 = stream_totals()
    nruns = 2                       # cold + 1 timed repeat
    dc = max(c1 - c0, 0.0)
    return dict({"rows_per_sec": rows / warm,
                 "datagen_s": round(datagen_s, 2),
                 "budget_bytes": budget,
                 "stream_chunks": round(dc / nruns, 1),
                 "stream_h2d_bytes": round((b1 - b0) / nruns, 1),
                 "stream_overlap_ratio":
                     round((o1 - o0) / dc, 4) if dc else 0.0},
                **_cw_keys(cold, warm))


def _leg_telemetry(schema: str, iters: int) -> dict:
    """Fractional overhead of telemetry on the DEFAULT (multistage
    MPP) distributed path: TPC-H q1 through two in-process workers
    with collect_node_stats OFF vs ON — ON meaning the full PR 15
    stack (distributed tracing with traceparent propagation and
    id-preserving span merge, device/CPU attribution, OTLP file
    export) PLUS the PR 19 ride-alongs: learned operator statistics
    (worker ``learnedStats`` deltas merged at the scheduler,
    exec/learnedstats.py) and a query-history record append per run
    (obs/history.py). The always-on OperatorStats question — this
    ratio is what decides whether telemetry can default on; target
    < 0.05 (tests/test_observability.py). ``overhead`` is a fraction
    (0.03 = 3% slower); the compile/warm split rides along from the
    telemetry-off run. Each bench round also appends its own summary
    record to the DEFAULT history store, so the perf trajectory
    itself is queryable via system.runtime.queries."""
    import tempfile

    import trino_tpu  # noqa: F401
    from trino_tpu.benchmarks.tpch_queries import TPCH_QUERIES
    from trino_tpu.config import CONFIG
    from trino_tpu.exec.learnedstats import LEARNED_STATS
    from trino_tpu.exec.remote import DistributedHostQueryRunner
    from trino_tpu.obs.history import QueryHistoryStore, sql_digest
    from trino_tpu.server.task_worker import TaskWorkerServer
    from trino_tpu.session import Session

    workers = [TaskWorkerServer().start() for _ in range(2)]
    uris = [w.base_uri for w in workers]
    sink = os.path.join(tempfile.mkdtemp(prefix="bench_otlp_"),
                        "traces.jsonl")
    hist = QueryHistoryStore(os.path.join(
        CONFIG.spool_dir, "history", "queries.jsonl"))
    old_file = CONFIG.otlp_file
    lstats0 = len(LEARNED_STATS)
    plan_key = ""
    try:
        def cold_best(collect: bool):
            # OTLP export + history append ride ONLY the telemetry-on
            # side: the overhead number prices tracing + attribution +
            # export + history + learned stats together, against a
            # genuinely dark baseline
            CONFIG.otlp_file = sink if collect else ""
            r = DistributedHostQueryRunner(
                uris, session=Session(catalog="tpch", schema=schema),
                collect_node_stats=collect)

            def once():
                res = r.execute(TPCH_QUERIES[1])
                if collect:
                    nonlocal plan_key
                    plan_key = getattr(res, "plan_key", "") or plan_key
                    hist.record({
                        "query_id": "bench_telemetry_"
                                    + time.strftime("%Y%m%d_%H%M%S"),
                        "state": "FINISHED", "user": "bench",
                        "source": "bench", "sql": TPCH_QUERIES[1][:512],
                        "sql_digest": sql_digest(TPCH_QUERIES[1]),
                        "plan_key": plan_key,
                        "wall_s": 0.0, "rows": len(res.rows),
                        "cpu_s": getattr(res, "cpu_seconds", 0.0),
                        "created": time.time()})

            return _cold_warm(once, iters)

        off_cold, off = cold_best(False)
        _, on = cold_best(True)
        try:
            with open(sink) as f:
                exports = sum(1 for _ in f)
        except OSError:
            exports = 0
    finally:
        CONFIG.otlp_file = old_file
        for w in workers:
            w.stop()
    # the leg's own verdict record: one summary per bench round, the
    # overhead trajectory queryable as source='bench' history rows
    hist.record({
        "query_id": "bench_round_" + time.strftime("%Y%m%d_%H%M%S"),
        "state": "FINISHED", "user": "bench", "source": "bench",
        "sql": "-- bench telemetry leg summary",
        "sql_digest": sql_digest("-- bench telemetry leg summary"),
        "plan_key": plan_key, "wall_s": on, "created": time.time(),
        "bench_overhead": max(on / off - 1.0, 0.0)})
    return dict({"overhead": max(on / off - 1.0, 0.0),
                 "otlp_exports": exports,
                 "learned_entries": len(LEARNED_STATS) - lstats0,
                 "history_records": len(hist)},
                **_cw_keys(off_cold, off))


def _fault_failover_subleg() -> dict:
    """Coordinator-failover resume mini-leg: a 3-stage distributed
    query whose coordinator dies at the ``coordinator.post_stage_commit``
    fault site (fte/faultpoints.py) right after the first stage's
    partitions commit; a replacement coordinator binds the SAME port,
    reloads the spooled execution manifest, re-reads the committed
    partitions off the spool and re-dispatches only the rest. Reports
    the wall seconds from coordinator death to the client seeing
    FINISHED (through the ordinary nextUri chain — the client's
    bounded poll retry rides out the outage) plus the resumed/replayed
    partition split."""
    import threading
    import time as _time

    from trino_tpu.client import StatementClient
    from trino_tpu.fte import faultpoints
    from trino_tpu.obs.metrics import FAILOVER_PARTITIONS
    from trino_tpu.server.coordinator import Coordinator
    from trino_tpu.server.task_worker import TaskWorkerServer

    sql = ("SELECT n_name, count(*) FROM nation "
           "JOIN region ON n_regionkey = r_regionkey "
           "GROUP BY n_name ORDER BY n_name")
    workers = [TaskWorkerServer().start() for _ in range(2)]
    uris = [w.base_uri for w in workers]
    co1 = Coordinator(worker_uris=uris).start()
    died = {}
    replacement = {}

    def kill(site):
        # in-process stand-in for SIGKILL at the fault site: the HTTP
        # server goes away and SystemExit (not an Exception — q.run
        # cannot catch it) freezes the query thread mid-flight
        died["t"] = _time.perf_counter()
        co1.tracker.manifests = None
        co1.tracker.results = None
        co1._httpd.shutdown()
        co1._httpd.server_close()
        died["closed"] = True
        raise SystemExit

    def boot_replacement():
        while "closed" not in died:
            _time.sleep(0.005)
        for _ in range(100):    # the dying server's port may linger
            try:
                replacement["co"] = Coordinator(
                    port=co1.port, worker_uris=uris).start()
                return
            except OSError:
                _time.sleep(0.02)

    r0 = FAILOVER_PARTITIONS.value(outcome="resumed")
    p0 = FAILOVER_PARTITIONS.value(outcome="replayed")
    faultpoints.reset()
    faultpoints.install("coordinator.post_stage_commit", callback=kill)
    try:
        threading.Thread(target=boot_replacement, daemon=True).start()
        client = StatementClient(
            co1.base_uri, session_properties={
                "retry_policy": "TASK",
                "retry_initial_delay_ms": "10",
                "remote_task_timeout": "30"})
        res = client.execute(sql)
        wall = _time.perf_counter() - died["t"]
        if res.state != "FINISHED" or "t" not in died:
            return {}
        return {
            "coordinator_failover_resume_s": wall,
            "failover_parts_resumed":
                FAILOVER_PARTITIONS.value(outcome="resumed") - r0,
            "failover_parts_replayed":
                FAILOVER_PARTITIONS.value(outcome="replayed") - p0,
        }
    finally:
        faultpoints.reset()
        co = replacement.get("co")
        if co is not None:
            co.stop()
        for w in workers:
            w.stop()


def _leg_fault(iters: int) -> dict:
    """Fault-tolerant execution recovery overhead: the SAME distributed
    query through two in-process workers, 0 vs 1 injected worker
    failure (a stub that 500s every results pull), retry_policy=TASK.
    The fractional slowdown is the price of a mid-query worker death;
    the dict also carries the scrape-side artifacts (task-retry counter
    + per-query peak-memory gauge) so the leg proves /metrics exposes
    them, and the coordinator-failover mini-leg's resume timing +
    partition split ride along."""
    import threading
    from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

    import trino_tpu  # noqa: F401
    from trino_tpu.exec.remote import DistributedHostQueryRunner
    from trino_tpu.obs.metrics import METRICS
    from trino_tpu.server.task_worker import TaskWorkerServer
    from trino_tpu.session import Session

    sql = ("SELECT l_returnflag, l_linestatus, sum(l_quantity), "
           "count(*) FROM lineitem GROUP BY l_returnflag, "
           "l_linestatus ORDER BY l_returnflag, l_linestatus")

    class _DeadHandler(BaseHTTPRequestHandler):
        def log_message(self, *a):
            pass

        def do_POST(self):
            n = int(self.headers.get("Content-Length", 0))
            self.rfile.read(n)
            body = b'{"taskId": "x", "state": "RUNNING"}'
            self.send_response(200)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def do_GET(self):
            self.send_error(500, "injected worker failure")

        def do_DELETE(self):
            self.send_response(204)
            self.send_header("Content-Length", "0")
            self.end_headers()

    # 3 workers in BOTH runs so nparts (and the per-worker split
    # share) is identical — the fault run swaps one good worker for
    # the dead stub, isolating recovery cost from fan-out changes
    workers = [TaskWorkerServer().start() for _ in range(3)]
    dead = ThreadingHTTPServer(("127.0.0.1", 0), _DeadHandler)
    threading.Thread(target=dead.serve_forever, daemon=True).start()
    dead_uri = f"http://127.0.0.1:{dead.server_address[1]}"

    def make_session():
        s = Session(catalog="tpch", schema="tiny")
        s.set("retry_policy", "TASK")
        s.set("retry_initial_delay_ms", 10)
        return s

    def best_of(uris):
        # collect_node_stats so workers report peakMemoryBytes and the
        # per-query gauge this leg advertises carries a real value
        r = DistributedHostQueryRunner(uris, session=make_session(),
                                       collect_node_stats=True)
        return _cold_warm(lambda: r.execute(sql), iters)

    try:
        good = [w.base_uri for w in workers]
        cold_ok, t_ok = best_of(good)
        _, t_fault = best_of([dead_uri] + good[:2])
    finally:
        dead.shutdown()
        for w in workers:
            w.stop()
    try:
        failover = _fault_failover_subleg()
    except Exception:           # noqa: BLE001 — the mini-leg is a
        failover = {}           # ride-along, never the leg's verdict
    return dict({
        "overhead": max(t_fault / t_ok - 1.0, 0.0),
        "task_retries_total":
            METRICS.counter("trino_tpu_task_retries_total").value(),
        "query_peak_memory_bytes":
            METRICS.gauge("trino_tpu_query_peak_memory_bytes").value(),
    }, **failover, **_cw_keys(cold_ok, t_ok))


def _mpp_ici_subleg(sql: str, nrows: int) -> dict:
    """ICI-native exchange mini-leg: the SAME stage DAG, executed on a
    4-virtual-device mesh with the hash repartition lowered to
    jax.lax.all_to_all (parallel/spmd.py) instead of spool+HTTP frames.
    Runs in a grandchild process because the virtual-device XLA flag
    must be set before jax imports (and must not perturb the other
    legs' single-device baseline)."""
    code = (
        "import json, os, time\n"
        "from trino_tpu.runner import LocalQueryRunner\n"
        "from trino_tpu.obs.metrics import METRICS\n"
        "sql = os.environ['BENCH_MPP_SQL']\n"
        "r = LocalQueryRunner(distributed=True, n_devices=4)\n"
        "r.execute(sql)\n"
        "b = METRICS.counter('trino_tpu_mesh_exchange_bytes_total')\n"
        "b0 = sum(v for _, v in b.samples())\n"
        "t0 = time.perf_counter(); r.execute(sql)\n"
        "wall = time.perf_counter() - t0\n"
        "moved = sum(v for _, v in b.samples()) - b0\n"
        "print(json.dumps({'wall_s': wall, 'ici_bytes': moved}))\n")
    env = dict(os.environ)
    env["PYTHONPATH"] = ""
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=4"
                        ).strip()
    # ONE timed iteration after the warm-up: the mesh path re-traces
    # its shard_map programs per query (known spmd cost), so extra
    # iterations buy accuracy at ~1 re-compile each — the CPU probe's
    # budget is better spent on the worker legs
    env["BENCH_MPP_SQL"] = sql
    budget = min(max(_remaining() * 0.5, 30.0), 150.0)
    try:
        p = subprocess.run([sys.executable, "-c", code],
                           capture_output=True, text=True,
                           timeout=budget, env=env)
        d = json.loads((p.stdout or "").strip().splitlines()[-1])
        return {"ici_rows_per_sec": nrows / max(d["wall_s"], 1e-9),
                "exchange_ici_bytes": float(d["ici_bytes"])}
    except Exception as e:      # noqa: BLE001 — the split stays a
        # reported 0, never a lost worker-leg result
        return {"exchange_ici_bytes": 0.0,
                "ici_error": f"{type(e).__name__}: {e}"[:160]}


def _leg_mpp(iters: int) -> dict:
    """Multi-stage MPP leg: a distributed hash-join + final-aggregation
    query through the stage-DAG scheduler (trino_tpu/stage/) — joins
    and the final aggregation run ON the workers over the partitioned
    worker-to-worker exchange — at 1 vs 3 in-process workers, with the
    per-stage-barrier vs eager-pipelining A/B (stage_pipelining) and
    the ICI-vs-spool exchange byte split. Reports rows/s (lineitem
    rows / best wall), the pipelining overlap ratio, and the exchange
    bytes each medium moved, so worker-side execution is a tracked
    metric next to cpu_engine_rows_per_sec."""
    from trino_tpu.exec.remote import DistributedHostQueryRunner
    from trino_tpu.obs.metrics import METRICS
    from trino_tpu.runner import LocalQueryRunner
    from trino_tpu.server.task_worker import TaskWorkerServer
    from trino_tpu.session import Session

    sql = ("SELECT o_orderpriority, count(*), sum(l_extendedprice) "
           "FROM lineitem JOIN orders ON l_orderkey = o_orderkey "
           "GROUP BY o_orderpriority ORDER BY o_orderpriority")
    nrows = int(LocalQueryRunner(
        session=Session(catalog="tpch", schema="tiny")).execute(
            "SELECT count(*) FROM lineitem").rows[0][0])

    def make_session(pipelining: bool = True):
        s = Session(catalog="tpch", schema="tiny")
        s.set("multistage_execution", True)
        s.set("stage_pipelining", pipelining)
        return s

    def ex_bytes_written():
        # producer side only: "read" re-counts the same frames on the
        # consumer side, and summing both would double-report the
        # shuffle volume
        return METRICS.counter(
            "trino_tpu_exchange_partition_bytes_total").value(
                direction="written")

    nruns = max(iters, 1) + 1       # warm-up + timed iterations

    def best_of(uris, pipelining: bool = True):
        r = DistributedHostQueryRunner(
            uris, session=make_session(pipelining))
        return _cold_warm(lambda: r.execute(sql), iters)

    workers = [TaskWorkerServer().start() for _ in range(3)]
    try:
        uris = [w.base_uri for w in workers]
        _, t_one = best_of(uris[:1])
        # the A/B: identical DAG, identical fleet — only the barrier
        # differs (stage_pipelining=false is the pre-PR-13 behavior)
        _, t_barrier = best_of(uris, pipelining=False)
        b0 = ex_bytes_written()
        cold_all, t_all = best_of(uris, pipelining=True)
        # identical runs: the per-query shuffle volume is the written
        # delta divided by how many times the query executed
        moved = (ex_bytes_written() - b0) / nruns
        overlap = METRICS.gauge(
            "trino_tpu_mpp_pipeline_overlap_ratio").value()
    finally:
        for w in workers:
            w.stop()
    return dict({
        "rows_per_sec": nrows / t_all,
        "rows_per_sec_1_worker": nrows / t_one,
        "rows_per_sec_barrier": nrows / t_barrier,
        "speedup_vs_1_worker": t_one / t_all,
        "pipelined_speedup_vs_barrier": t_barrier / t_all,
        "pipeline_overlap_ratio": overlap,
        "exchange_bytes": moved,
        "exchange_spool_bytes": moved,
    }, **_mpp_ici_subleg(sql, nrows), **_cw_keys(cold_all, t_all))


def _leg_load(duration_s: float, clients: int) -> dict:
    """Closed-loop concurrency leg (ROADMAP item 2's tracked metric):
    K concurrent protocol clients hammer one coordinator for a fixed
    duration against a concurrency-capped resource group, so queries
    queue, drain fair, and occasionally bounce off the full queue.
    Reports QPS, p50/p95/p99 query wall (from the PR 4 histogram,
    delta-snapshotted around the run), average queued time, and the
    governance counters (rejections, memory kills) — overload behavior
    as a number, like rows/s."""
    import threading

    import trino_tpu  # noqa: F401
    from trino_tpu.client import ClientError, StatementClient
    from trino_tpu.obs.metrics import (MEMORY_KILLS, QUEUE_REJECTIONS,
                                       QUERY_QUEUED_SECONDS,
                                       QUERY_WALL_SECONDS)
    from trino_tpu.server.coordinator import Coordinator
    from trino_tpu.server.resourcegroups import (ResourceGroup,
                                                 ResourceGroupManager)

    mgr = ResourceGroupManager()
    grp = mgr.root.add(ResourceGroup(
        "bench", hard_concurrency=2,
        # smaller than the client count minus the running slots, so
        # the burst occasionally trips QUERY_QUEUE_FULL — the
        # rejection path is part of what this leg measures
        max_queued=max(2, clients // 3)))
    mgr.add_selector(grp)
    co = Coordinator(resource_groups=mgr,
                     memory_pool_bytes=4 << 30).start()
    sql = "SELECT count(*) FROM tpch.tiny.region"
    # warm the engine — and split the warm-up into the leg's own
    # compile/warm scoreboard keys while at it
    warm_client = StatementClient(co.base_uri)
    cold_s, warm_s = _cold_warm(lambda: warm_client.execute(sql), 1)
    wall0, n0, _ = QUERY_WALL_SECONDS.snapshot()
    q0, qn0, qs0 = QUERY_QUEUED_SECONDS.snapshot()
    rej0 = QUEUE_REJECTIONS.value()
    kills0 = MEMORY_KILLS.value()
    completed = [0] * clients
    rejected = [0] * clients
    stop_at = time.monotonic() + duration_s

    def run(i: int):
        c = StatementClient(co.base_uri)
        while time.monotonic() < stop_at:
            try:
                c.execute(sql)
                completed[i] += 1
            except ClientError as e:
                if "QUERY_QUEUE_FULL" in str(e):
                    rejected[i] += 1
                    time.sleep(0.02)    # back off like a real client
                else:
                    raise

    threads = [threading.Thread(target=run, args=(i,))
               for i in range(clients)]
    t0 = time.monotonic()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    elapsed = time.monotonic() - t0
    wall1, n1, _ = QUERY_WALL_SECONDS.snapshot()
    _, qn1, qs1 = QUERY_QUEUED_SECONDS.snapshot()
    co.stop()
    deltas = [b - a for a, b in zip(wall0, wall1)]
    n = n1 - n0
    pct = lambda q: QUERY_WALL_SECONDS.quantile_from_deltas(  # noqa: E731
        QUERY_WALL_SECONDS.buckets, deltas, n, q)
    qcount = qn1 - qn0
    return dict(_cw_keys(cold_s, warm_s), **{
        "qps": sum(completed) / max(elapsed, 1e-9),
        "clients": clients,
        "duration_s": round(elapsed, 2),
        "completed": sum(completed),
        "p50_ms": round(pct(0.50) * 1000, 2),
        "p95_ms": round(pct(0.95) * 1000, 2),
        "p99_ms": round(pct(0.99) * 1000, 2),
        "queued_ms_avg": round(
            (qs1 - qs0) / qcount * 1000, 2) if qcount else 0.0,
        "queued_dequeues": qcount,
        "rejections": (QUEUE_REJECTIONS.value() - rej0),
        "memory_kills": (MEMORY_KILLS.value() - kills0),
    })


def _leg_load_mixed(duration_s: float, clients: int) -> dict:
    """Mixed-size load leg (ISSUE 14 acceptance): K >> runner-threads
    concurrent clients — half small point queries, half large joins —
    against ONE worker whose shared split scheduler (exec/taskexec.py)
    time-slices every query's tasks through 2 runner slots. Reports
    the small queries' p95 vs their ISOLATED latency (the acceptance
    bound: within 3x at K >> runners — without the fair scheduler a
    large query owns the worker and small-query latency balloons) and
    a starvation/fairness metric (min/max completed across the small
    clients; 1.0 = perfectly fair, 0 = a client starved)."""
    import threading

    import trino_tpu  # noqa: F401
    from trino_tpu.client import ClientError, StatementClient
    from trino_tpu.obs.metrics import METRICS
    from trino_tpu.server.coordinator import Coordinator
    from trino_tpu.server.task_worker import TaskWorkerServer

    RUNNERS = 2
    worker = TaskWorkerServer(task_runners=RUNNERS).start()
    # no admission cap: this leg measures WORKER-side fairness, so
    # every client's query must actually reach the worker at once
    co = Coordinator(worker_uris=[worker.base_uri],
                     memory_pool_bytes=4 << 30).start()
    small_sql = "SELECT count(*) FROM tpch.tiny.region"
    # the large shape is scan-heavy (chunkable end to end): forced
    # chunking below turns every chunk into a scheduler yield point
    large_sql = ("SELECT l_returnflag, count(*), "
                 "sum(l_extendedprice * (1 - l_discount)), "
                 "avg(l_quantity) FROM tpch.tiny.lineitem "
                 "WHERE l_shipdate <= DATE '1998-09-02' "
                 "GROUP BY l_returnflag ORDER BY l_returnflag")
    warm_client = StatementClient(co.base_uri)
    cold_s, warm_s = _cold_warm(
        lambda: (warm_client.execute(small_sql),
                 warm_client.execute(large_sql)), 1)
    # isolated small-query latency (warm, no contention): the
    # denominator of the acceptance ratio
    iso = []
    for _ in range(5):
        t0 = time.monotonic()
        warm_client.execute(small_sql)
        iso.append(time.monotonic() - t0)
    iso_p50 = sorted(iso)[len(iso) // 2]
    n_small = max(clients // 2, 1)
    lats: list = [[] for _ in range(clients)]
    completed = [0] * clients
    yields0 = METRICS.counter(
        "trino_tpu_task_scheduler_yields_total").value()
    stop_at = time.monotonic() + duration_s

    errors = [0] * clients

    def run(i: int):
        # large clients force chunked execution (stream_chunk_rows):
        # every chunk is a scheduler yield point, so a large query
        # cannot own a runner slot for a whole operator — the quanta
        # the small queries' latency bound depends on
        props = ({} if i < n_small
                 else {"stream_chunk_rows": "4096"})
        props["retry_policy"] = "TASK"
        c = StatementClient(co.base_uri, session_properties=props)
        sql = small_sql if i < n_small else large_sql
        while time.monotonic() < stop_at:
            t0 = time.monotonic()
            try:
                c.execute(sql)
            except ClientError:
                # transient under churn (connection resets on the
                # threaded HTTP stack): counted, never a dead client
                errors[i] += 1
                continue
            lats[i].append(time.monotonic() - t0)
            completed[i] += 1

    threads = [threading.Thread(target=run, args=(i,))
               for i in range(clients)]
    t0 = time.monotonic()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    elapsed = time.monotonic() - t0
    co.stop()
    worker.stop()
    small_lats = sorted(x for i in range(n_small) for x in lats[i])
    large_lats = sorted(x for i in range(n_small, clients)
                        for x in lats[i])

    def pct(sorted_xs, q):
        if not sorted_xs:
            return 0.0
        return sorted_xs[min(int(q * len(sorted_xs)),
                             len(sorted_xs) - 1)]

    small_counts = completed[:n_small]
    fairness = (min(small_counts) / max(small_counts)
                if max(small_counts) else 0.0)
    p95 = pct(small_lats, 0.95)
    return dict(_cw_keys(cold_s, warm_s), **{
        "mixed_qps": sum(completed) / max(elapsed, 1e-9),
        "clients": clients,
        "runner_threads": RUNNERS,
        "duration_s": round(elapsed, 2),
        "small_completed": sum(small_counts),
        "large_completed": sum(completed[n_small:]),
        "small_p50_ms": round(pct(small_lats, 0.50) * 1000, 2),
        "small_p95_ms": round(p95 * 1000, 2),
        "large_p95_ms": round(pct(large_lats, 0.95) * 1000, 2),
        "isolated_small_p50_ms": round(iso_p50 * 1000, 2),
        # the acceptance ratio: <= 3.0 means small queries held their
        # latency next to the large ones at K >> runner threads
        "small_p95_vs_isolated": round(p95 / max(iso_p50, 1e-9), 2),
        "fairness_min_over_max": round(fairness, 3),
        "client_errors": sum(errors),
        "scheduler_yields": METRICS.counter(
            "trino_tpu_task_scheduler_yields_total").value() - yields0,
    })


def _leg_storm(duration_s: float, clients: int) -> dict:
    """Point-query-storm leg (ISSUE 18): K concurrent protocol clients
    replay Zipf-distributed point lookups against ONE coordinator —
    the dashboard-storm shape the ragged batch executor
    (exec/taskexec.py RaggedBatcher + executor._try_ragged_chain) and
    the coordinator result cache (exec/resultcache.py) exist to serve.
    Phase A runs with both OFF (every query dispatches and executes
    alone); phase B turns on ragged_batching + result_cache_enabled —
    same clients, same Zipf stream, same duration. Reports each
    phase's client-observed p99, phase B's queries-per-compile
    (completed / structural jit-cache misses — > 1 means co-batched
    or cached queries shared a compiled program), and the
    result-cache hit ratio the Zipf head drove."""
    import threading

    import trino_tpu  # noqa: F401
    from trino_tpu.client import ClientError, StatementClient
    # the real metric objects, not name lookups: resultcache/taskexec
    # register these families with labels on first import — a bare
    # METRICS.counter(name) here would register an unlabeled twin
    from trino_tpu.exec.resultcache import RESULT_CACHE_LOOKUPS as rc
    from trino_tpu.exec.taskexec import (RAGGED_BATCHES as rb,
                                         RAGGED_QUERIES as rq)
    from trino_tpu.obs.metrics import JIT_CACHE_LOOKUPS as jit
    from trino_tpu.server.coordinator import Coordinator

    KEYS = 256          # distinct point lookups under the Zipf tail

    def sql_for(k: int) -> str:
        return ("SELECT c_name FROM tpch.tiny.customer "
                f"WHERE c_custkey = {k}")

    def jit_misses() -> float:
        # every cache family (chain/stream/masked/ragged) counts: a
        # compile is a compile wherever it lands
        return sum(v for k, v in jit.samples() if k and k[-1] == "miss")

    # both phases ride the canonical-key structural path — only the
    # batching/cache session properties differ between A and B
    prev = os.environ.get("TRINO_TPU_FRAGMENT_JIT")
    os.environ["TRINO_TPU_FRAGMENT_JIT"] = "1"
    co = Coordinator(memory_pool_bytes=4 << 30).start()
    try:
        # warm-up: generate tiny tables + pay the parse/plan caches,
        # split into the leg's compile/warm scoreboard keys
        warm_client = StatementClient(co.base_uri)
        cold_s, warm_s = _cold_warm(
            lambda: warm_client.execute(sql_for(KEYS + 1)), 1)

        def phase(props):
            lats: list = []
            lock = threading.Lock()
            errors = [0]
            stop_at = time.monotonic() + duration_s

            def run(i: int):
                c = StatementClient(co.base_uri,
                                    session_properties=props)
                rng = np.random.default_rng(1000 + i)
                mine = []
                while time.monotonic() < stop_at:
                    k = min(int(rng.zipf(1.3)), KEYS)
                    t0 = time.monotonic()
                    try:
                        c.execute(sql_for(k))
                    except (ClientError, OSError):
                        # transient under churn (admission bounce or a
                        # connection reset on the threaded HTTP
                        # stack): counted, never a dead client
                        errors[0] += 1
                        continue
                    mine.append(time.monotonic() - t0)
                with lock:
                    lats.extend(mine)

            threads = [threading.Thread(target=run, args=(i,))
                       for i in range(clients)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            return sorted(lats), errors[0]

        def pct(sorted_xs, q):
            if not sorted_xs:
                return 0.0
            return sorted_xs[min(int(q * len(sorted_xs)),
                                 len(sorted_xs) - 1)]

        a_lats, a_errs = phase({})
        m0, h0, l0 = (jit_misses(), rc.value(result="hit"),
                      sum(v for _, v in rc.samples()))
        q0, b0 = rq.value(), rb.value()
        b_lats, b_errs = phase({"ragged_batching": "true",
                                "result_cache_enabled": "true"})
        dm = jit_misses() - m0
        dl = sum(v for _, v in rc.samples()) - l0
        hits = rc.value(result="hit") - h0
    finally:
        co.stop()
        if prev is None:
            os.environ.pop("TRINO_TPU_FRAGMENT_JIT", None)
        else:
            os.environ["TRINO_TPU_FRAGMENT_JIT"] = prev
    return dict(_cw_keys(cold_s, warm_s), **{
        "clients": clients,
        "duration_s": round(duration_s, 2),
        "storm_completed": len(a_lats),
        "storm_batched_completed": len(b_lats),
        "storm_p99_ms": round(pct(a_lats, 0.99) * 1000, 2),
        "storm_batched_p99_ms": round(pct(b_lats, 0.99) * 1000, 2),
        "storm_p50_ms": round(pct(a_lats, 0.50) * 1000, 2),
        "storm_batched_p50_ms": round(pct(b_lats, 0.50) * 1000, 2),
        # phase B completions per structural compile: > 1 means the
        # storm amortized compiles across queries (ragged batches
        # sharing one program + result-cache hits compiling nothing)
        "storm_queries_per_compile": round(
            len(b_lats) / max(dm, 1.0), 2),
        "result_cache_hit_ratio": round(hits / dl, 4) if dl else 0.0,
        "ragged_queries": rq.value() - q0,
        "ragged_batches": rb.value() - b0,
        "client_errors": a_errs + b_errs,
    })


def _leg_streaming(duration_s: float) -> dict:
    """Streaming ingest throughput leg (ISSUE 20): a producer streams
    newline-delimited JSON batches into POST /v1/ingest/{topic} for a
    fixed duration while a continuous ``insert`` job drains the topic
    into a sink table on a poll cadence. Headline is
    ``ingest_rows_per_sec`` (producer-observed append throughput
    through the HTTP route, segment-file durability included);
    ride-alongs are the drain side — rows the continuous job moved
    per second, cycles it took, and the end-to-end lag from last
    ingest to fully-drained sink."""
    import json as _json
    import tempfile
    import urllib.request

    import trino_tpu  # noqa: F401
    from trino_tpu.client import StatementClient
    from trino_tpu.config import CONFIG as _CFG
    from trino_tpu.server.coordinator import Coordinator

    _CFG.stream_dir = tempfile.mkdtemp(prefix="bench_stream_")
    BATCH = 200                       # rows per producer POST

    def _post(uri, body=b"", method="POST"):
        req = urllib.request.Request(uri, data=body or None,
                                     method=method)
        with urllib.request.urlopen(req, timeout=30) as resp:
            return _json.load(resp)

    def _batch(base: int) -> bytes:
        return b"\n".join(
            _json.dumps({"k": (base + i) % 16, "v": float(base + i),
                         "ts": float(base + i)}).encode()
            for i in range(BATCH))

    co = Coordinator().start()
    try:
        c = StatementClient(co.base_uri)
        c.execute("CREATE TABLE stream.default.bench_events "
                  "(k BIGINT, v DOUBLE, ts DOUBLE)")
        c.execute("CREATE TABLE memory.default.bench_sink "
                  "(k BIGINT, o BIGINT, v DOUBLE)")
        # warm-up round = one ingest POST + the scan the continuous
        # cycles will re-dispatch, split into the leg's compile/warm
        # scoreboard keys
        warm = [0]

        def round_once():
            _post(co.base_uri + "/v1/ingest/bench_events",
                  _batch(warm[0]))
            warm[0] += BATCH
            c.execute("SELECT count(*) "
                      "FROM stream.default.bench_events")

        cold_s, warm_s = _cold_warm(round_once, 2)
        job = _post(co.base_uri + "/v1/continuous", _json.dumps({
            "kind": "insert", "topic": "bench_events",
            "poll_interval_ms": 100,
            "sql": "INSERT INTO memory.default.bench_sink "
                   "SELECT k, _offset, v "
                   "FROM stream.default.bench_events"}).encode())
        # the ingest storm: closed-loop single producer for the
        # duration — every POST durably appends before returning
        produced = warm[0]
        t0 = time.monotonic()
        while time.monotonic() < t0 + duration_s:
            _post(co.base_uri + "/v1/ingest/bench_events",
                  _batch(produced))
            produced += BATCH
        ingest_s = time.monotonic() - t0
        # drain: wait for the continuous job to catch up, then read
        # its scoreboard
        drain_t0 = time.monotonic()
        deadline = drain_t0 + max(duration_s * 10, 30.0)
        sink = 0
        while time.monotonic() < deadline:
            sink = c.execute("SELECT count(*) FROM "
                             "memory.default.bench_sink").rows[0][0]
            if sink >= produced:
                break
            time.sleep(0.1)
        drain_lag_s = time.monotonic() - drain_t0
        info = _post(co.base_uri + "/v1/continuous/" + job["job_id"],
                     method="GET")
        return dict(_cw_keys(cold_s, warm_s), **{
            "ingest_rows_per_sec": (produced - warm[0]) / ingest_s,
            "ingested_rows": produced,
            "drained_rows": sink,
            "drain_rows_per_sec": (
                info["rows_total"] / max(ingest_s + drain_lag_s,
                                         1e-9)),
            "drain_lag_s": round(drain_lag_s, 3),
            "continuous_cycles": info["cycles"],
            "zero_dup_zero_loss": bool(sink == produced),
        })
    finally:
        co.stop()


def _run_probe_body(kind: str):
    """Inside the subprocess: run both legs, print one JSON line per leg
    the moment it completes so a timeout loses only the unfinished leg."""
    if kind == "init":
        # fail-fast device-init probe: backend contact ONLY, no data,
        # no compile — the ≤60s answer to "is there a device at all",
        # kept separate so an init hang can never eat compute budget
        # (round-5 verdict: device init alone ate 360s of 540s)
        import jax
        devs = jax.devices()
        platform = devs[0].platform
        # a silent jax fallback to CPU is NOT a device: passing it
        # through would let the compute leg record CPU throughput as
        # the device engine number (the exact scoreboard corruption
        # the driver-unverified README annotation exists to prevent)
        print(json.dumps({"leg": "init", "ok": platform != "cpu",
                          "platform": platform,
                          "device_count": len(devs)}), flush=True)
        return
    if kind == "cpu":
        import jax
        jax.config.update("jax_platforms", "cpu")
    if kind == "scale":
        sf = os.environ.get("BENCH_Q18_SCHEMA", "sf10")
        legs = [("q18", lambda: _leg_q18(sf))]
    elif kind == "first_compile":
        # the device compile sub-probe: ONLY the warm leg — its cold
        # wall pays the real q1 compile (fresh runners, nothing cached
        # beforehand) and populates the shared persistent XLA cache the
        # steady sub-probe (a separate process) then rides
        legs = [("warm", lambda: _leg_warm("sf1"))]
    elif kind == "steady":
        # steady-state sub-probe: engine/micro/telemetry with the XLA
        # compile already on disk — pays re-trace, never the compile
        legs = [("engine", lambda: _leg_engine("sf1", 2)),
                ("micro", lambda: _leg_micro(1.0, 3)),
                ("telemetry", lambda: _leg_telemetry("sf1", 2))]
    else:
        legs = [("warm", lambda: _leg_warm("sf1")),
                ("engine", lambda: _leg_engine("sf1", 2)),
                ("micro", lambda: _leg_micro(0.1, 2)),
                ("telemetry", lambda: _leg_telemetry("sf1", 2)),
                ("fault", lambda: _leg_fault(2)),
                ("mpp", lambda: _leg_mpp(2)),
                ("load", lambda: _leg_load(6.0, 6)),
                ("load_mixed", lambda: _leg_load_mixed(6.0, 8)),
                ("storm", lambda: _leg_storm(6.0, 64)),
                ("streaming", lambda: _leg_streaming(6.0))]
    for name, fn in legs:
        try:
            # every leg returns a dict carrying (at least) compile_s +
            # warm_speedup next to its headline number — the
            # compile-tax split is a first-class column of every row
            print(json.dumps(dict({"leg": name}, **fn())), flush=True)
        except Exception as e:  # report, keep going to the next leg
            print(json.dumps(
                {"leg": name,
                 "error": f"{type(e).__name__}: {e}"[:300]}), flush=True)


def _probe(kind: str, timeout: float, force_cpu: bool = False,
           extra_env: dict = None):
    """Run a probe subprocess; returns ({leg: rps}, {leg: err}).
    ``force_cpu`` pins a non-cpu probe kind to the CPU backend (the
    scale leg's fallback when no device landed an engine number)."""
    env = dict(os.environ)
    if kind == "cpu" or force_cpu:
        env["PYTHONPATH"] = ""       # the probe runs from the checkout
        env["JAX_PLATFORMS"] = "cpu"
    # every probe compiles against ONE persistent cache dir (placed by
    # trino_tpu/config.py from the inherited environment)
    env["BENCH_PROBE_KIND"] = kind
    if extra_env:
        env.update(extra_env)
    out_text = ""
    err_note = None
    try:
        p = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--probe"],
            capture_output=True, text=True, timeout=max(timeout, 10),
            env=env, cwd=os.path.dirname(os.path.abspath(__file__)))
        out_text = p.stdout or ""
        if p.returncode != 0:
            # a hard crash (PJRT abort/segfault) after some legs printed
            # must still be surfaced — round 3 lost its engine leg to a
            # silent 0.0 exactly here
            tail = (p.stderr or "").strip().splitlines()[-4:]
            err_note = (f"rc={p.returncode}: "
                        + " | ".join(t.strip() for t in tail))[-300:]
    except subprocess.TimeoutExpired as e:
        s = e.stdout   # alias of e.output
        out_text = s.decode() if isinstance(s, bytes) else (s or "")
        err_note = f"probe timed out after {int(timeout)}s"
    vals, errs = {}, {}
    for line in out_text.splitlines():
        line = line.strip()
        if not line.startswith("{"):
            continue
        try:
            d = json.loads(line)
        except json.JSONDecodeError:
            continue
        leg = d.get("leg", "?")
        # compile-tax scoreboard keys ride every leg (acceptance:
        # compile_s + warm_speedup in every leg's JSON) — hoovered
        # into prefixed vals so the final report can surface any of
        # them without per-leg plumbing
        for k in ("compile_s", "warm_speedup", "cold_s", "warm_s"):
            if k in d:
                vals[f"{leg}_{k}"] = d[k]
        if leg == "warm" and "warm_speedup" in d:
            vals["warm"] = d["warm_speedup"]
        if d.get("leg") == "init":
            if d.get("ok"):
                vals["init"] = d
            else:
                # keep the diagnostic: "not ok" here means the probe
                # RAN and found no device (e.g. silent jax CPU
                # fallback) — the scoreboard must say that, not the
                # generic "leg did not complete" hang message
                errs["init"] = ("no accelerator: platform="
                                f"{d.get('platform')} x"
                                f"{d.get('device_count')}")
        elif leg == "load_mixed" and "mixed_qps" in d:
            # mixed-size load ride-alongs: worker-side fairness
            vals["load_mixed"] = d["mixed_qps"]
            for k in ("small_p50_ms", "small_p95_ms", "large_p95_ms",
                      "isolated_small_p50_ms", "small_p95_vs_isolated",
                      "fairness_min_over_max", "small_completed",
                      "large_completed", "scheduler_yields"):
                if k in d:
                    vals[f"load_mixed_{k}"] = d[k]
        elif leg == "storm" and "storm_p99_ms" in d:
            # point-query-storm ride-alongs: the ragged-batch +
            # result-cache scoreboard (ISSUE 18 acceptance keys)
            vals["storm"] = d["storm_p99_ms"]
            for k in ("storm_p99_ms", "storm_batched_p99_ms",
                      "storm_p50_ms", "storm_batched_p50_ms",
                      "storm_queries_per_compile",
                      "result_cache_hit_ratio", "storm_completed",
                      "storm_batched_completed", "ragged_queries",
                      "ragged_batches"):
                if k in d:
                    vals[f"storm_{k}" if not k.startswith("storm")
                         else k] = d[k]
        elif leg == "streaming" and "ingest_rows_per_sec" in d:
            # streaming ingest leg (ISSUE 20): the producer-side
            # append throughput is the headline; the continuous
            # job's drain side rides along
            vals["streaming"] = d["ingest_rows_per_sec"]
            for k in ("ingest_rows_per_sec", "drain_rows_per_sec",
                      "drain_lag_s", "continuous_cycles",
                      "ingested_rows", "drained_rows",
                      "zero_dup_zero_loss"):
                if k in d:
                    vals[f"streaming_{k}"] = d[k]
        elif "qps" in d:
            # load leg ride-alongs: the concurrency scoreboard
            vals["load"] = d["qps"]
            for k in ("p50_ms", "p95_ms", "p99_ms", "queued_ms_avg",
                      "rejections", "memory_kills", "completed"):
                if k in d:
                    vals[f"load_{k}"] = d[k]
        elif "rows_per_sec" in d:
            vals[d.get("leg", "?")] = d["rows_per_sec"]
            # streamed-execution ride-alongs (the q18 scale leg):
            # chunk count, overlap ratio, transfer volume, budget
            for k in ("stream_chunks", "stream_overlap_ratio",
                      "stream_h2d_bytes", "budget_bytes",
                      "datagen_s"):
                if k in d:
                    vals[f"{leg}_{k}"] = d[k]
            # mpp leg ride-alongs: worker-side execution artifacts,
            # the barrier-vs-pipelined A/B, and the ICI/spool split
            if "speedup_vs_1_worker" in d:
                vals["mpp_speedup"] = d["speedup_vs_1_worker"]
            if "exchange_bytes" in d:
                vals["mpp_exchange_bytes"] = d["exchange_bytes"]
            if "rows_per_sec_1_worker" in d:
                vals["mpp_1_worker"] = d["rows_per_sec_1_worker"]
            for k in ("rows_per_sec_barrier",
                      "pipelined_speedup_vs_barrier",
                      "pipeline_overlap_ratio",
                      "exchange_spool_bytes", "exchange_ici_bytes",
                      "ici_rows_per_sec"):
                if k in d:
                    vals[f"mpp_{k}"] = d[k]
        elif "overhead" in d:
            vals[d.get("leg", "?")] = d["overhead"]
            # fault leg ride-alongs: scrape-side FTE artifacts
            if "task_retries_total" in d:
                vals["task_retries"] = d["task_retries_total"]
            if "query_peak_memory_bytes" in d:
                vals["peak_memory_bytes"] = d["query_peak_memory_bytes"]
            # fault leg ride-alongs: coordinator-failover resume
            for k in ("coordinator_failover_resume_s",
                      "failover_parts_resumed",
                      "failover_parts_replayed"):
                if k in d:
                    vals[k] = d[k]
            # telemetry leg ride-along: OTLP documents the file sink
            # actually accepted during the telemetry-on runs
            if "otlp_exports" in d:
                vals["telemetry_otlp_exports"] = d["otlp_exports"]
        elif "error" in d:
            errs[d.get("leg", "?")] = d["error"]
    if err_note:
        errs.setdefault("probe", err_note)
    expected = ("init",) if kind == "init" else \
        ("q18",) if kind == "scale" else \
        ("warm",) if kind == "first_compile" else \
        ("engine", "micro", "telemetry") if kind == "steady" else \
        ("engine", "warm", "micro", "telemetry",
         "fault", "mpp", "load", "load_mixed", "storm")
    for leg in expected:              # a 0.0 must never be unexplained
        if leg not in vals and leg not in errs:
            errs[leg] = "leg did not complete"
    return vals, errs


def main():
    if "--probe" in sys.argv:
        _run_probe_body(os.environ.get("BENCH_PROBE_KIND", "device"))
        return

    # Last-ditch net: whatever goes wrong below, print the JSON line.
    state = {"printed": False, "report": None}

    def _emit(report):
        if not state["printed"]:
            state["printed"] = True
            print(json.dumps(report), flush=True)

    def _alarm(signum, frame):
        _emit(state["report"] or {
            "metric": "tpch_q1_sf1_engine_rows_per_sec", "value": 0.0,
            "unit": "rows/s", "vs_baseline": 0.0,
            "error": "bench harness overran its own budget"})
        os._exit(0)

    signal.signal(signal.SIGALRM, _alarm)
    signal.alarm(int(BUDGET) + 20)

    # --- sub-probe machinery: every probe is its own subprocess under
    # its OWN cap, checkpointed the moment it lands cleanly. One
    # sub-probe blowing its cap zeroes only its own keys (the r04/r05
    # failure mode — one device hang zeroing every device number — is
    # structurally impossible), and a rerun of the round resumes past
    # whatever already landed.
    forced_blowouts = {s.strip() for s in os.environ.get(
        "BENCH_FORCE_SUBPROBE_TIMEOUT", "").split(",") if s.strip()}
    ckpt = _ckpt_load()
    subtimes = {}

    def _subprobe(name: str, kind: str, cap: float,
                  force_cpu: bool = False, extra_env: dict = None):
        """One checkpointed, individually-capped sub-probe. Completed
        sub-probes replay from the checkpoint (status "resumed") —
        only the unfinished remainder of a blown round re-runs."""
        done = ckpt.get(name)
        if done is not None:
            subtimes[name] = {
                "status": "resumed", "cap_s": round(cap, 1),
                "elapsed_s": done.get("elapsed_s", 0.0)}
            return dict(done.get("vals", {})), dict(done.get("errs", {}))
        # the blowout drill: the named sub-probe gets a ~1s cap, times
        # out, and the artifact must still carry every OTHER number
        cap_eff = 1.0 if name in forced_blowouts else cap
        t0 = time.monotonic()
        vals, errs = _probe(kind, cap_eff, force_cpu=force_cpu,
                            extra_env=extra_env)
        elapsed = time.monotonic() - t0
        blowout = any("timed out" in str(v) for v in errs.values())
        subtimes[name] = {
            "status": ("ok" if vals and not errs else
                       "timeout" if blowout else
                       "partial" if vals else "error"),
            "cap_s": round(cap_eff, 1), "elapsed_s": round(elapsed, 1)}
        # partial results checkpoint too: a probe that timed out after
        # landing some legs keeps them on resume — re-burning its full
        # cap to reproduce the same partial is the one thing a blown
        # round cannot afford
        if vals:
            ckpt[name] = {"vals": vals, "errs": errs,
                          "elapsed_s": round(elapsed, 1)}
            _ckpt_save(ckpt)
        return vals, errs

    # --- CPU baseline probe FIRST (round-5 verdict #1: the device
    # probe ate 360s of the 540s budget and the scoreboard lost its
    # only real number) — the engine leg leads inside the probe, so
    # cpu_engine_rows_per_sec lands every round no matter what the
    # device backend does afterwards. Checkpointed like the device
    # sub-probes: a resumed round keeps its baseline for free.
    cpu_vals, cpu_errs = {}, {}
    cpu_budget = min(_remaining() - 90, 210)
    # a checkpointed baseline replays even when this run's budget
    # would not admit a fresh probe — resumed numbers are free
    if cpu_budget > 30 or "cpu_baseline" in ckpt:
        cpu_vals, cpu_errs = _subprobe("cpu_baseline", "cpu",
                                       cpu_budget)
    else:
        cpu_errs["probe"] = "skipped: insufficient budget"

    # --- device side: the init -> first_compile -> steady ladder
    INIT_CAP = float(os.environ.get(
        "BENCH_DEV_INIT_CAP", min(60.0, 0.1 * BUDGET)))
    COMPILE_CAP = float(os.environ.get(
        "BENCH_DEV_COMPILE_CAP", 0.2 * BUDGET))
    STEADY_CAP = float(os.environ.get(
        "BENCH_DEV_STEADY_CAP", 0.2 * BUDGET))
    Q18_CAP = float(os.environ.get(
        "BENCH_DEV_Q18_CAP", 0.3 * BUDGET))

    dev_vals = {}
    sub_errs = {}           # {sub-probe name: cause} — satellite shape
    if _remaining() > 45:
        init_vals, init_errs = _subprobe(
            "device_init", "init", min(INIT_CAP, _remaining() - 20))
        if "init" not in init_vals:
            # no device within the fail-fast window: skip the compute
            # sub-probes entirely instead of feeding them caps to hang in
            sub_errs["device_init"] = json.dumps(init_errs)[:200]
        else:
            if _remaining() > 60:
                cv, ce = _subprobe(
                    "device_first_compile", "first_compile",
                    min(COMPILE_CAP, _remaining() - 45))
                dev_vals.update(cv)
                if ce:
                    sub_errs["device_first_compile"] = \
                        json.dumps(ce)[:200]
            else:
                sub_errs["device_first_compile"] = \
                    "skipped: insufficient budget"
            if _remaining() > 60:
                sv, se = _subprobe(
                    "device_steady", "steady",
                    min(STEADY_CAP, _remaining() - 30))
                dev_vals.update(sv)
                if se:
                    sub_errs["device_steady"] = json.dumps(se)[:200]
            else:
                sub_errs["device_steady"] = \
                    "skipped: insufficient budget"
    else:
        sub_errs["device_init"] = "skipped: insufficient budget"

    # --- scale leg: q18 under a beyond-HBM budget ---------------------
    # (BASELINE configs[3] direction). A device round runs STREAMED
    # q18 at sf100 as its own capped+checkpointed sub-probe; CPU
    # fallback keeps the scaled-down schema with the same scaled-down
    # memory budget — the morsel-streaming path (exec/streamjoin.py)
    # is exercised every round either way. Failure here never harms
    # the primary metric.
    scale_vals, scale_errs = {}, {}
    on_device = bool(dev_vals.get("engine"))
    q18_schema = os.environ.get(
        "BENCH_Q18_SCHEMA",
        os.environ.get("BENCH_Q18_SCHEMA_DEVICE", "sf100")
        if on_device else "sf10")
    if (on_device or cpu_vals.get("engine")) and _remaining() > 120:
        scale_vals, scale_errs = _subprobe(
            "device_q18" if on_device else "cpu_q18", "scale",
            min(Q18_CAP if on_device else 420, _remaining() - 30),
            force_cpu=not on_device,
            extra_env={"BENCH_Q18_SCHEMA": q18_schema})
        if on_device and scale_errs:
            sub_errs["device_q18"] = json.dumps(scale_errs)[:200]
    else:
        scale_errs["q18"] = ("skipped: no engine leg landed"
                             if not (on_device
                                     or cpu_vals.get("engine"))
                             else "skipped: insufficient budget")

    # stamp cause + elapsed/cap onto every failed sub-probe (the
    # "failed device leg must say WHICH phase died and how long it
    # lived" satellite)
    for name, cause in list(sub_errs.items()):
        st = subtimes.get(name)
        if st:
            sub_errs[name] = (f"{cause} (elapsed {st['elapsed_s']}s"
                              f"/cap {st['cap_s']}s)")

    tpu_eng = dev_vals.get("engine")
    tpu_micro = dev_vals.get("micro")
    cpu_eng = cpu_vals.get("engine")
    cpu_micro = cpu_vals.get("micro")
    value = tpu_eng or 0.0
    vs = (value / cpu_eng) if (value and cpu_eng) else 0.0
    report = {
        "metric": "tpch_q1_sf1_engine_rows_per_sec",
        "value": round(value, 1),
        "unit": "rows/s",
        "vs_baseline": round(vs, 2),
        "baseline": "SQL q1 sf1 through the same engine on 1 host CPU "
                    f"worker ({round(cpu_eng, 1) if cpu_eng else 'n/a'} "
                    "rows/s); north star >=5x (BASELINE.json)",
        # first-class every round (round-5 verdict #1): the CPU engine
        # number is the one metric five rounds have actually produced —
        # it must never again live only inside the baseline string
        "cpu_engine_rows_per_sec": round(cpu_eng or 0.0, 1),
        "micro_rows_per_sec": round(tpu_micro or 0.0, 1),
        # cpu micro ran on a 10% sample: rows/sec normalizes per-row, so
        # the ratio divides the rates directly
        "micro_vs_cpu": (round(tpu_micro / cpu_micro, 2)
                         if tpu_micro and cpu_micro else 0.0),
        # compile-amortization scoreboard (ROADMAP item 1): compile
        # wall split out of the engine leg, and the explicit
        # cold-vs-warm leg's speedup (same q1 through two fresh
        # runners — what the second run gains once the compile tax is
        # paid). Device preferred, CPU fallback: these keys are
        # PARTIAL-SAFE — the CPU probe runs first, so a dying device
        # leg can no longer produce an all-zero artifact.
        # sourced from the WARM leg, not the engine leg: warm runs
        # first and genuinely pays the q1 compile; the engine leg's
        # cold run then rides the process-wide caches the warm leg
        # populated, so its compile_s is structurally ~0
        "compile_s": round(
            dev_vals.get("warm_compile_s",
                         cpu_vals.get("warm_compile_s", 0.0))
            or 0.0, 4),
        "warm_speedup": round(
            dev_vals.get("warm_warm_speedup",
                         cpu_vals.get("warm_warm_speedup", 0.0))
            or 0.0, 2),
        "cold_s": round(
            dev_vals.get("warm_cold_s",
                         cpu_vals.get("warm_cold_s", 0.0)) or 0.0, 4),
        "warm_s": round(
            dev_vals.get("warm_warm_s",
                         cpu_vals.get("warm_warm_s", 0.0)) or 0.0, 4),
        # per-sub-probe scoreboard (round-5 postmortem: WHICH device
        # phase died, how long it lived, under what cap — first-class
        # keys, never only inside the errors blob)
        "device_init_s": round(
            subtimes.get("device_init", {}).get("elapsed_s", 0.0), 1),
        "device_first_compile_s": round(
            subtimes.get("device_first_compile", {})
            .get("elapsed_s", 0.0), 1),
        "device_steady_s": round(
            subtimes.get("device_steady", {}).get("elapsed_s", 0.0), 1),
        "device_subprobes": json.dumps(subtimes)[:500],
        # observability-regression tripwire: q1 on the DEFAULT
        # distributed MPP path with the full telemetry stack
        # (tracing + device/CPU attribution + OTLP export) on vs off;
        # device preferred, CPU fallback — target < 0.05
        # (tests/test_observability.py)
        "telemetry_overhead": round(
            dev_vals.get("telemetry",
                         cpu_vals.get("telemetry", 0.0)) or 0.0, 4),
        "telemetry_otlp_exports": int(
            dev_vals.get("telemetry_otlp_exports",
                         cpu_vals.get("telemetry_otlp_exports", 0))
            or 0),
        # fault-tolerant execution (trino_tpu/fte/): fractional
        # slowdown of the same distributed query with one injected
        # worker failure under retry_policy=TASK, plus the scrape-side
        # artifacts the leg drove (task retries, peak-memory gauge)
        "fault_recovery_overhead": round(
            cpu_vals.get("fault", 0.0) or 0.0, 4),
        "fault_task_retries": round(
            cpu_vals.get("task_retries", 0.0) or 0.0, 1),
        "query_peak_memory_bytes": round(
            cpu_vals.get("peak_memory_bytes", 0.0) or 0.0, 1),
        # mid-flight coordinator failover (fte/faultpoints.py +
        # recovery.py ExecutionManifestStore): seconds from coordinator
        # death — injected at coordinator.post_stage_commit after the
        # first stage commits — to the SAME query FINISHED on a
        # replacement coordinator, and how many stage partitions were
        # read off the spool (resumed) vs re-dispatched (replayed)
        "coordinator_failover_resume_s": round(
            cpu_vals.get("coordinator_failover_resume_s", 0.0)
            or 0.0, 4),
        "failover_partitions_resumed": int(
            cpu_vals.get("failover_parts_resumed", 0.0) or 0),
        "failover_partitions_replayed": int(
            cpu_vals.get("failover_parts_replayed", 0.0) or 0),
        # multi-stage MPP (trino_tpu/stage/): a distributed hash-join +
        # final-aggregation query with joins/aggs executing ON workers
        # (default-on engine since PR 13); rows/s at 3 workers with
        # eager pipelining, the 1-worker and per-stage-barrier ratios,
        # the pipelining overlap ratio, and the exchange byte split —
        # spool/HTTP frames vs ICI device collectives (stage/ici.py)
        "mpp_rows_per_sec": round(cpu_vals.get("mpp", 0.0) or 0.0, 1),
        "mpp_speedup_vs_1_worker": round(
            cpu_vals.get("mpp_speedup", 0.0) or 0.0, 2),
        "mpp_rows_per_sec_barrier": round(
            cpu_vals.get("mpp_rows_per_sec_barrier", 0.0) or 0.0, 1),
        "mpp_pipelined_speedup_vs_barrier": round(
            cpu_vals.get("mpp_pipelined_speedup_vs_barrier", 0.0)
            or 0.0, 3),
        "mpp_pipeline_overlap_ratio": round(
            cpu_vals.get("mpp_pipeline_overlap_ratio", 0.0) or 0.0, 4),
        "mpp_exchange_bytes": round(
            cpu_vals.get("mpp_exchange_bytes", 0.0) or 0.0, 1),
        "exchange_spool_bytes_total": round(
            cpu_vals.get("mpp_exchange_spool_bytes", 0.0) or 0.0, 1),
        "exchange_ici_bytes_total": round(
            cpu_vals.get("mpp_exchange_ici_bytes", 0.0) or 0.0, 1),
        "mpp_ici_rows_per_sec": round(
            cpu_vals.get("mpp_ici_rows_per_sec", 0.0) or 0.0, 1),
        # overload governance (server/resourcegroups.py + memory.py):
        # closed-loop load — K concurrent clients for a fixed duration
        # against a hard_concurrency=2 group. QPS + latency percentiles
        # from the query-wall histogram, average admission queue wait,
        # and the governance counters the run drove (ROADMAP item 2's
        # concurrency metric, tracked like rows/s)
        "load_qps": round(cpu_vals.get("load", 0.0) or 0.0, 2),
        "load_p50_ms": round(cpu_vals.get("load_p50_ms", 0.0) or 0.0, 2),
        "load_p95_ms": round(cpu_vals.get("load_p95_ms", 0.0) or 0.0, 2),
        "load_p99_ms": round(cpu_vals.get("load_p99_ms", 0.0) or 0.0, 2),
        "load_queued_ms_avg": round(
            cpu_vals.get("load_queued_ms_avg", 0.0) or 0.0, 2),
        "load_rejections": round(
            cpu_vals.get("load_rejections", 0.0) or 0.0, 1),
        "load_memory_kills": round(
            cpu_vals.get("load_memory_kills", 0.0) or 0.0, 1),
        # worker-side multi-query runtime (exec/taskexec.py, ISSUE 14):
        # mixed-size closed loop — K=8 clients (half small point
        # queries, half large joins) over ONE worker with 2 runner
        # slots. The acceptance bound is small_p95_vs_isolated <= 3.0
        # (small queries hold their latency at K >> runner threads);
        # fairness is min/max completed across the small clients
        "load_mixed_qps": round(
            cpu_vals.get("load_mixed", 0.0) or 0.0, 2),
        "load_mixed_small_p95_ms": round(
            cpu_vals.get("load_mixed_small_p95_ms", 0.0) or 0.0, 2),
        "load_mixed_small_p95_vs_isolated": round(
            cpu_vals.get("load_mixed_small_p95_vs_isolated", 0.0)
            or 0.0, 2),
        "load_mixed_isolated_small_p50_ms": round(
            cpu_vals.get("load_mixed_isolated_small_p50_ms", 0.0)
            or 0.0, 2),
        "load_mixed_large_p95_ms": round(
            cpu_vals.get("load_mixed_large_p95_ms", 0.0) or 0.0, 2),
        "load_mixed_fairness_min_over_max": round(
            cpu_vals.get("load_mixed_fairness_min_over_max", 0.0)
            or 0.0, 3),
        "load_mixed_scheduler_yields": round(
            cpu_vals.get("load_mixed_scheduler_yields", 0.0) or 0.0, 1),
        # point-query-storm serving (ISSUE 18: exec/taskexec.py
        # RaggedBatcher + exec/resultcache.py): K=64 Zipf clients,
        # phase A per-query dispatch vs phase B ragged batching +
        # coordinator result cache. Acceptance: batched p99 below
        # unbatched p99, queries-per-compile > 1, and a non-zero
        # result-cache hit ratio off the Zipf head
        "storm_p99_ms": round(
            cpu_vals.get("storm_p99_ms", 0.0) or 0.0, 2),
        "storm_batched_p99_ms": round(
            cpu_vals.get("storm_batched_p99_ms", 0.0) or 0.0, 2),
        "storm_queries_per_compile": round(
            cpu_vals.get("storm_queries_per_compile", 0.0) or 0.0, 2),
        "result_cache_hit_ratio": round(
            cpu_vals.get("storm_result_cache_hit_ratio", 0.0)
            or 0.0, 4),
        "storm_ragged_batches": round(
            cpu_vals.get("storm_ragged_batches", 0.0) or 0.0, 1),
        "budget_s": BUDGET,
        "elapsed_s": round(time.monotonic() - _T0, 1),
        # BASELINE configs[3] direction: q18 at scale, now through the
        # chunk-streamed probe join (exec/streamjoin.py): the leg runs
        # under a memory budget smaller than the probe working set and
        # reports the chunk count, the double-buffer overlap ratio,
        # and the h2d volume next to rows/s.
        f"q18_{q18_schema}_rows_per_sec":
            round(scale_vals.get("q18", 0.0), 1),
        "q18_stream_chunks": round(
            scale_vals.get("q18_stream_chunks", 0.0) or 0.0, 1),
        "q18_stream_overlap_ratio": round(
            scale_vals.get("q18_stream_overlap_ratio", 0.0) or 0.0, 4),
        "q18_stream_h2d_bytes": round(
            scale_vals.get("q18_stream_h2d_bytes", 0.0) or 0.0, 1),
        "q18_budget_bytes": round(
            scale_vals.get("q18_budget_bytes", 0.0) or 0.0, 1),
        "q18_datagen_s": round(
            scale_vals.get("q18_datagen_s", 0.0) or 0.0, 2),
        "q18_sf100": (
            round(scale_vals.get("q18", 0.0), 1)
            if q18_schema == "sf100" and scale_vals.get("q18")
            else "sf100 (~600M-row lineitem, ~34GB of q18 lanes) runs "
                 "as the device_q18 sub-probe on device rounds (the "
                 "chunk-streamed probe join bounds the footprint to "
                 "hash table + 2 chunk buffers); CPU-fallback rounds "
                 f"ran BENCH_Q18_SCHEMA={q18_schema} under a scaled-"
                 "down budget instead"),
    }
    # per-sub-probe causes keep their own keys (device_init /
    # device_first_compile / device_steady / device_q18 — each cause
    # stamped with elapsed/cap); cpu+scale keep the old prefixes
    errs = {**sub_errs,
            **{f"cpu_{k}": v for k, v in cpu_errs.items()},
            # device_q18 causes already live in sub_errs under their
            # own key — don't double-report them with a scale_ prefix
            **({} if on_device else
               {f"scale_{k}": v for k, v in scale_errs.items()})}
    if errs:
        report["errors"] = json.dumps(errs)[:800]
    state["report"] = report
    _emit(report)


if __name__ == "__main__":
    main()
