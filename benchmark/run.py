#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json, in ONE process that alone
touches JAX.

    python benchmark/run.py --workload tpch_sf1.power --seed 7 \\
        --seconds 30 --trace 0

Set-up (counted in ``setup_s``): start a coordinator as
``trino_tpu/server/main.py`` does, ask the served tables for the data
pins, warm the cell's own query classes at the mix's own concurrency.
Then the window: the mix's streams send SQL over HTTP through the
program's ``StatementClient`` and wait for the last page. After the
window: every result it returned is compared with the plain reference
(``reference/``), and the last line of standard output is one JSON
object (``correct``, ``attempted``, ``failed``, ``metrics``, ``device``,
with ``--trace 1`` ``breakdown``, and ``checks`` last).

Without a TPU the run exits 2 at once and prints no result. With
``--rehearse`` it walks every phase on the CPU at the configuration's
rehearsal scale, never prints ``"correct": true`` and exits 3. A metric
that BENCHMARK.json gives the cell and whose reader finds nothing to
read ends the run with exit code 4 and no result, in a rehearsal too:
the driver's check would refuse the line that lacks it.

Everything that belongs to one configuration, one mix or one metric is
a file of its own, found by the name in BENCHMARK.json: see README.md.
"""

import time

T_PROCESS = time.time()

import argparse  # noqa: E402
import hashlib  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for p in (HERE, ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

RUN_DIR = os.path.join(HERE, ".run")        # wiped by every run
CACHE_DIR = os.path.join(HERE, ".cache")    # reference answers
EXIT_NOTHING_TO_READ = 4    # not 0 (a result), 2 (no chip), 3 (rehearsal)


def say(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


class Run:
    """What a metric's reader may read."""

    def __init__(self):
        self.cell = self.config = self.mix = self.classes = None
        self.records = []       # harness.stats.Record, the window's
        self.t0 = 0.0           # window start, perf_counter seconds
        self.setup_s = 0.0
        self.phases = {}        # set-up phase -> seconds
        self.jax_setup = {}     # JaxCounters.snapshot() at window start
        self.jax_window = {}    # ... and its growth over the window
        self.gc_window = {}     # GcCounters growth over the window
        self.engine_before = {}  # /metrics samples at window start
        self.engine_after = {}
        self.spans = {}         # query id -> {root span: (start, end) ns}
        self.trace = None       # harness.trace.load(...) or None
        self.trace_window = None
        self.trace_busy_s = None  # device-busy seconds inside it
        self.anchor = None      # (unix ns, trace ns) of one instant
        self.device = {}
        self.peaks = None
        self.memory_peak_bytes = 0
        self.streams = 1


def load_cell(name: str):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json "
                         f"(has: {', '.join(sorted(cells))})")
    cell = cells[name]
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    with open(os.path.join(ROOT, entry["file"])) as f:
        config = json.load(f)
    return bench, cell, config


def metrics_of(bench: dict, cell: str, kind: str):
    """The metrics of ``kind`` (``end_to_end`` / ``per_layer``) that this
    cell reports: those that list it under ``workloads``; without that
    key, an end-to-end metric is every cell's, and a per-layer metric
    belongs to every cell that reports the end-to-end metric it moves."""
    def reports(m):
        return "workloads" not in m or cell in m["workloads"]
    mine = {m["name"] for m in bench["end_to_end"] if reports(m)}
    if kind == "end_to_end":
        return [m for m in bench[kind] if m["name"] in mine]
    return [m for m in bench[kind]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in mine)]


class NothingToRead(Exception):
    """A metric of the cell whose reader returned ``None``."""


def read_metrics(run: Run, specs, package: str, excused: str = "") -> dict:
    """{metric: {value, unit}} from each metric's own reader. A reader
    that finds nothing to read (``None``) raises ``NothingToRead``: the
    cell lists a metric it cannot report. With ``excused`` (why this run
    has nothing for some readers: no chip, no engine) it is named on
    standard error and left out."""
    out = {}
    for m in specs:
        reader = importlib.import_module(f"{package}.{m['name']}")
        value = reader.read(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
            continue
        what = (f"cell {run.cell['name']}: the metric {m['name']} is the "
                f"cell's by BENCHMARK.json and its reader "
                f"{os.path.relpath(reader.__file__, ROOT)} found nothing "
                "to read")
        if not excused:
            raise NothingToRead(
                f"{what}: give the metric a \"workloads\" list without "
                "this cell, or the cell what the reader reads")
        say(f"benchmark: {what} ({excused})")
    return out


def answer_of(answers, cls: str, params):
    """The rows of one (class, parameters) pair; ``params`` None: the
    class as its one fixed text has it."""
    return (answers.answer(cls) if params is None
            else answers.answer(cls, params))


def wanted(pairs):
    """What the reference's ``Answers`` is asked for: a class name, or a
    (class, parameters) pair."""
    return [c if p is None else (c, p) for c, p in pairs]


def reference_answers(config: dict, sf: float, pairs):
    """{(class, params): rows} from the plain reference, kept per
    checkout with one file per (reference source, scale, class,
    parameters); the pairs no file holds yet are answered in ONE pass."""
    module = importlib.import_module(f"reference.{config['reference']}")
    source = hashlib.sha256()
    for name in sorted(os.listdir(os.path.join(HERE, "reference"))):
        if name.endswith(".py"):
            with open(os.path.join(HERE, "reference", name), "rb") as f:
                source.update(f.read())

    def path(cls, params):
        h = source.copy()
        h.update(json.dumps([config["reference"], sf, cls,
                             None if params is None else list(params)
                             ]).encode())
        return os.path.join(CACHE_DIR, f"answers-{h.hexdigest()[:16]}.json")
    out, missing = {}, []
    for key in sorted(set(pairs), key=repr):
        if os.path.exists(path(*key)):
            with open(path(*key)) as f:
                out[key] = json.load(f)
        else:
            missing.append(key)
    if missing:
        answers = module.Answers(sf, wanted(missing))
        os.makedirs(CACHE_DIR, exist_ok=True)
        for key in missing:
            out[key] = answer_of(answers, *key)
            with open(path(*key) + ".tmp", "w") as f:
                json.dump(out[key], f)
            os.replace(path(*key) + ".tmp", path(*key))
    return out


def check_pins(engine, config: dict, sf: float, rehearse: bool):
    """Ask the served tables for their pins. Returns (mismatches, log).

    The wanted pins are the configuration's; a rehearsal runs at another
    scale and takes them from ``pins(sf)`` of the configuration's own
    reference module. An entry with ``"pins": "deployment"`` pins the
    deployment and not data (how many chips the coordinator spans): the
    reference knows nothing of it and the CPU of a rehearsal is not it,
    so a rehearsal asks, says what it found and holds nothing to it."""
    tables = config["tables"]
    want = {t: (d["rows"], d["pin_sum"]) for t, d in tables.items()}
    if rehearse:
        module = importlib.import_module(f"reference.{config['reference']}")
        want.update((t, (p["rows"], p["pin_sum"]))
                    for t, p in module.pins(sf).items())
    bad, log = 0, {}
    for table, spec in tables.items():
        deployment = spec.get("pins") == "deployment"
        try:
            res = engine.client("pins").execute(spec["pin_sql"])
            got = tuple(res.rows[0]) if res.state == "FINISHED" else None
        except Exception as e:   # noqa: BLE001 — the statement failed
            if not deployment:
                raise
            got = f"{type(e).__name__}: {e}"[:300]
        held = not (deployment and rehearse)
        log[table] = {"got": got, "want": want[table], "held": held}
        if got == want[table]:
            continue
        bad += held
        if deployment:
            words = (f"deployment pin {table}: `{spec['pin_sql']}` answered "
                     f"{got}, the configuration wants {want[table]}: ")
            if not held:
                say(words + "not held in a rehearsal, whose CPU is not "
                    "the deployment")
            elif isinstance(got, str):
                raise SystemExit(words + "the program cannot serve this "
                                 "deployment, the run ends here")
            else:
                say(words + "the program does not serve this deployment")
    return bad, log


def compare_window(records, answers):
    """Every finished result of the window against the reference's
    answer for its own class and parameters."""
    from reference.compare import gaps
    mismatches, worst = 0, 0.0
    for r in records:
        if not r.ok:
            continue
        m, rel = gaps(r.rows, answers[r.cls, r.params])
        mismatches += m
        worst = max(worst, rel)
        r.tags["mismatches"], r.tags["rel_err"] = m, rel
    return mismatches, worst


def span_labeler(run: Run):
    """(label, cuts) for ``idle_gaps``, trace ns: a piece of idle time
    is named by the query class in flight and, through the one anchor
    that aligns the clocks, the engine span open then; ``cuts`` are the
    times at which either changes."""
    from harness import trace as tr
    queries = tr.Spans((a, b, cls) for cls, _s, a, b in tr.queries(run.trace))
    shift = run.anchor[1] - run.anchor[0] if run.anchor else None
    engine = tr.Spans((s + shift, e + shift, name)
                      for by_name in run.spans.values()
                      for name, (s, e) in by_name.items()
                      ) if shift is not None else None

    def label(s, e):
        mid = (s + e) // 2
        cls = queries.at(mid)
        if cls is None:
            return "between_queries"
        if run.streams > 1:
            cls = "query"
        if engine is None:
            return f"{cls}/host"
        return f"{cls}/{engine.at(mid) or 'no_engine_span'}"
    return label, queries.edges() + (engine.edges() if engine else [])


def reduce_trace(run: Run, trace_dir: str, anchor_unix: int, dump_to):
    """Load the trace; returns (busy_s, window_s, breakdown)."""
    from harness import trace as tr
    t = time.perf_counter()
    run.trace = tr.load(trace_dir)
    if dump_to:
        with open(dump_to, "w") as f:
            json.dump(run.trace, f)
    anchors = [e for e in run.trace["host"] if e[0] == tr.ANCHOR]
    if anchors:
        run.anchor = (anchor_unix, anchors[0][1])
    lo, hi = run.trace_window = tr.window_of(run.trace)
    run.trace_busy_s, _ = tr.device_busy(run.trace, lo, hi)
    label, cuts = span_labeler(run)
    breakdown = {"device_ops": tr.top_ops(run.trace, lo, hi),
                 "idle_gaps": tr.idle_gaps(run.trace, lo, hi, label,
                                           cuts=cuts)}
    say(f"trace read in {time.perf_counter() - t:.1f} s; planes "
        f"{json.dumps(run.trace['lines'])[:1500]}")
    shutil.rmtree(trace_dir, ignore_errors=True)
    return run.trace_busy_s, (hi - lo) / 1e9, breakdown


def grown(after: dict, before: dict) -> dict:
    return {k: after[k] - before[k] for k in after}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--dump-trace", metavar="FILE",
                    help="with --trace 1: also write the trace's plain "
                         "lists (selfcheck/ keeps a small one) as JSON")
    ap.add_argument("--control", choices=("float32",),
                    help="the output check's control: the reference, "
                         "computed in this precision, answers in the "
                         "program's place; has to come out not correct")
    ap.add_argument("--rehearse", action="store_true",
                    help="walk every phase on the CPU at the rehearsal "
                         "scale; never correct, exits 3")
    args = ap.parse_args(argv)
    bench, cell, config = load_cell(args.workload)

    if args.rehearse:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        os.environ.setdefault("TRINO_TPU_PALLAS", "interpret")
        os.environ.setdefault("TRINO_TPU_DEVICE_GEN", "1")
        os.environ.setdefault("TRINO_TPU_WHOLE_TABLE", "1")
    import jax
    from harness import engine as eng
    from harness import trace as tr
    from harness import traffic
    from harness.counters import GcCounters, JaxCounters
    from harness.roofline import peaks_for

    run = Run()
    run.cell, run.config = cell, config
    run.device = eng.device_info()
    if not args.rehearse:
        if run.device["platform"] != "tpu":
            say(f"benchmark: no TPU (platform {run.device['platform']!r}); "
                "--rehearse walks the phases without one")
            return 2
        if run.device["count"] < int(cell["chips"]):
            say(f"benchmark: the cell needs {cell['chips']} chips, JAX "
                f"has {run.device['count']}")
            return 2
        run.peaks = peaks_for(run.device["kind"])
    run.mix = traffic.load_mix(cell["traffic"])
    run.classes = traffic.classes_of(run.mix, config)
    run.streams = int(run.mix.get("clients", 1))
    # a configuration with "parameters" sends each query its own drawn
    # parameters, substituted into its class's template
    parameters = (importlib.import_module(
        f"reference.{config['parameters']}") if "parameters" in config
        else None)
    if parameters is None:
        sql = {c: traffic.load_sql(c, config) for c in run.classes}
    else:
        sql = {c: traffic.load_sql(c, {"queries_dir":
                                       parameters.TEMPLATES_DIR})
               for c in run.classes}

    def text(cls, params):
        return sql[cls] if params is None else parameters.substitute(
            sql[cls], params)
    schema = config["rehearsal_schema" if args.rehearse else "schema"]
    sf = float(config["rehearsal_scale_factor" if args.rehearse
                      else "scale_factor"])

    # ---- set-up ----------------------------------------------------------
    counters = JaxCounters()
    gc_counters = GcCounters()
    shutil.rmtree(RUN_DIR, ignore_errors=True)
    t = time.perf_counter()
    if args.control:
        module = importlib.import_module(f"reference.{config['reference']}")
        pairs = [(c, p) for c in run.classes
                 for p in (parameters.domain(c) if parameters else [None])]
        answers = module.Answers(sf, wanted(pairs), dtype=args.control)
        engine = eng.ControlEngine({text(c, p): answer_of(answers, c, p)
                                    for c, p in pairs})
    else:
        engine = eng.Engine(config.get("catalog", "tpch"), schema,
                            os.path.join(RUN_DIR, "state"))
    run.phases["start"] = time.perf_counter() - t

    def execute(stream, cls, params=None):
        return engine.client(stream).execute(text(cls, params))

    t = time.perf_counter()
    pin_mismatches, pin_log = (
        (0, "left out: the control serves no table") if args.control
        else check_pins(engine, config, sf, args.rehearse))
    run.phases["pins"] = time.perf_counter() - t
    say("pins", json.dumps(pin_log), json.dumps(counters.snapshot()))

    t = time.perf_counter()
    for cls in run.classes:                 # first execution: compiles
        t1 = time.perf_counter()
        res = execute("warm", cls, None if parameters is None
                      else parameters.validation(cls))
        say(f"first {cls}: {time.perf_counter() - t1:.3f} s {res.state} "
            f"{json.dumps(counters.snapshot())}")
        if res.state != "FINISHED":
            raise SystemExit(f"warm-up of {cls} ended {res.state}")
    run.phases["first_pass"] = time.perf_counter() - t
    t = time.perf_counter()
    warm = traffic.warm_up(run.mix, run.classes, args.seed, execute,
                           parameters)
    run.phases["warm_cycles"] = time.perf_counter() - t
    bad = [r for r in warm if not r.ok]
    if bad:
        raise SystemExit(f"warm-up failed: {bad[0].cls}: {bad[0].error}")

    on_query = None
    trace_dir = os.path.join(RUN_DIR, "trace")
    if args.trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        with jax.profiler.TraceAnnotation(tr.ANCHOR):
            anchor_unix = time.time_ns()

        def on_query(cls, stream):
            return jax.profiler.TraceAnnotation(
                f"{tr.QUERY_PREFIX}{cls}:{stream}")
    run.jax_setup = counters.snapshot()
    gc_counters.longest_s = 0.0
    gc_before = gc_counters.snapshot()
    run.engine_before = engine.counters()
    run.setup_s = time.time() - T_PROCESS

    # ---- the window ------------------------------------------------------
    run.t0, run.records = traffic.run_window(
        run.mix, run.classes, args.seed, args.seconds, execute, on_query,
        parameters)
    t_close = time.perf_counter()

    if args.trace:
        jax.profiler.stop_trace()
    run.memory_peak_bytes = eng.memory_peak_bytes()
    after = counters.snapshot()
    run.jax_window = grown(after, run.jax_setup)
    gc_after = gc_counters.snapshot()
    run.gc_window = dict(grown(gc_after, gc_before),
                         longest_s=gc_after["longest_s"])
    run.engine_after = engine.counters()
    if args.trace:
        for r in run.records:
            if r.ok:
                spans = engine.root_spans(r.query_id)
                if spans:
                    run.spans[r.query_id] = spans
    engine.stop()

    # ---- the output check, once the window has closed ---------------------
    t = time.perf_counter()
    answers = reference_answers(config, sf,
                                [(r.cls, r.params) for r in run.records])
    mismatches, worst = compare_window(run.records, answers)
    reference_s = time.perf_counter() - t
    failed = sum(1 for r in run.records
                 if not r.ok or r.tags.get("mismatches")
                 or r.tags.get("rel_err", 0) > config["limits"]["max_rel_err"])
    values = {"max_rel_err": worst, "exact_mismatches": mismatches,
              "failed_queries": sum(1 for r in run.records if not r.ok),
              "pin_mismatches": pin_mismatches}
    checks = {k: {"value": v, "limit": config["limits"][k]}
              for k, v in values.items()}
    correct = bool(run.records) and all(
        c["value"] <= c["limit"] for c in checks.values())
    for r in run.records:
        if not r.ok:
            say(f"failed {r.cls} stream {r.stream}: {r.error}")

    # ---- metrics ------------------------------------------------------------
    device = dict(run.device, memory_peak_bytes=run.memory_peak_bytes)
    breakdown = None
    # per-layer readers that need the chip or the engine find nothing here
    excused = ("the rehearsal has no chip" if args.rehearse
               else "the control has no spans and no counters"
               if args.control else "")
    try:
        if args.trace:
            device["busy_s"], device["window_s"], breakdown = reduce_trace(
                run, trace_dir, anchor_unix, args.dump_trace)
            metrics = read_metrics(
                run, metrics_of(bench, cell["name"], "per_layer"),
                "layer_metrics", excused)
        else:
            metrics = read_metrics(
                run, metrics_of(bench, cell["name"], "end_to_end"),
                "end_to_end")
    except NothingToRead as e:
        say(f"benchmark: {e}; no result")
        return EXIT_NOTHING_TO_READ

    from harness import stats
    say("queries (class, stream, latency ms, engine root spans ms):")
    for r in sorted(run.records, key=lambda r: r.start_s):
        spans = {n: round((e - b) / 1e6, 2)
                 for n, (b, e) in run.spans.get(r.query_id, {}).items()}
        say(f"  {r.cls} {r.stream} {r.latency_ms:.2f} {json.dumps(spans)}"
            + ("" if r.params is None else f" {json.dumps(r.params)}"))
    param_sets = {} if parameters is None else {"class_param_sets": {
        c: len({r.params for r in run.records if r.cls == c})
        for c in run.classes}}
    say("window", json.dumps({
        "seconds": t_close - run.t0, "phases_s": run.phases,
        "class_mean_ms": stats.class_means_ms(run.records),
        "class_p50_ms": stats.class_p50_ms(run.records),
        "class_count": {c: sum(1 for r in run.records if r.cls == c)
                        for c in run.classes},
        "p95_ms": stats.percentile_ms(run.records, 95),
        "geomean_ms": stats.geomean_ms(run.records),
        "rate_per_s": stats.rate_per_s(run.records, run.t0),
        "gc": run.gc_window,
        "window_compile_requests": run.jax_window["compile_requests"],
        "reference_s": reference_s, **param_sets}))
    checks_pass = correct
    if args.rehearse:
        correct = False
    result = {"correct": correct, "attempted": len(run.records),
              "failed": failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    if args.rehearse:
        result["rehearsal"] = True
        result["rehearsal_checks_pass"] = checks_pass
    if args.control:
        result["control"] = args.control
    result["checks"] = checks
    say("checks " + " ".join(f"{k}={c['value']!r}(limit {c['limit']!r})"
                             for k, c in checks.items()))
    print(json.dumps(result), flush=True)
    return 3 if args.rehearse else 0


if __name__ == "__main__":
    sys.exit(main())
