"""Spans and counters inside the served path (ISSUE 26): a real
``Coordinator`` + ``StatementClient`` over ``tpch.tiny`` on the CPU.

- the four old root spans are still roots with no ``parentSpanId``; the
  new roots exist, are ordered, those of the query thread do not
  overlap, and all of them fit inside the client's latency;
- each phase's ``_count`` at ``/metrics`` grows by one per query
  (``host_read`` and ``dispatch`` by their span counts), and every
  sample line matches the regex the benchmark reads ``/metrics`` with;
- a served query waits on the chip only where its logic reads: no
  ``device_execute``, no per-node fence, ONE ``host_read[node_rows]``
  at the end of ``execute``, and its node row counts are EXPLAIN
  ANALYZE's (ISSUE 38);
- one clock: under a ``jax.profiler`` session the spans are IN the
  profiler's trace as ``tpusql:<name>`` annotations carrying the query
  id, with the span's own duration, and a dispatch's the program of
  the ``jit_<kind>_<key8>`` module that follows it;
- deterministic names: q1, q3, q6 planned and lowered in two fresh
  processes give the same program names and the same HLO text.
"""

import glob
import json
import os
import re
import subprocess
import sys
import time
import urllib.request

import pytest

from trino_tpu.obs.metrics import parse_exposition
from trino_tpu.obs.trace import (EXECUTE_PHASES, PHASES, ROOT_PHASES,
                                 QueryTrace)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
QUERIES = os.path.join(ROOT, "benchmark", "traffic", "queries")
# benchmark/harness/engine.py Engine.counters: the line it keeps
SAMPLE = re.compile(r"^([a-zA-Z_:][^ ]*) ([-+0-9.eE]+|NaN)$")
OLD_ROOTS = ("parse", "plan", "optimize", "execute")
QUERY_THREAD = ("parse", "plan", "optimize", "execute", "fetch",
                "persist", "finish")
CLASSES = ("q1", "q3", "q6")
FAMILY = "trino_tpu_query_phase_seconds"


def sql_of(cls: str) -> str:
    with open(os.path.join(QUERIES, f"{cls}.sql")) as f:
        return f.read()


@pytest.fixture(scope="module")
def coordinator(tmp_path_factory):
    """A coordinator with a result spool (so ``persist`` exists) whose
    executors jit their fragments, as on the chip (so programs are
    dispatched through ``_jit_call``)."""
    from trino_tpu.fte.spool import LocalDirSpool
    from trino_tpu.server import Coordinator
    mp = pytest.MonkeyPatch()
    mp.setenv("TRINO_TPU_FRAGMENT_JIT", "1")
    co = Coordinator(
        spool=LocalDirSpool(str(tmp_path_factory.mktemp("spool"))),
        history_dir=str(tmp_path_factory.mktemp("history"))).start()
    yield co
    co.stop()
    mp.undo()


def client(co):
    from trino_tpu.client import StatementClient
    return StatementClient(co.base_uri, catalog="tpch", schema="tiny")


def served(co, sql):
    """(result, client latency s) once the query thread has finished
    its terminal bookkeeping (``finish`` closes after the client is
    released)."""
    t0 = time.perf_counter()
    res = client(co).execute(sql)
    latency = time.perf_counter() - t0
    assert res.state == "FINISHED", res.error
    trace = co.tracker.get(res.query_id).trace
    deadline = time.time() + 10
    while time.time() < deadline:
        done = [s for s in trace.roots
                if s.name == "finish" and s.end_s is not None]
        if done:
            break
        time.sleep(0.005)
    assert done, "the finish span never closed"
    return res, latency


def otlp_spans(co, query_id):
    with urllib.request.urlopen(
            f"{co.base_uri}/v1/trace/{query_id}") as r:
        doc = json.loads(r.read())
    return [s for rs in doc["resourceSpans"]
            for ss in rs["scopeSpans"] for s in ss["spans"]]


def scrape_text(co) -> str:
    with urllib.request.urlopen(f"{co.base_uri}/metrics") as r:
        return r.read().decode()


def counts(co) -> dict:
    """{phase: _count} plus the two counter families, summed."""
    fams = parse_exposition(scrape_text(co))
    out = {p: fams.get(FAMILY + "_count", {}).get((f"phase={p}",), 0.0)
           for p in PHASES}
    out["reads"] = sum(
        fams.get("trino_tpu_host_reads_total", {}).values())
    out["programs"] = sum(
        fams.get("trino_tpu_device_programs_total", {}).values())
    return out


def settled_counts(co, since: dict, queries: int) -> dict:
    """``counts`` once the spans of the ``queries`` served since the
    scrape ``since`` are all IN them: a span is counted by the hook that
    runs after its end is stamped, and the ``finish`` and last
    ``respond`` spans of a query close after its client was released,
    so a scrape right behind ``served`` can miss them. Settled: every
    one of those queries' ``finish`` is counted and two scrapes 20 ms
    apart agree."""
    deadline = time.time() + 10
    last = None
    while time.time() < deadline:
        now = counts(co)
        if now == last and now["finish"] >= since["finish"] + queries:
            return now
        last = now
        time.sleep(0.02)
    raise AssertionError(f"the counters never settled: {last}")


# ---------------------------------------------------------------------------
# the span tree of a served query
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("cls", ["q6", "q1"])
def test_roots_old_and_new(coordinator, cls):
    res, latency = served(coordinator, sql_of(cls))
    spans = otlp_spans(coordinator, res.query_id)
    roots = [s for s in spans if not s.get("parentSpanId")]
    by_name = {s["name"]: s for s in roots}
    # the benchmark's protocol_ms / plan_ms / execute_ms read these four
    # ROOT spans by name: still there, still parentless, one each
    for name in OLD_ROOTS:
        assert [s["name"] for s in roots].count(name) == 1
        assert "parentSpanId" not in by_name[name]
    assert set(ROOT_PHASES) <= set(by_name), sorted(by_name)
    assert set(by_name) <= set(ROOT_PHASES)

    def start(n):
        return int(by_name[n]["startTimeUnixNano"])

    def end(n):
        return int(by_name[n]["endTimeUnixNano"])

    # ordered: the served life of the query
    order = ["submit", "queued", "parse", "plan", "optimize", "execute",
             "fetch", "persist", "finish"]
    starts = [start(n) for n in order]
    assert starts == sorted(starts), list(zip(order, starts))
    assert end("persist") <= start("respond")
    # the query thread's roots do not overlap
    for a, b in zip(QUERY_THREAD, QUERY_THREAD[1:]):
        assert end(a) <= start(b), (a, b)
    assert end("submit") <= start("queued") <= end("queued") \
        <= start("parse")
    # before the answer: everything but ``finish`` (after the client is
    # released) fits inside the latency the client saw
    before_answer = sum(end(n) - start(n) for n in by_name
                        if n != "finish") / 1e9
    assert 0 < before_answer <= latency
    # under execute: dispatches carry the program's identity, reads
    # their site
    kids = [s for s in spans
            if s.get("parentSpanId") == by_name["execute"]["spanId"]]
    assert kids and {s["name"] for s in kids} <= set(EXECUTE_PHASES)

    def attr(s, key):
        return {a["key"]: a["value"] for a in s["attributes"]}.get(key)

    dispatches = [s for s in spans
                  if s["name"] in ("dispatch", "jit_trace")]
    assert dispatches
    for s in dispatches:
        program = attr(s, "program")["stringValue"]
        assert re.fullmatch(r"[a-z_]+:([0-9a-f]{8}|local|kernel)",
                            program), program
        assert attr(s, "cache") is not None
    reads = [s for s in spans if s["name"] == "host_read"]
    assert reads and all(attr(s, "site") for s in reads)


def test_a_poll_without_data_or_terminal_state_leaves_no_respond_span():
    tr = QueryTrace("q")
    ctx = tr.span("respond", root=True)
    with ctx:
        ctx.dropped = True
    seen = []
    tr.on_close = seen.append
    with tr.span("respond", root=True):
        pass
    assert [s.name for s in tr.roots] == ["respond"]
    assert [s.name for s in seen] == ["respond"]


def test_root_span_from_another_thread_s_stack_is_still_a_root():
    tr = QueryTrace("q")
    with tr.span("execute") as ex:
        with tr.span("respond", root=True) as rs:
            pass
        with tr.span("host_read", site="x") as hr:
            pass
    assert [s.name for s in tr.roots] == ["execute", "respond"]
    assert ex.children == [hr] and rs.children == []
    # a span begun here and ended elsewhere joins no stack
    q = tr.begin("queued")
    assert tr.current() is None
    tr.end(q)
    assert q.end_s is not None and tr.roots[-1] is q


def test_submit_is_back_dated_to_the_request_s_arrival():
    t = time.perf_counter()
    time.sleep(0.01)
    tr = QueryTrace("q", origin_s=t)
    with tr.span("submit", start_s=t) as sp:
        pass
    assert sp.start_s == t and sp.wall_s >= 0.01
    d = tr.to_dicts()[0]
    assert d["startMillis"] == 0.0
    assert d["endUnixNanos"] - d["startUnixNanos"] >= 10_000_000


# ---------------------------------------------------------------------------
# the counters the spans feed
# ---------------------------------------------------------------------------

def test_each_phase_counts_once_per_query(coordinator):
    start = counts(coordinator)
    served(coordinator, sql_of("q6"))           # warm: traces, fills
    before = settled_counts(coordinator, start, 1)
    n = 3
    seen = dict.fromkeys(EXECUTE_PHASES, 0)
    for _ in range(n):
        res, _lat = served(coordinator, sql_of("q6"))
        for s in coordinator.tracker.get(res.query_id).trace.all_spans():
            if s.name in seen:
                seen[s.name] += 1
    after = settled_counts(coordinator, before, n)
    grew = {k: after[k] - before[k] for k in after}
    for phase in ROOT_PHASES:
        assert grew[phase] == n, (phase, grew)
    # under execute: by the span counts (at tpch.tiny on the CPU q6's
    # masked program is per-query, so each run traces it anew)
    dispatches = seen["dispatch"] + seen["jit_trace"]
    assert dispatches > 0 and seen["host_read"] > 0
    for phase in EXECUTE_PHASES:
        assert grew[phase] == seen[phase], (phase, grew, seen)
    assert grew["reads"] == seen["host_read"]
    assert grew["programs"] == dispatches
    assert seen["scan_fill"] == 0       # the warm run filled the cache


def test_every_sample_line_is_one_the_benchmark_reads(coordinator):
    served(coordinator, sql_of("q1"))
    text = scrape_text(coordinator)
    mine = [ln for ln in text.splitlines()
            if ln.startswith(("trino_tpu_query_phase_seconds",
                              "trino_tpu_device_programs_total",
                              "trino_tpu_host_reads_total",
                              "trino_tpu_scan_fill_seconds"))]
    assert len(mine) > 50
    for ln in text.splitlines():
        if not ln.startswith("#"):
            assert SAMPLE.match(ln), ln
    keys = {SAMPLE.match(ln).group(1) for ln in mine}
    assert f'{FAMILY}_sum{{phase="execute"}}' in keys
    assert f'{FAMILY}_count{{phase="respond"}}' in keys
    assert "trino_tpu_scan_fill_seconds_sum" in keys
    assert any(k.startswith('trino_tpu_host_reads_total{site="')
               for k in keys)
    assert any(k.startswith('trino_tpu_device_programs_total{kind="')
               for k in keys)


def test_a_join_s_search_steps_ride_its_total_read(coordinator):
    """A count program ends in ONE host read: the total, and beside it
    the steps its probe took and whether its directory was exact —
    ``steps`` and ``exact`` on the ``host_read[join_total]`` span,
    summed at /metrics. q3's two joins are on dense integer keys: exact,
    0 steps, and the steps family is exported all the same (the
    benchmark reads 0.0, not nothing); a join on two columns searches
    a hashed lane."""
    names = ("trino_tpu_join_probes_total",
             "trino_tpu_join_search_steps_total",
             "trino_tpu_host_reads_total",
             "trino_tpu_join_exact_probes_total")

    def fams():
        f = parse_exposition(scrape_text(coordinator))
        return [sum(f.get(n, {}).values()) for n in names]

    def one(sql):
        served(coordinator, sql)                # warm
        before = fams()
        res, _lat = served(coordinator, sql)
        grew = [a - b for a, b in zip(fams(), before)]
        spans = coordinator.tracker.get(res.query_id).trace.all_spans()
        reads = [s for s in spans if s.name == "host_read"]
        totals = [s for s in reads if s.attrs.get("site") == "join_total"]
        assert [s for s in reads if "steps" in s.attrs] == totals
        assert [s for s in reads if "exact" in s.attrs] == totals
        assert grew[0] == len(totals)
        assert grew[1] == sum(s.attrs["steps"] for s in totals)
        assert grew[2] == len(reads)    # no read added for the mode
        assert grew[3] == sum(s.attrs["exact"] for s in totals)
        return [(s.attrs["steps"], s.attrs["exact"]) for s in totals]

    assert one(sql_of("q3")) == [(0, 1), (0, 1)]
    samples = {SAMPLE.match(ln).group(1)
               for ln in scrape_text(coordinator).splitlines()
               if not ln.startswith("#")}
    assert {n + '{site="join_total"}' for n in names} <= samples
    (steps, exact), = one(
        "select count(*) from lineitem l join partsupp ps "
        "on l.l_partkey = ps.ps_partkey and l.l_suppkey = ps.ps_suppkey")
    assert exact == 0 and 0 < steps <= 6


def test_a_join_s_expand_program_says_its_form(coordinator):
    """The host that dispatches an expand program knows which form its
    static shapes chose for mapping output rows to probe rows
    (``ops/join.py expand_form``): ``form`` on the program's dispatch
    span, counted in ``trino_tpu_join_expands_total{site, form}``: no
    new read, nothing from the device. q3 at ``tiny`` runs two joins,
    both with the histogram; no other dispatch carries a form."""
    from trino_tpu.ops.join import expand_form

    def expands():
        f = parse_exposition(scrape_text(coordinator))
        return dict(f.get("trino_tpu_join_expands_total", {}))

    served(coordinator, sql_of("q3"))                # warm
    before = expands()
    res, _lat = served(coordinator, sql_of("q3"))
    spans = coordinator.tracker.get(res.query_id).trace.all_spans()
    dispatches = [s for s in spans
                  if s.name in ("dispatch", "jit_trace")]
    joins = [s for s in dispatches
             if str(s.attrs.get("program")).startswith("join_expand:")]
    assert len(joins) == 2
    assert [s for s in dispatches if "form" in s.attrs] == joins
    assert [s.attrs["form"] for s in joins] == ["histogram"] * 2
    grew = {k: v - before.get(k, 0.0) for k, v in expands().items()}
    assert {k: v for k, v in grew.items() if v} == {
        ("site=join_expand", "form=histogram"): 2.0}
    # few rows kept of many: the other form, by the same rule
    assert expand_form(1 << 25, 1 << 15) == "search"
    assert expand_form(1 << 25, 1 << 22) == "histogram"


# ---------------------------------------------------------------------------
# what a served query waits for (ISSUE 38)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("cls", CLASSES)
def test_a_served_query_waits_once_at_the_end_of_execute(coordinator,
                                                         cls):
    """No span waits for a program and no plan node is fenced: one
    ``dispatch`` (or first-call ``jit_trace``) per program counted at
    ``/metrics``, and ONE ``host_read[node_rows]``, the last child of
    ``execute``."""
    start = counts(coordinator)
    served(coordinator, sql_of(cls))            # warm
    before = settled_counts(coordinator, start, 1)
    res, _lat = served(coordinator, sql_of(cls))
    after = settled_counts(coordinator, before, 1)
    trace = coordinator.tracker.get(res.query_id).trace
    names = [s.name for s in trace.all_spans()]
    sites = [s.attrs.get("site") for s in trace.all_spans()
             if s.name == "host_read"]
    assert "device_execute" not in names
    assert not {"node_fence", "split_rows"} & set(sites), sites
    assert sites.count("node_rows") == 1
    execute, = [s for s in trace.roots if s.name == "execute"]
    last = execute.children[-1]
    assert (last.name, last.attrs.get("site")) == ("host_read",
                                                   "node_rows")
    assert names.count("dispatch") > 0
    assert after["programs"] - before["programs"] == \
        names.count("dispatch") + names.count("jit_trace")
    assert after["device_execute"] == before["device_execute"]


def analyzed_rows(lines):
    """(operator, output rows, input rows or -1) of each stats line of
    an EXPLAIN ANALYZE (exec/executor.py stats_lines)."""
    out = []
    for line in lines:
        m = re.match(r"(\w+): [0-9.]+ms, (?:in (\d+) rows[^,]*, )?"
                     r"out (\d+) rows", line)
        if m:
            out.append((m.group(1), int(m.group(3)),
                        int(m.group(2)) if m.group(2) else -1))
    return out


@pytest.mark.parametrize("cls", CLASSES)
def test_a_served_query_s_node_rows_are_explain_analyze_s(coordinator,
                                                          cls):
    """Read once at the end, the row counts are the ones EXPLAIN ANALYZE
    reads node by node; EXPLAIN ANALYZE still waits for and times each
    program (``device_execute``, ``device_ms``)."""
    res, _lat = served(coordinator, sql_of(cls))
    stats = coordinator.tracker.get(res.query_id).result.stats
    mine = [(s.name, s.output_rows, s.input_rows) for s in stats]
    assert mine and all(n >= 0 for _op, n, _in in mine)
    explained = client(coordinator).execute("EXPLAIN ANALYZE "
                                            + sql_of(cls))
    lines = [row[0] for row in explained.rows]
    assert analyzed_rows(lines) == mine
    assert any("- device_execute:" in ln and "device_ms=" in ln
               for ln in lines), lines
    assert not any("- dispatch:" in ln for ln in lines)
    assert any("site=node_fence" in ln for ln in lines)


def test_the_hook_counts_only_the_fixed_phases():
    from trino_tpu.obs.metrics import (DEVICE_PROGRAMS, HOST_READS,
                                       QUERY_PHASE_SECONDS,
                                       observe_span)
    tr = QueryTrace("q", on_close=observe_span)
    c0 = QUERY_PHASE_SECONDS.count(phase="host_read")
    r0 = HOST_READS.value(site="a_site")
    p0 = DEVICE_PROGRAMS.value(kind="join_count")
    with tr.span("execute"):
        with tr.span("host_read", site="a site"):
            pass
        with tr.span("dispatch", cache="join",
                     program="join_count:1a2b3c4d"):
            pass
        # EXPLAIN ANALYZE's name for it counts alike
        with tr.span("device_execute", cache="join",
                     program="join_count:1a2b3c4d"):
            pass
        with tr.span("schedule"):       # not a phase: no sample
            pass
    assert QUERY_PHASE_SECONDS.count(phase="host_read") == c0 + 1
    assert HOST_READS.value(site="a_site") == r0 + 1   # no space
    assert DEVICE_PROGRAMS.value(kind="join_count") == p0 + 2
    assert QUERY_PHASE_SECONDS.count(phase="schedule") == 0


# ---------------------------------------------------------------------------
# one clock: the spans are in the profiler's trace
# ---------------------------------------------------------------------------

def test_spans_are_annotations_on_the_profiler_s_clock(coordinator,
                                                       tmp_path):
    import jax
    served(coordinator, sql_of("q6"))           # warm
    t0 = time.perf_counter()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        res, _lat = served(coordinator, sql_of("q6"))
    finally:
        jax.profiler.stop_trace()
    paths = glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    assert paths
    data = jax.profiler.ProfileData.from_file(paths[-1])
    found = {}
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("tpusql:"):
                    stats = dict(ev.stats)
                    if stats.get("query_id") == res.query_id:
                        found.setdefault(ev.name, []).append(
                            (ev.duration_ns, stats.get("span_id")))
    trace = coordinator.tracker.get(res.query_id).trace
    by_name = {s.name: s for s in trace.roots}
    # every root the annotating threads opened is there (``queued``
    # begins and ends on different threads: a TraceMe cannot)
    for name in ROOT_PHASES:
        if name != "queued":
            assert f"tpusql:{name}" in found, sorted(found)
    assert "tpusql:host_read" in found
    (dur_ns, span_id), = found["tpusql:execute"]
    assert span_id == by_name["execute"].span_id
    assert abs(dur_ns / 1e6 - by_name["execute"].wall_s * 1e3) < 1.0
    assert time.perf_counter() - t0 < 20


@pytest.mark.parametrize("cls", CLASSES)
def test_a_dispatch_names_the_module_that_follows_it(coordinator,
                                                     tmp_path, cls):
    """The host's span and the device's module pair by name on one
    clock: each ``tpusql:dispatch`` annotation carries its span's
    ``program=<kind>:<key8>``, and a run of ``jit_<kind>_<key8>``
    starts after it, one run for each dispatch (XLA:CPU names each
    op's module at host tracer level 3; on the chip, ``XLA Modules``).
    The runs are asynchronous: the program dispatched before may still
    start after the annotation, so the pairing is by name."""
    import jax
    served(coordinator, sql_of(cls))            # warm
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 3
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        res, _lat = served(coordinator, sql_of(cls))
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(path)
    events = sorted(((ev.start_ns, ev.name, dict(ev.stats))
                     for plane in data.planes
                     if plane.name.startswith("/host:")
                     for line in plane.lines for ev in line.events),
                    key=lambda e: e[0])
    runs = {}                   # a module's run: its first op's start
    for t, _name, stats in events:
        if stats.get("hlo_module"):
            runs.setdefault(stats.get("run_id"),
                            (t, stats["hlo_module"]))
    runs = sorted(runs.values())
    spans = [s for s in coordinator.tracker.get(
        res.query_id).trace.all_spans() if s.name == "dispatch"]
    dispatches = [(t, stats["program"]) for t, name, stats in events
                  if name == "tpusql:dispatch"
                  and stats.get("query_id") == res.query_id]
    # (by program: the profile reads a hex span id such as "12e4..."
    # back as a number)
    assert dispatches and sorted(p for _t, p in dispatches) == sorted(
        s.attrs["program"] for s in spans)
    for t, program in dispatches:
        module = "jit_" + program.replace(":", "_")
        paired = next((i for i, (t_run, m) in enumerate(runs)
                       if m == module and t_run >= t), None)
        assert paired is not None, (program, runs)
        del runs[paired]            # one run for each dispatch


# ---------------------------------------------------------------------------
# names come from the canonical key only
# ---------------------------------------------------------------------------

def test_program_names_are_a_function_of_the_key():
    from trino_tpu.exec.progkey import named_jit, program_name
    key = ("mjoin_count", True, ("a",), ("b",), (("a", "int64"),), 8)
    assert program_name("join_count", key) == \
        program_name("join_count", tuple(key))
    assert re.fullmatch(r"join_count_[0-9a-f]{8}",
                        program_name("join_count", key))
    assert program_name("masked", None) == "masked_local"

    class Plain:                    # a default repr holds an address
        pass
    assert program_name("chain", (Plain(),)) == \
        program_name("chain", (Plain(),))
    jitted = named_jit(lambda x: x + 1, "chain", key)
    import jax.numpy as jnp
    text = jitted.lower(jnp.ones(3)).as_text()
    name = program_name("chain", key)
    assert f"@jit_{name}" in text
    assert jitted.program == "chain:" + name.split("_")[-1]


_LOWER = r"""
import hashlib, json, os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["TRINO_TPU_FRAGMENT_JIT"] = "1"
os.environ["TRINO_TPU_XLA_CACHE"] = "0"
import jax
lowered = []
real_jit = jax.jit
def jit(fn, *a, **k):
    jitted = real_jit(fn, *a, **k)
    class Spy:
        program = None
        def __call__(self, *args):
            text = jitted.lower(*args).as_text()
            lowered.append((getattr(fn, "__name__", "?"),
                            hashlib.sha256(text.encode()).hexdigest()))
            return jitted(*args)
        def __getattr__(self, name):
            return getattr(jitted, name)
    return Spy()
jax.jit = jit
from trino_tpu.runner import LocalQueryRunner
from trino_tpu.session import Session
runner = LocalQueryRunner(session=Session(catalog="tpch", schema="tiny"),
                          collect_node_stats=True)
for path in sys.argv[1:]:
    with open(path) as f:
        res = runner.execute(f.read())
    lowered.append(("rows", len(res.rows)))
print(json.dumps(lowered))
"""


def test_same_query_lowers_alike_in_two_processes():
    """q1, q3, q6 in two fresh processes (different hash seeds): the
    same program names and the same lowered module text, so the
    persistent compile cache of one serves the other."""
    paths = [os.path.join(QUERIES, f"{c}.sql") for c in ("q1", "q3", "q6")]
    outs = []
    for seed in ("1", "2"):
        env = dict(os.environ, PYTHONHASHSEED=seed, PYTHONPATH=ROOT)
        p = subprocess.run([sys.executable, "-c", _LOWER] + paths,
                           capture_output=True, text=True, env=env,
                           timeout=300, cwd=ROOT)
        assert p.returncode == 0, p.stderr[-2000:]
        outs.append(json.loads(p.stdout.strip().splitlines()[-1]))
    first, second = outs
    names = [n for n, _h in first if n != "rows"]
    assert any(re.fullmatch(r"(stream|stream_full|chain|join_count|"
                            r"join_expand|masked)_([0-9a-f]{8}|local)",
                            n) for n in names), names
    # no cached program is left under an anonymous name
    assert not [n for n in names if n in ("fn", "run", "run_full")]
    assert first == second
