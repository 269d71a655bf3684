"""Process-wide metrics registry with Prometheus text exposition.

Reference parity: the reference exports engine counters through JMX
(io.airlift.stats CounterStat/DistributionStat on QueryManager,
SqlTaskManager, the exchange clients) and the prometheus-jmx bridge.
Here the registry is a small lock-safe process singleton (``METRICS``)
rendered in the Prometheus text format (version 0.0.4) at GET /metrics
on both the coordinator and the task worker.

Design notes:
- one ``threading.Lock`` per registry covers every mutation AND the
  render pass; metric operations are dict updates, so the hot-path cost
  is a lock acquire + float add (the executor increments these per
  query, not per row — never inside a jitted program).
- label support is positional-by-name: a metric declares its label
  names once; every sample supplies them as keyword arguments. A
  mismatched label set raises — silent label drift would corrupt the
  time series.
- gauges may also be fed by *collector callbacks* run at render time
  (queue depth, cache residency): values that are cheap to read but
  wasteful to push on every change.
"""

from __future__ import annotations

import math
import threading
from bisect import bisect_left
from typing import Callable, Dict, List, Optional, Sequence, Tuple


def _escape(v: object) -> str:
    return (str(v).replace("\\", r"\\").replace('"', r'\"')
            .replace("\n", r"\n"))


def _fmt(v: float) -> str:
    if v == math.inf:
        return "+Inf"
    if float(v).is_integer() and abs(v) < 1e15:
        return str(int(v))
    return repr(float(v))


class _Metric:
    """Base: a named family of (label-tuple -> value) samples."""

    kind = "untyped"

    def __init__(self, name: str, help: str, labelnames: Sequence[str],
                 lock: threading.Lock):
        self.name = name
        self.help = help
        self.labelnames = tuple(labelnames)
        self._lock = lock
        self._values: Dict[Tuple[str, ...], float] = {}

    def _key(self, labels: Dict[str, object]) -> Tuple[str, ...]:
        if set(labels) != set(self.labelnames):
            raise ValueError(
                f"metric {self.name} expects labels "
                f"{self.labelnames}, got {tuple(labels)}")
        return tuple(str(labels[n]) for n in self.labelnames)

    def value(self, **labels) -> float:
        with self._lock:
            return self._values.get(self._key(labels), 0.0)

    def samples(self) -> List[Tuple[Tuple[str, ...], float]]:
        with self._lock:
            return sorted(self._values.items())

    def _render_labels(self, key: Tuple[str, ...],
                       extra: Sequence[Tuple[str, str]] = ()) -> str:
        pairs = [f'{n}="{_escape(v)}"'
                 for n, v in list(zip(self.labelnames, key)) + list(extra)]
        return "{" + ",".join(pairs) + "}" if pairs else ""

    def render(self) -> List[str]:
        lines = [f"# HELP {self.name} {self.help}",
                 f"# TYPE {self.name} {self.kind}"]
        samples = self.samples()
        if not samples and not self.labelnames:
            # Prometheus convention: an unlabeled family is initialized
            # to 0 at registration — scrapers can alert on rate() the
            # moment the process boots, not after the first event
            samples = [((), 0.0)]
        for key, v in samples:
            lines.append(
                f"{self.name}{self._render_labels(key)} {_fmt(v)}")
        return lines


class Counter(_Metric):
    kind = "counter"

    def inc(self, amount: float = 1.0, **labels) -> None:
        if amount < 0:
            raise ValueError("counters only go up")
        self.inc_at(self._key(labels), amount)

    def inc_at(self, key: Tuple[str, ...], amount: float = 1.0) -> None:
        """``inc`` for a caller that holds the label-value tuple (in
        ``labelnames`` order) already: the per-span hot path."""
        with self._lock:
            self._values[key] = self._values.get(key, 0.0) + amount


class Gauge(_Metric):
    kind = "gauge"

    def set(self, value: float, **labels) -> None:
        key = self._key(labels)
        with self._lock:
            self._values[key] = float(value)

    def inc(self, amount: float = 1.0, **labels) -> None:
        key = self._key(labels)
        with self._lock:
            self._values[key] = self._values.get(key, 0.0) + amount

    def dec(self, amount: float = 1.0, **labels) -> None:
        self.inc(-amount, **labels)


# wall-time-oriented default buckets: 1ms .. ~2min
DEFAULT_BUCKETS = (0.001, 0.005, 0.025, 0.1, 0.25, 0.5, 1.0, 2.5,
                   5.0, 10.0, 30.0, 120.0)


class Histogram(_Metric):
    kind = "histogram"

    def __init__(self, name, help, labelnames, lock,
                 buckets: Sequence[float] = DEFAULT_BUCKETS):
        super().__init__(name, help, labelnames, lock)
        self.buckets = tuple(sorted(buckets))
        # per label-key: [observations that fell INTO each bucket...,
        # those above the last bound, sum] — cumulated on read, so an
        # observation is one bisect and two adds
        self._hist: Dict[Tuple[str, ...], List[float]] = {}

    def observe(self, value: float, **labels) -> None:
        self.observe_at(self._key(labels), value)

    def observe_at(self, key: Tuple[str, ...], value: float) -> None:
        """``observe`` for a caller that holds the label-value tuple
        (in ``labelnames`` order) already: the per-span hot path."""
        with self._lock:
            h = self._hist.get(key)
            if h is None:
                h = [0.0] * (len(self.buckets) + 2)
                self._hist[key] = h
            h[bisect_left(self.buckets, value)] += 1
            h[-1] += value       # sum

    def _cumulated(self, h: List[float]) -> Tuple[List[float], float]:
        """(cumulative count per bucket bound, total count)."""
        cum, run = [], 0.0
        for c in h[:len(self.buckets)]:
            run += c
            cum.append(run)
        return cum, run + h[-2]

    def count(self, **labels) -> float:
        with self._lock:
            h = self._hist.get(self._key(labels))
            return self._cumulated(h)[1] if h else 0.0

    def snapshot(self, **labels) -> Tuple[Tuple[float, ...], float,
                                          float]:
        """(cumulative bucket counts, total count, sum) — the readback
        half of the histogram for in-process consumers (the bench load
        leg computes percentile deltas between two snapshots rather
        than re-parsing its own exposition text)."""
        with self._lock:
            h = self._hist.get(self._key(labels))
            if h is None:
                return (0.0,) * len(self.buckets), 0.0, 0.0
            cum, total = self._cumulated(h)
            return tuple(cum), total, h[-1]

    @staticmethod
    def quantile_from_deltas(buckets: Sequence[float],
                             deltas: Sequence[float], count: float,
                             q: float) -> float:
        """Estimate the q-quantile from cumulative-bucket-count deltas
        (Prometheus histogram_quantile semantics: linear interpolation
        within the containing bucket, clamped to the largest finite
        bucket bound for the +Inf tail)."""
        if count <= 0:
            return 0.0
        rank = q * count
        prev_bound, prev_cum = 0.0, 0.0
        for bound, cum in zip(buckets, deltas):
            if cum >= rank:
                span = cum - prev_cum
                frac = ((rank - prev_cum) / span) if span > 0 else 1.0
                return prev_bound + (bound - prev_bound) * frac
            prev_bound, prev_cum = bound, cum
        return buckets[-1] if buckets else 0.0

    def render(self) -> List[str]:
        lines = [f"# HELP {self.name} {self.help}",
                 f"# TYPE {self.name} {self.kind}"]
        with self._lock:
            items = sorted((key, self._cumulated(h), h[-1])
                           for key, h in self._hist.items())
        for key, (cum, total), hsum in items:
            for b, c in zip(self.buckets, cum):
                lines.append(
                    f"{self.name}_bucket"
                    f"{self._render_labels(key, [('le', _fmt(b))])}"
                    f" {_fmt(c)}")
            lines.append(
                f"{self.name}_bucket"
                f"{self._render_labels(key, [('le', '+Inf')])}"
                f" {_fmt(total)}")
            lines.append(
                f"{self.name}_sum{self._render_labels(key)} "
                f"{_fmt(hsum)}")
            lines.append(
                f"{self.name}_count{self._render_labels(key)} "
                f"{_fmt(total)}")
        return lines


class MetricsRegistry:
    """Named-metric registry; ``counter``/``gauge``/``histogram`` are
    get-or-create (idempotent across modules instrumenting the same
    family). ``render()`` produces the full text exposition."""

    def __init__(self):
        self._lock = threading.Lock()
        self._metrics: Dict[str, _Metric] = {}
        self._collectors: List[Callable[[], None]] = []

    def _get(self, cls, name: str, help: str,
             labelnames: Sequence[str], **kw) -> _Metric:
        with self._lock:
            m = self._metrics.get(name)
            if m is not None:
                if type(m) is not cls:
                    raise ValueError(
                        f"metric {name} already registered as {m.kind}")
                return m
            m = cls(name, help, tuple(labelnames), self._lock, **kw)
            self._metrics[name] = m
            return m

    def counter(self, name: str, help: str = "",
                labelnames: Sequence[str] = ()) -> Counter:
        return self._get(Counter, name, help, labelnames)

    def gauge(self, name: str, help: str = "",
              labelnames: Sequence[str] = ()) -> Gauge:
        return self._get(Gauge, name, help, labelnames)

    def histogram(self, name: str, help: str = "",
                  labelnames: Sequence[str] = (),
                  buckets: Sequence[float] = DEFAULT_BUCKETS
                  ) -> Histogram:
        return self._get(Histogram, name, help, labelnames,
                         buckets=buckets)

    def register_collector(self, fn: Callable[[], None]) -> None:
        """Run ``fn`` before every render; it refreshes gauges whose
        values are polled, not pushed (queue depth, cache bytes).
        Pair with ``unregister_collector`` when the owning component
        shuts down — the registry is process-global and would pin the
        callback (and keep rendering its stale gauges) forever."""
        with self._lock:
            self._collectors.append(fn)

    def unregister_collector(self, fn: Callable[[], None]) -> None:
        with self._lock:
            try:
                self._collectors.remove(fn)
            except ValueError:
                pass

    def render(self) -> str:
        with self._lock:
            collectors = list(self._collectors)
            metrics = list(self._metrics.values())
        for fn in collectors:
            try:
                fn()
            except Exception:   # noqa: BLE001 — scrape must not fail
                pass
        lines: List[str] = []
        for m in sorted(metrics, key=lambda m: m.name):
            lines.extend(m.render())
        return "\n".join(lines) + "\n"


# the process-wide registry (the JMX MBean server analog)
METRICS = MetricsRegistry()

# shared across every runner flavor (LocalQueryRunner and the remote
# DistributedHostQueryRunner feed the same latency histogram — one
# definition so the help text and identity cannot drift)
QUERY_WALL_SECONDS = METRICS.histogram(
    "trino_tpu_query_wall_seconds",
    "End-to-end query wall time through the runner")

# scrape-friendly spot value (ROADMAP follow-on): the most recently
# completed query's peak reserved memory. A scraper sampling between
# queries sees the live high-water mark; QueryCompletedEvent carries
# the authoritative per-query figure for audit sinks.
QUERY_PEAK_MEMORY_BYTES = METRICS.gauge(
    "trino_tpu_query_peak_memory_bytes",
    "Peak reserved memory (bytes) of the most recently completed query")

# plan sanity checking (analysis/sanity.py): runs are counted so a
# fleet can alert on validation being accidentally disabled (rate
# drops to 0 while queries keep flowing); failures carry the validator
# name — the responsible optimizer pass is in the error message
PLAN_VALIDATIONS = METRICS.counter(
    "trino_tpu_plan_validations_total",
    "Plan sanity-checker batteries executed")
PLAN_VALIDATION_FAILURES = METRICS.counter(
    "trino_tpu_plan_validation_failures_total",
    "Plans rejected by the sanity checker, by validator", ("validator",))

# multi-stage MPP (trino_tpu/stage/): the partitioned worker-to-worker
# exchange. "written" counts a producing task cutting its output into
# partition frames; "read" counts a consuming task pulling its
# partition of upstream tasks (stage/repartition.py, stage/exchange.py)
# — defined here because the two directions live in different modules
# and their identity must not drift.
# overload governance (server/resourcegroups.py + server/memory.py):
# admission queueing, the cluster memory pool, and deadline
# enforcement. Defined here because producers span modules (tracker,
# group manager, memory manager, remote scheduler) and the bench load
# leg re-reads them — one identity, no drift.
QUERY_QUEUED_SECONDS = METRICS.histogram(
    "trino_tpu_query_queued_seconds",
    "Time queries spent queued in resource-group admission before "
    "starting")
QUEUE_REJECTIONS = METRICS.counter(
    "trino_tpu_queue_rejections_total",
    "Queries rejected at admission because the group queue was full "
    "(QUERY_QUEUE_FULL)")
MEMORY_POOL_BYTES = METRICS.gauge(
    "trino_tpu_memory_pool_bytes",
    "Cluster memory pool state in bytes", ("kind",))   # total|reserved
MEMORY_POOL_QUERIES = METRICS.gauge(
    "trino_tpu_memory_pool_queries",
    "Queries currently holding a cluster memory pool reservation")
MEMORY_KILLS = METRICS.counter(
    "trino_tpu_memory_kills_total",
    "Queries killed by the low-memory killer (CLUSTER_OUT_OF_MEMORY)")
DEADLINE_CANCELS = METRICS.counter(
    "trino_tpu_deadline_cancels_total",
    "Queries canceled for exceeding query_max_run_time "
    "(EXCEEDED_TIME_LIMIT)")

EXCHANGE_PARTITIONS = METRICS.counter(
    "trino_tpu_exchange_partitions_total",
    "Partitioned-exchange frames by direction", ("direction",))
EXCHANGE_PARTITION_BYTES = METRICS.counter(
    "trino_tpu_exchange_partition_bytes_total",
    "Serialized partitioned-exchange bytes by direction", ("direction",))
STAGES_SCHEDULED = METRICS.counter(
    "trino_tpu_stages_scheduled_total",
    "Worker stages dispatched by the stage-DAG scheduler")
# coordinator failover (stage/scheduler.py resume mode): per resumed
# query, stage partitions already COMMITTED on the exchange spool are
# "resumed" (served off spool, zero re-execution); the rest are
# "replayed" (re-dispatched)
FAILOVER_PARTITIONS = METRICS.counter(
    "trino_tpu_failover_partitions_total",
    "Stage partitions handled during coordinator-failover resume by "
    "outcome", ("outcome",))
# eager stage pipelining (stage/scheduler.py): the last query's share
# of exchange-connected wall time where tasks of >= 2 different stages
# ran concurrently (0 under the per-stage barrier; the bench mpp leg's
# mpp_pipeline_overlap_ratio)
MPP_OVERLAP_RATIO = METRICS.gauge(
    "trino_tpu_mpp_pipeline_overlap_ratio",
    "Pipelined stage overlap of the most recent stage-DAG query")
# beyond-HBM morsel streaming (exec/streamjoin.py): registered here —
# not in the lazily-imported streaming module — so every consumer
# (bench deltas, /metrics scrapes, tests) sees the same labeled
# families regardless of import order
STREAM_CHUNKS = METRICS.counter(
    "trino_tpu_stream_chunks_total",
    "Chunks processed by morsel-streamed operators", ("op",))
STREAM_H2D_BYTES = METRICS.counter(
    "trino_tpu_stream_bytes_h2d_total",
    "Bytes moved host->device by streamed-operator chunk transfers")
STREAM_OVERLAPPED = METRICS.counter(
    "trino_tpu_stream_transfers_overlapped_total",
    "Chunk transfers issued while the previous chunk's compute was "
    "still in flight (the double-buffer overlap)")

# worker-side multi-query runtime (exec/taskexec.py +
# server/task_worker.py): the shared split scheduler interleaving
# splits/chunks from every concurrent query's tasks, live per-task
# memory beats into the cluster pool, pressure-driven cache eviction,
# and the BUSY load-shed signal. Registered here — not in the lazily
# imported scheduler module — so scrapes and bench deltas see one
# family identity regardless of import order.
TASK_SCHED_QUANTA = METRICS.counter(
    "trino_tpu_task_scheduler_quanta_total",
    "Split/chunk quanta the shared task scheduler accounted, by "
    "resource group (the fairness observable)", ("group",))
TASK_SCHED_YIELDS = METRICS.counter(
    "trino_tpu_task_scheduler_yields_total",
    "Times a task handed its runner slot to a higher-priority task "
    "at a split/chunk boundary")
TASK_SCHED_RUNNABLE = METRICS.gauge(
    "trino_tpu_task_scheduler_open_tasks",
    "Tasks currently registered with the shared task scheduler "
    "(running + waiting + blocked)")
WORKER_BUSY_REJECTS = METRICS.counter(
    "trino_tpu_worker_busy_rejections_total",
    "Task dispatches this worker declined with the retryable BUSY "
    "signal under sustained load (the stage scheduler's retry/"
    "rotation machinery re-places them)")
LIVE_MEMORY_BEATS = METRICS.counter(
    "trino_tpu_worker_live_memory_beats_total",
    "Worker-reported live task reservations folded into the cluster "
    "memory pool DURING execution (status-poll beats)")
CACHE_PRESSURE_EVICTS = METRICS.counter(
    "trino_tpu_cache_pressure_evictions_total",
    "Cache entries evicted by memory-pressure governance, by cache "
    "(scan = HBM scan cache, jit = structural program caches, "
    "replicate = exchange fetch-once cache)", ("cache",))
REPLICATE_CACHE = METRICS.counter(
    "trino_tpu_exchange_replicate_cache_total",
    "Per-worker fetch-once cache lookups on replicate exchange "
    "edges, by outcome", ("result",))

# structural jitted-program caches (exec/executor.py chain/stream/
# masked programs + exec/streamjoin.py probe programs): ONE family
# definition here so the two producer modules cannot drift into
# duplicate registrations of the same name
JIT_CACHE_LOOKUPS = METRICS.counter(
    "trino_tpu_jit_cache_total",
    "Structural jitted-program cache lookups by cache and outcome",
    ("cache", "result"))

# distributed tracing + scheduler attribution (ISSUE 15): the
# worker-side split scheduler's observables (exec/taskexec.py) and the
# OTLP trace exporter (obs/otlp.py). Registered here — not in the
# lazily imported producer modules — so scrapes, the bench telemetry
# leg, and the EMA busy-shed all read one family identity.
TASK_SCHED_QUEUE_DEPTH = METRICS.gauge(
    "trino_tpu_task_scheduler_queue_depth",
    "Tasks waiting for a runner slot in the shared split scheduler "
    "(the backlog the EMA busy-shed smooths)")
TASK_QUANTUM_SECONDS = METRICS.histogram(
    "trino_tpu_task_quantum_seconds",
    "Wall seconds per scheduler quantum (the work between two "
    "split/chunk checkpoints)")
EXCHANGE_WAIT_SECONDS = METRICS.histogram(
    "trino_tpu_exchange_wait_seconds",
    "Wall seconds a consumer task spent blocked on upstream exchange "
    "commits with its runner slot released")
TASK_SCHED_LEVEL_SECONDS = METRICS.counter(
    "trino_tpu_task_scheduled_seconds_total",
    "Scheduled wall seconds accounted by the shared split scheduler, "
    "by multilevel-feedback level at grant time", ("level",))
OTLP_EXPORTS = METRICS.counter(
    "trino_tpu_otlp_exports_total",
    "OTLP trace-export attempts by sink and outcome (obs/otlp.py "
    "file/HTTP sinks)", ("sink", "result"))

# query history + learned operator statistics (obs/history.py +
# exec/learnedstats.py): terminal-query records appended to the
# durable history store, slow-query-log emissions, and the learned
# selectivity/throughput registry's observation flow. Registered here
# — not in the producer modules — so coordinator scrapes, worker
# scrapes and bench deltas all read one family identity.
HISTORY_RECORDS = METRICS.counter(
    "trino_tpu_query_history_records_total",
    "Terminal-query records appended to the coordinator's durable "
    "query-history store, by terminal state", ("state",))
SLOW_QUERY_LOGS = METRICS.counter(
    "trino_tpu_slow_query_log_total",
    "Queries whose wall time crossed the slow_query_log_ms threshold "
    "and were written to the trace-linked slow-query log")
LEARNED_STATS_OBSERVATIONS = METRICS.counter(
    "trino_tpu_learned_stats_observations_total",
    "Per-operator executions folded into the learned-stats registry "
    "(observed = this process's executors, merged = worker "
    "task-status deltas)", ("outcome",))
LEARNED_STATS_SIZE = METRICS.gauge(
    "trino_tpu_learned_stats_entries",
    "(program key, operator, occurrence) entries currently tracked "
    "by the learned-stats registry")

# streaming ingestion + continuous queries (trino_tpu/streaming/ +
# connectors/stream.py): producers POST /v1/ingest/{topic} on the
# coordinator or any worker, offset commits seal each continuous
# cycle, and the job scheduler re-dispatches incremental plans on a
# cadence. Registered here — the producers span the message log, both
# HTTP server modules and the continuous-query manager — so scrapes
# and bench deltas read one family identity regardless of import
# order.
INGEST_ROWS = METRICS.counter(
    "trino_tpu_ingest_rows_total",
    "Messages appended to the streaming message log, by topic",
    ("topic",))
INGEST_BYTES = METRICS.counter(
    "trino_tpu_ingest_bytes_total",
    "Message payload bytes appended to the streaming message log, "
    "by topic", ("topic",))
OFFSET_COMMITS = METRICS.counter(
    "trino_tpu_stream_offset_commits_total",
    "Consumer offset epochs committed to the spool-backed offset "
    "store, by outcome (committed = this process sealed the epoch, "
    "superseded = an earlier commit already won)", ("outcome",))
CONTINUOUS_CYCLES = METRICS.counter(
    "trino_tpu_continuous_cycles_total",
    "Continuous-query scheduler cycles, by outcome (advanced = new "
    "offsets committed, idle = no new messages, failed)", ("outcome",))
CONTINUOUS_JOBS = METRICS.gauge(
    "trino_tpu_continuous_queries",
    "Continuous-query jobs currently RUNNING on this coordinator")


# the served path, phase by phase (obs/trace.py PHASES): fed by ONE
# hook, ``observe_span``, that QueryTrace calls with every span that
# closes — the spans are the timers, nothing is timed twice. A fixed
# set of phase names; no label value holds a space (line-oriented
# scrapers split the sample line at the first one).
PHASE_BUCKETS = (0.00001, 0.0001, 0.0005, 0.001, 0.005, 0.025, 0.1,
                 0.5, 2.5, 10.0, 60.0)
QUERY_PHASE_SECONDS = METRICS.histogram(
    "trino_tpu_query_phase_seconds",
    "Wall time of the spans of a served query, by span name (root "
    "phases submit..finish; dispatch, jit_trace, host_read and "
    "scan_fill under execute, device_execute under EXPLAIN ANALYZE; "
    "exchange around each mesh exchange)",
    ("phase",), buckets=PHASE_BUCKETS)
DEVICE_PROGRAMS = METRICS.counter(
    "trino_tpu_device_programs_total",
    "Device programs dispatched by traced queries, by the cache (and "
    "role) the program lives in", ("kind",))
HOST_READS = METRICS.counter(
    "trino_tpu_host_reads_total",
    "Blocking device-to-host reads of the executor in traced queries, "
    "by call site", ("site",))
JOIN_PROBES = METRICS.counter(
    "trino_tpu_join_probes_total",
    "Join probes whose step count traced queries read, by the host "
    "read that carried it (join_total: the count program of a "
    "materialized join; of a mesh join, one probe: the most steps of "
    "its shards, exact, packed and bitmap where every shard was)",
    ("site",))
JOIN_SEARCH_STEPS = METRICS.counter(
    "trino_tpu_join_search_steps_total",
    "Bisection steps those probes took inside their directory buckets "
    "(ops/join.py probe_runs), after the ONE gather that reads a "
    "bucket's bounds: over the probes, 0 where the directory is exact "
    "(a bucket is one key value: no search ran) or a bitmap, 2-4 "
    "over a hashed lane, log2(build capacity)+1 where one key fills a "
    "bucket",
    ("site",))
JOIN_EXACT_PROBES = METRICS.counter(
    "trino_tpu_join_exact_probes_total",
    "Those probes whose build side chose the exact directory (one "
    "integer key column whose usable values span less than the "
    "directory: 0 steps, one probe-sized gather in all where packed); "
    "the others read the bitmap directory or searched their buckets",
    ("site",))
JOIN_PACKED_PROBES = METRICS.counter(
    "trino_tpu_join_packed_probes_total",
    "Those probes whose rows read their bucket's bounds in ONE gather "
    "of a 32-bit directory word, first position above size (the "
    "fullest bucket's size fits the bits a position leaves: 11 at a "
    "build capacity of 2^20, 5 at 2^26); the others read two adjacent "
    "sums, two gathers", ("site",))
JOIN_BITMAP_PROBES = METRICS.counter(
    "trino_tpu_join_bitmap_probes_total",
    "Those probes whose build side chose the bitmap directory (one "
    "integer key column of unique values whose span the exact "
    "directory cannot serve from its head: a probe row reads ONE word, "
    "its keys' first position above a bit a key value; no search)",
    ("site",))
JOIN_PROBE_ROWS = METRICS.counter(
    "trino_tpu_join_probe_rows_total",
    "Live rows on the probe side of those joins (a mesh join: summed "
    "over its shards), from the same read", ("site",))
JOIN_OUTPUT_ROWS = METRICS.counter(
    "trino_tpu_join_output_rows_total",
    "Rows those joins put out (a mesh join: summed over its shards), "
    "from the same read: with the probe rows, a join chain's shape",
    ("site",))
JOIN_EXPANDS = METRICS.counter(
    "trino_tpu_join_expands_total",
    "Join expand programs dispatched by traced queries, by the kind of "
    "the program (join_expand, spmd_join_expand, streamjoin) and the "
    "form its static shapes chose for mapping output rows to probe "
    "rows (ops/join.py expand_form: histogram | search)",
    ("site", "form"))
JOIN_EXPAND_LANES = METRICS.counter(
    "trino_tpu_join_expand_lanes_total",
    "Lanes of the two inputs of those expand programs, by the kind of "
    "the program and whether the expand gathered them (kept=yes: what "
    "the plan above the join reads, and its residual filter's inputs) "
    "or left them out (kept=no: join keys and columns read by nothing "
    "above)", ("site", "kept"))
GROUPBYS = METRICS.counter(
    "trino_tpu_groupby_total",
    "Grouped aggregations of traced queries, by the kind of the "
    "program they are in (stream_full, stream_dense, stream, chain, "
    "masked ...; eager: run operation by operation, in no program) and "
    "the form the aggregation RUNS in (ops/groupby.py group_aggregate: "
    "packed | dense | sort), counted per dispatch, not per compile",
    ("site", "form"))
GROUPBY_LANES = METRICS.counter(
    "trino_tpu_groupby_lanes_total",
    "Input capacity (lanes) of those aggregations, by the same labels: "
    "the dense form's share of it says how much of the grouping went "
    "without a sort", ("site", "form"))
EXPR_CONSTANT_SUBTREES = METRICS.counter(
    "trino_tpu_expr_constant_subtrees_total",
    "Subtrees of an expression with no column and no volatile call "
    "that exec/expr.py evaluated at ONE row and broadcast, by the "
    "subtree's root (a call's name, cast, case); grows when a program "
    "is traced or an eager batch is evaluated, not per dispatch",
    ("fn",))
EXCHANGE_BYTES = METRICS.counter(
    "trino_tpu_mesh_exchange_bytes_total",
    "Bytes the mesh executor's exchanges moved in traced queries: live "
    "rows times the widths of the lanes sent, not padding, by kind "
    "(repartition: all_to_all by key hash or range; broadcast: "
    "all_gather of a join's build side; gather: the collect on the "
    "coordinator)", ("kind",))
EXCHANGE_ROWS = METRICS.counter(
    "trino_tpu_mesh_exchange_rows_total",
    "Live rows those exchanges moved, by kind", ("kind",))
PROGRAM_LITERAL_ARGS = METRICS.counter(
    "trino_tpu_program_literal_args_total",
    "Literals bound to device programs as arguments (exec/literals.py: "
    "the slots of a canonical program), by the kind of the program: "
    "the args attr of its dispatch span", ("kind",))
SCAN_DERIVES = METRICS.counter(
    "trino_tpu_scan_derive_total",
    "Pushed-down constraints whose lanes one program derived from the "
    "table's resident base lanes (exec/scanderive.py), by table",
    ("table",))
SCAN_FILL_SECONDS = METRICS.histogram(
    "trino_tpu_scan_fill_seconds",
    "Scan-cache miss path: reading or generating a split's missing "
    "lanes and pinning them on the device", buckets=PHASE_BUCKETS)


from .trace import PHASES as _PHASE_NAMES  # noqa: E402

_PHASES = frozenset(_PHASE_NAMES)


_LABELS: Dict[object, Tuple[str]] = {}


def _label_key(v: object) -> Tuple[str]:
    """The one-label key of ``v``, with no space in it; remembered,
    since sites and kinds are a small fixed set."""
    key = _LABELS.get(v)
    if key is None:
        key = _LABELS[v] = (str(v).replace(" ", "_") or "none",)
    return key


def observe_span(sp) -> None:
    """The ``QueryTrace.on_close`` hook (set where a trace is born):
    a closed span of a known phase becomes one histogram observation,
    and one program / host-read count."""
    name = sp.name
    if name not in _PHASES:
        return
    wall = sp.wall_s
    QUERY_PHASE_SECONDS.observe_at((name,), wall)
    if name == "host_read":
        site = _label_key(sp.attrs.get("site", "other"))
        HOST_READS.inc_at(site)
        steps = sp.attrs.get("steps")
        if steps is not None:
            JOIN_PROBES.inc_at(site)
            # 0 steps still creates the sample: a run of exact probes
            # exports the family, reading 0
            JOIN_SEARCH_STEPS.inc_at(site, steps)
            JOIN_EXACT_PROBES.inc_at(site, sp.attrs.get("exact", 0))
            JOIN_PACKED_PROBES.inc_at(site, sp.attrs.get("packed", 0))
            JOIN_BITMAP_PROBES.inc_at(site, sp.attrs.get("bitmap", 0))
            JOIN_PROBE_ROWS.inc_at(site, sp.attrs.get("probe_rows", 0))
            JOIN_OUTPUT_ROWS.inc_at(site, sp.attrs.get("total", 0))
    elif name in ("dispatch", "device_execute", "jit_trace"):
        program = str(sp.attrs.get("program")
                      or sp.attrs.get("cache") or "other")
        kind = _label_key(program.split(":", 1)[0])
        DEVICE_PROGRAMS.inc_at(kind)
        args = sp.attrs.get("args")
        if args:
            PROGRAM_LITERAL_ARGS.inc_at(kind, args)
        groupby = sp.attrs.get("groupby")
        form = sp.attrs.get("form")
        if groupby is not None:
            # an aggregation program: "form:input lanes,...", what the
            # dispatcher kept from the program's trace
            for part in str(groupby).split(","):
                gform, _, lanes = part.partition(":")
                labels = kind + _label_key(gform)
                GROUPBYS.inc_at(labels)
                GROUPBY_LANES.inc_at(labels, float(lanes or 0))
        elif form is not None:
            JOIN_EXPANDS.inc_at(kind + _label_key(form))
        lanes = sp.attrs.get("lanes")
        if lanes is not None:
            # a join's expand: "<gathered>/<offered>" lanes of its inputs
            kept, _, offered = str(lanes).partition("/")
            JOIN_EXPAND_LANES.inc_at(kind + ("yes",), int(kept))
            JOIN_EXPAND_LANES.inc_at(kind + ("no",),
                                     int(offered) - int(kept))
    elif name == "scan_fill":
        SCAN_FILL_SECONDS.observe_at((), wall)
    elif name == "scan_derive":
        SCAN_DERIVES.inc_at(_label_key(sp.attrs.get("table", "other")))
    elif name == "exchange":
        kind = _label_key(sp.attrs.get("kind", "other"))
        EXCHANGE_BYTES.inc_at(kind, sp.attrs.get("bytes", 0))
        EXCHANGE_ROWS.inc_at(kind, sp.attrs.get("rows", 0))


def write_exposition(handler) -> None:
    """Serve METRICS as a Prometheus text response on a
    BaseHTTPRequestHandler — the one /metrics implementation shared by
    the coordinator and the task worker."""
    raw = METRICS.render().encode()
    handler.send_response(200)
    handler.send_header("Content-Type",
                        "text/plain; version=0.0.4; charset=utf-8")
    handler.send_header("Content-Length", str(len(raw)))
    handler.end_headers()
    handler.wfile.write(raw)


def parse_exposition(text: str) -> Dict[str, Dict[Tuple[str, ...], float]]:
    """Parse Prometheus text exposition back into
    {metric_name: {(label=value, ...): value}} — the test-side decoder
    (asserting on re-parsed samples, not on string formatting)."""
    out: Dict[str, Dict[Tuple[str, ...], float]] = {}
    for line in text.splitlines():
        line = line.strip()
        if not line or line.startswith("#"):
            continue
        name_labels, _, raw = line.rpartition(" ")
        if "{" in name_labels:
            name, _, rest = name_labels.partition("{")
            body = rest.rstrip("}")
            labels = []
            for part in _split_labels(body):
                k, _, v = part.partition("=")
                labels.append(f"{k}={v.strip(chr(34))}")
            key = tuple(labels)
        else:
            name, key = name_labels, ()
        out.setdefault(name, {})[key] = float(raw)
    return out


def _split_labels(body: str) -> List[str]:
    parts, cur, inq = [], "", False
    for ch in body:
        if ch == '"':
            inq = not inq
            cur += ch
        elif ch == "," and not inq:
            parts.append(cur)
            cur = ""
        else:
            cur += ch
    if cur:
        parts.append(cur)
    return parts
