"""Global configuration for the TPU-native engine.

The engine requires 64-bit types: SQL BIGINT is int64 and DOUBLE is float64
(XLA rewrites both onto 32-bit words on TPU; what the v5e compiler
accepts and refuses is in PERF.md). This module must be
imported before any jax.numpy use, so every entry point imports trino_tpu
first.

Reference parity: plays the role of Trino's FeaturesConfig / TaskManagerConfig
(reference: core/trino-main/.../sql/analyzer/FeaturesConfig.java,
execution/TaskManagerConfig.java) — a process-wide knob registry, with
per-session overrides layered on top by ``trino_tpu.session.Session``.
"""

from __future__ import annotations

import dataclasses
import os
import tempfile

import jax

jax.config.update("jax_enable_x64", True)

# Persistent XLA compilation cache (the analog of the reference's
# compiled-class caches in ExpressionCompiler/PageFunctionCompiler —
# sql/gen/ExpressionCompiler.java:56 keeps generated bytecode across
# queries; we keep compiled XLA programs across PROCESSES). Fresh
# processes re-running the same operator shapes skip recompilation:
# measured 268s -> 8s for a 4-join TPC-DS query on a 1-core CPU host.
#
# Placement: where JAX_COMPILATION_CACHE_DIR is set, jax reads it
# itself and this module sets no directory. Unset, the cache lives at
# ONE fixed path inside the checkout, <repo>/.jax_cache/xla-<tag>
# (git-ignored; the path is part of jax's cache key, so a directory
# that moves never hits). TRINO_TPU_XLA_CACHE=0 switches it off.
#
# The <tag> sub-directory fingerprints the host CPU's feature set:
# XLA:CPU persists AOT machine code, and an entry compiled on a host
# with a different ISA extension set EXECUTES WRONG CODE when loaded
# (observed: avx512-family mismatch loading a previous machine's cache
# produced intermittent memory corruption — flaky page-checksum
# failures — with cpu_aot_loader.cc warning "could lead to execution
# errors such as SIGILL"). A checkout directory copied between
# machines carries its .jax_cache along, so the tag stays. A directory
# named by JAX_COMPILATION_CACHE_DIR is used exactly as given.

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _machine_tag() -> str:
    import hashlib
    import platform
    feats = ""
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                # x86 says "flags", aarch64 says "Features"
                if line.startswith(("flags", "Features")):
                    feats = " ".join(sorted(line.split(":", 1)[1]
                                            .split()))
                    break
    except OSError:
        pass
    raw = f"{platform.machine()}|{feats}"
    return hashlib.sha256(raw.encode()).hexdigest()[:12]


if os.environ.get("TRINO_TPU_XLA_CACHE", "") != "0":
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        from jax.experimental.compilation_cache import (
            compilation_cache as _compilation_cache)
        _compilation_cache.set_cache_dir(os.path.join(
            REPO_ROOT, ".jax_cache", f"xla-{_machine_tag()}"))
    jax.config.update(
        "jax_persistent_cache_min_compile_time_secs", float(
            os.environ.get("TRINO_TPU_XLA_CACHE_MIN_SECS", "0.1")))
else:
    jax.config.update("jax_enable_compilation_cache", False)


def _env_int(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, default))
    except ValueError:
        return default


def _env_float(name: str, default: float) -> float:
    try:
        return float(os.environ.get(name, default))
    except ValueError:
        return default


@dataclasses.dataclass
class EngineConfig:
    """Process-wide engine configuration (Trino: etc/config.properties)."""

    # Max rows per Batch flowing through a pipeline. Static-shape buckets are
    # powers of two up to this; larger inputs are processed in chunks.
    max_batch_rows: int = _env_int("TRINO_TPU_MAX_BATCH_ROWS", 1 << 22)
    # Minimum physical capacity bucket, to bound the number of distinct
    # compiled shapes (each bucket is a separate XLA compilation).
    min_capacity: int = 1 << 10
    # Per-query memory limit in bytes (Trino: query.max-memory-per-node).
    max_query_memory_per_node: int = _env_int(
        "TRINO_TPU_QUERY_MAX_MEMORY", 16 << 30
    )
    # Cluster memory pool in bytes (Trino: query.max-memory feeding
    # ClusterMemoryManager's GENERAL pool). When > 0 the coordinator
    # governs the SUM of concurrent queries' reservations through
    # server/memory.py (per-group aggregation + low-memory killer);
    # 0 disables pool governance (per-node limits still apply).
    cluster_memory_pool_bytes: int = _env_int(
        "TRINO_TPU_CLUSTER_MEMORY_POOL", 0)
    # Enable host spill when device memory is exhausted.
    spill_enabled: bool = os.environ.get("TRINO_TPU_SPILL", "1") == "1"
    # HBM-resident scan cache budget for immutable generator connectors
    # (tpch/tpcds): table columns live in device memory across queries
    # — the "storage layer in HBM" design of README.md. 0 disables.
    scan_cache_bytes: int = _env_int("TRINO_TPU_SCAN_CACHE",
                                     4 << 30)
    # Fault-tolerant execution (trino_tpu/fte/): root directory of the
    # local spooling exchange (Trino: exchange.base-directories) and
    # the TTL after which an abandoned query's spool is reaped
    # (exchange.file-listing... collapsed to an mtime sweep). The
    # default is uid-scoped (and the spool chmods it 0700): query
    # results transit this directory, so a world-shared path would let
    # any local user read or forge them.
    spool_dir: str = os.environ.get(
        "TRINO_TPU_SPOOL_DIR",
        os.path.join(tempfile.gettempdir(),
                     f"trino_tpu_spool_{getattr(os, 'getuid', int)()}"))
    spool_ttl_s: int = _env_int("TRINO_TPU_SPOOL_TTL", 3600)
    # spool backend selection (fte/spool.py make_spool): "local" is the
    # directory tree above; "memory" routes through the object-store
    # code path (fte/objectstore.py) — S3/GCS-shaped put/get/list with
    # bounded retries. Per-query override: SET SESSION spool_backend.
    spool_backend: str = os.environ.get("TRINO_TPU_SPOOL_BACKEND",
                                        "local")
    # object-store spool retry budget: real buckets throw transient
    # 503 SlowDown / connection resets; each spool op gets this many
    # tries with exponential backoff from the initial delay
    objectstore_max_attempts: int = _env_int(
        "TRINO_TPU_OBJECTSTORE_ATTEMPTS", 4)
    objectstore_backoff_s: float = float(os.environ.get(
        "TRINO_TPU_OBJECTSTORE_BACKOFF_S", "0.02"))
    # capacity of EACH bucket of the in-process program cache
    # (exec/progkey.py PROGRAMS): entries are compiled-program
    # closures, cheap to hold, expensive to rebuild — evictions are
    # counted in trino_tpu_jit_cache_evictions_total
    jit_cache_entries: int = _env_int("TRINO_TPU_JIT_CACHE_ENTRIES",
                                      256)
    # compile-amortization subsystem (exec/progkey.py + exec/aot.py +
    # exec/hotshapes.py): hot-shape registry capacity, how many top
    # shapes a joining worker AOT-compiles before advertising itself
    # warm, and the process-wide pre-warm default (per-query recording
    # is additionally gated by the prewarm_enabled session property)
    hot_shape_entries: int = _env_int("TRINO_TPU_HOT_SHAPE_ENTRIES",
                                      128)
    prewarm_top_k: int = _env_int("TRINO_TPU_PREWARM_TOP_K", 8)
    prewarm_enabled: bool = os.environ.get("TRINO_TPU_PREWARM",
                                           "1") == "1"
    # beyond-HBM morsel streaming (exec/streamjoin.py): process-wide
    # default for the stream_chunk_rows session property — 0 means
    # auto (engage on budget breach, capacity derived from the
    # budget); > 0 forces chunking at that row count
    stream_chunk_rows: int = _env_int("TRINO_TPU_STREAM_CHUNK_ROWS", 0)
    # worker-side multi-query runtime (exec/taskexec.py, wired by
    # server/task_worker.py): size of the shared runner pool that
    # time-slices ALL concurrent queries' task splits/chunks on one
    # worker (the reference's task.max-worker-threads); 0 = auto
    # (max(4, 2 x cores)). Every task still has a thread — the pool
    # bounds how many EXECUTE concurrently; the rest wait their
    # fair-share turn at split/chunk boundaries.
    task_runner_threads: int = _env_int("TRINO_TPU_TASK_RUNNERS", 0)
    # worker-local memory budget in bytes governing live task
    # reservations + shared caches: past it the worker sheds cache
    # entries (scan/jit/replicate) before queries feel pressure, and
    # it feeds the BUSY shed signal. 0 disables worker-local
    # governance (the coordinator pool still governs globally).
    worker_memory_bytes: int = _env_int(
        "TRINO_TPU_WORKER_MEMORY_BYTES", 0)
    # graceful degradation: a worker declines NEW task dispatches with
    # a retryable BUSY (HTTP 503) once its open-task count exceeds
    # factor x runner-pool size (or its memory budget is breached) —
    # the stage scheduler's retry/rotation machinery re-places the
    # task on another worker. 0 disables shedding. The factor is the
    # FLOOR; the shed decision itself smooths the open-task count
    # through a time-decayed EMA (time constant below) so a transient
    # dispatch burst does not shed while sustained overload still does.
    busy_shed_factor: int = _env_int("TRINO_TPU_BUSY_SHED_FACTOR", 8)
    # EMA time constant (seconds) for the busy-shed queue-depth
    # smoothing (exec/taskexec.py open_tasks_ema). 0 = no smoothing
    # (the EMA tracks the spot value — the pre-EMA behavior).
    busy_shed_ema_s: float = _env_float("TRINO_TPU_BUSY_SHED_EMA_S",
                                        2.0)
    # OTLP trace export sinks (obs/otlp.py): a JSONL file path and/or
    # an OTLP/HTTP collector endpoint. Empty = export off; the
    # coordinator's GET /v1/trace/{query_id} pull surface works either
    # way. Per-query opt-out: SET SESSION otlp_export = false.
    otlp_file: str = os.environ.get("TRINO_TPU_OTLP_FILE", "")
    otlp_endpoint: str = os.environ.get("TRINO_TPU_OTLP_ENDPOINT", "")
    # per-worker fetch-once cache for replicate exchange edges
    # (stage/exchange.py): every consumer task of a replicated
    # (broadcast) stage output reads the SAME frame 0 — the cache
    # makes that one fetch per worker process instead of one per
    # task. 0 disables.
    replicate_cache_bytes: int = _env_int(
        "TRINO_TPU_REPLICATE_CACHE", 64 << 20)
    # restart-recovery persistence cap (fte/recovery.py): the persist
    # runs on the query thread before FINISHED is client-visible, so
    # an unbounded result would add O(result) latency + a second
    # in-memory copy. Past the cap the query is not restart-
    # recoverable (results_spool_skipped metric). 0 = no cap.
    result_spool_max_bytes: int = _env_int(
        "TRINO_TPU_RESULT_SPOOL_MAX_BYTES", 64 << 20)
    # coordinator result cache (exec/resultcache.py): byte budget for
    # cached final results of deterministic point queries, keyed on
    # canonical program key + split fingerprint and invalidated by
    # connector data version. A repeated identical dashboard query is
    # served with ZERO dispatched tasks. 0 disables the cache outright
    # (the result_cache_enabled session property gates per query).
    result_cache_bytes: int = _env_int(
        "TRINO_TPU_RESULT_CACHE_BYTES", 64 << 20)
    # ragged multi-query batching (exec/taskexec.py RaggedBatcher):
    # cap on COMBINED live rows of co-batched fragments — fragments
    # only coalesce while the sum stays under this bucket (the default
    # for the ragged_batch_max_rows session property)
    ragged_batch_rows: int = _env_int(
        "TRINO_TPU_RAGGED_BATCH_ROWS", 1 << 16)
    # batch-formation window in milliseconds: the first arrival of a
    # canonical shape (the batch leader) waits this long at the
    # quantum boundary for compatible co-batchers before executing;
    # the wait releases the leader's runner slot, so it costs queue
    # time, not pool throughput
    ragged_window_ms: float = _env_float(
        "TRINO_TPU_RAGGED_WINDOW_MS", 3.0)
    # query history + learned statistics (obs/history.py +
    # exec/learnedstats.py): bounded terminal-query record store
    # (JSONL under <spool_dir>/history so it survives coordinator
    # restarts) with a TTL sweep; the learned-stats registry caps its
    # (program key, operator, occurrence) entries and smooths
    # observed selectivity/throughput with this EMA alpha
    history_capacity: int = _env_int("TRINO_TPU_HISTORY_CAPACITY",
                                     1024)
    history_ttl_s: int = _env_int("TRINO_TPU_HISTORY_TTL", 7 * 86400)
    learned_stats_entries: int = _env_int(
        "TRINO_TPU_LEARNED_STATS_ENTRIES", 512)
    learned_stats_alpha: float = _env_float(
        "TRINO_TPU_LEARNED_STATS_ALPHA", 0.2)
    # system.runtime.metrics ring buffer (obs/history.py MetricsRing):
    # how many periodic whole-registry snapshots the coordinator
    # retains, and the minimum seconds between samples (lazy — the
    # first reader past the interval takes the snapshot)
    metrics_ring_slots: int = _env_int("TRINO_TPU_METRICS_RING", 64)
    metrics_ring_interval_s: float = _env_float(
        "TRINO_TPU_METRICS_RING_INTERVAL_S", 15.0)
    # streaming ingestion (trino_tpu/streaming/): durable segment
    # files of the append-only message log. A SIBLING of the spool dir
    # — never inside it — so the spool's TTL sweep can't reap live
    # topics; same uid-scoping rationale as spool_dir (ingested
    # messages transit here).
    stream_dir: str = os.environ.get(
        "TRINO_TPU_STREAM_DIR",
        os.path.join(tempfile.gettempdir(),
                     f"trino_tpu_stream_{getattr(os, 'getuid', int)()}"))
    # default partition count of implicitly-created topics (CREATE
    # TABLE stream.default.t / first ingest into an unknown topic)
    stream_partitions: int = _env_int("TRINO_TPU_STREAM_PARTITIONS", 2)
    # process-wide default for the stream_poll_interval_ms session
    # property: the cadence at which continuous queries re-dispatch
    # their incremental plan (streaming/continuous.py)
    stream_poll_interval_ms: int = _env_int(
        "TRINO_TPU_STREAM_POLL_MS", 500)
    # default allowed event-time lateness (ms) for watermarked
    # windowed continuous queries: watermark = max(ts) - lateness
    stream_lateness_ms: int = _env_int(
        "TRINO_TPU_STREAM_LATENESS_MS", 1000)
    # fsync segment appends (durability vs ingest throughput): off by
    # default — the log survives process kills either way (O_APPEND
    # single-write framing); fsync additionally survives host power
    # loss at a per-append cost
    stream_fsync: bool = os.environ.get("TRINO_TPU_STREAM_FSYNC",
                                        "0") == "1"


CONFIG = EngineConfig()


class MemoryLimitExceeded(Exception):
    """EXCEEDED_LOCAL_MEMORY_LIMIT (spi/StandardErrorCode.java analog):
    a capacity decision would allocate more device memory than the
    query_max_memory_per_node session property allows."""


def reserve_bytes(rows: int, n_lanes: int, limit_bytes: int,
                  what: str) -> int:
    """Check an allocation of rows x n_lanes 8-byte device lanes against
    the per-node query memory limit (memory/ ClusterMemoryManager +
    LocalMemoryContext reservation, collapsed to the single decision
    point that matters in this engine: capacity planning)."""
    est = rows * max(n_lanes, 1) * 8
    if est > limit_bytes:
        raise MemoryLimitExceeded(
            f"Query exceeded per-node memory limit of {limit_bytes} "
            f"bytes ({what} needs ~{est} bytes for {rows} rows x "
            f"{n_lanes} lanes); raise query_max_memory_per_node or "
            "enable spill")
    return est


def capacity_for(n: int, minimum: int | None = None) -> int:
    """Round ``n`` up to a power-of-two capacity bucket.

    Static shapes are mandatory under jit; bucketing keeps the number of
    compiled variants logarithmic in data size (the analog of Trino compiling
    one bytecode class per expression shape, ExpressionCompiler.java:56).
    """
    floor = CONFIG.min_capacity if minimum is None else minimum
    cap = max(int(n), 1)
    bucket = max(floor, 1)
    while bucket < cap:
        bucket <<= 1
    return bucket
