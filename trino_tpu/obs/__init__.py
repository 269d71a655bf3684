"""Telemetry subsystem: metrics registry + query trace spans.

Reference parity: the reference treats observability as a first-class
subsystem — always-on QueryStats/OperatorStats
(operator/OperatorStats.java), JMX metrics exported per component
(io.airlift.stats), and the /v1/query detail API feeding the web UI.
Here the same three layers exist TPU-first:

- ``obs.metrics``: process-wide counters/gauges/histograms with
  Prometheus text exposition (GET /metrics on the coordinator and the
  task worker) — the JMX/MBean analog.
- ``obs.trace``: a per-query DISTRIBUTED span tree over the whole
  served life of a query (roots submit -> queued -> parse -> plan ->
  optimize -> execute -> fetch -> persist -> finish, respond from the
  HTTP thread; under execute: jit_trace vs dispatch per named
  program, host_read per blocking device-to-host read, scan_fill) —
  every span carries a real 128-bit-trace/64-bit-span identity, W3C
  ``traceparent`` context propagates into worker task payloads, and
  worker subtrees merge back id-preserving. Spans are also
  ``tpusql:<name>`` annotations in a profiler session (one clock with
  the device trace) and feed the phase counters of ``obs.metrics``
  through one hook. On a tensor runtime compilation/dispatch
  overheads dominate (PAPERS.md "Query Processing on Tensor
  Computation Runtimes"), so trace-vs-execute separation is the single
  most important measurement the JVM engine never needed. A span
  times the host; device time is read on the device trace, except
  under EXPLAIN ANALYZE, whose ``device_execute`` spans carry
  ``device_ms``: the host clock from dispatch to outputs ready, an
  upper bound on device time.
- ``obs.otlp``: stdlib-only OTLP/JSON export of finished traces
  (ResourceSpans shape; file + HTTP sinks, plus the coordinator's
  GET /v1/trace/{query_id} pull surface).
- rich ``NodeStats`` + the distributed rollup live with the executor
  (exec/executor.py, exec/remote.py): workers report per-node stats in
  task results and the coordinator merges them per stage.
"""

from .metrics import METRICS, MetricsRegistry, observe_span
from .trace import QueryTrace, Span


def adopt_or_mint(session, mint: bool, query_id: str = ""):
    """(trace, adopted) for a runner about to execute on ``session``:
    a served query's trace is born in ``QueryTracker.submit`` and rides
    the Session — the runner ADOPTS it (its owner names and exports
    it). A runner used directly mints its own when ``mint`` (it
    collects stats: tracing is cheap but not free, a span per jitted
    dispatch, so the no-telemetry path stays trace-less), else runs
    untraced (None)."""
    trace = session.trace
    if trace is not None:
        return trace, True
    if mint:
        return QueryTrace(query_id, on_close=observe_span), False
    return None, False


__all__ = ["METRICS", "MetricsRegistry", "QueryTrace", "Span",
           "adopt_or_mint", "observe_span"]
