"""From the profiler's trace to numbers.

``load`` turns an ``.xplane.pb`` into plain lists; everything else is
arithmetic over them, checked in ``selfcheck/`` on a small recorded
trace. Times are nanoseconds on the trace's own clock.

A device plane is one named ``/device:TPU:<n>``; its operations are the
events of its ``XLA Ops`` line. Host annotations (the benchmark's own
``TraceAnnotation`` around each query, ``bench_query:<class>:<stream>``)
are on the host plane. Busy time is the UNION of the operations'
intervals, so overlapping operations are not counted twice.
"""

import bisect
import glob
import os

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
QUERY_PREFIX = "bench_query:"
ANCHOR = "bench_anchor"


def load(trace_dir: str) -> dict:
    """{"devices": {plane: [(name, start, dur)]}, "host": [(name, start,
    dur)]} from the newest trace under ``trace_dir``. Host events are
    only the benchmark's own annotations."""
    import jax.profiler
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = jax.profiler.ProfileData.from_file(paths[-1])
    out = {"devices": {}, "host": [], "lines": {}}
    for plane in data.planes:
        lines = list(plane.lines)
        if plane.name.startswith(DEVICE_PREFIX):
            out["lines"][plane.name] = [
                [ln.name, sum(1 for _ in ln.events),
                 [e.name for _i, e in zip(range(3), ln.events)]]
                for ln in lines]
            ops = [ln for ln in lines if ln.name == OPS_LINE]
            out["devices"][plane.name] = [
                (e.name, int(e.start_ns), int(e.duration_ns))
                for ln in ops for e in ln.events]
        elif plane.name.startswith("/host:"):
            for ln in lines:
                for e in ln.events:
                    if e.name.startswith((QUERY_PREFIX, ANCHOR)):
                        out["host"].append(
                            (e.name, int(e.start_ns), int(e.duration_ns)))
    return out


def union(intervals):
    """Sorted disjoint [start, end) covering the same time."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def clip(merged, lo, hi):
    return [[max(s, lo), min(e, hi)] for s, e in merged
            if min(e, hi) > max(s, lo)]


def total(merged) -> int:
    return sum(e - s for s, e in merged)


class Busy:
    """The merged busy intervals of one device plane, with prefix sums:
    ``inside(lo, hi)`` is the busy time within [lo, hi) in O(log n), so
    a window of a thousand queries over a hundred thousand operations
    reduces in seconds."""

    def __init__(self, events):
        self.merged = union((s, s + d) for _n, s, d in events)
        self.starts = [s for s, _e in self.merged]
        self.ends = [e for _s, e in self.merged]
        self.prefix = [0]
        for s, e in self.merged:
            self.prefix.append(self.prefix[-1] + (e - s))

    def before(self, t: int) -> int:
        """Busy time before ``t``."""
        i = bisect.bisect_right(self.starts, t)     # intervals started
        if i == 0:
            return 0
        return self.prefix[i] - max(0, self.ends[i - 1] - t)

    def inside(self, lo: int, hi: int) -> int:
        return self.before(hi) - self.before(lo) if hi > lo else 0


def planes(trace: dict) -> dict:
    return {p: Busy(ev) for p, ev in sorted(trace["devices"].items())}


def device_busy(trace: dict, lo: int, hi: int):
    """(busy seconds averaged over the device planes, merged intervals
    of the first plane by name) inside [lo, hi)."""
    per = planes(trace)
    if not per:
        return 0.0, []
    mean = sum(b.inside(lo, hi) for b in per.values()) / len(per) / 1e9
    return mean, clip(next(iter(per.values())).merged, lo, hi)


def queries(trace: dict):
    """[(class, stream, start, end)] of the benchmark's annotations."""
    out = []
    for name, s, d in trace["host"]:
        if name.startswith(QUERY_PREFIX):
            _p, cls, stream = name.split(":", 2)
            out.append((cls, stream, s, s + d))
    return sorted(out, key=lambda q: q[2])


def window_of(trace: dict):
    """[lo, hi): first query's start to last query's end."""
    qs = queries(trace)
    if not qs:
        raise ValueError("the trace holds no bench_query annotation")
    return min(q[2] for q in qs), max(q[3] for q in qs)


def busy_per_query(trace: dict):
    """{class: [busy ns of each query]}: the device-busy time inside
    each query's own annotation. Sound only where queries do not
    overlap, one stream: the caller decides."""
    per = planes(trace)
    out = {}
    for cls, _stream, lo, hi in queries(trace):
        busy = sum(b.inside(lo, hi) for b in per.values()) / max(len(per), 1)
        out.setdefault(cls, []).append(busy)
    return out


def top_ops(trace: dict, lo: int, hi: int, n: int = 10, width: int = 200):
    """[[name, seconds]]: the operations that took most device time, by
    their full names in the trace; a name is cut to ``width`` characters
    in what is returned (a fusion's operand list can run to thousands)."""
    sums = {}
    for events in trace["devices"].values():
        for name, s, d in events:
            if s >= lo and s + d <= hi:
                sums[name] = sums.get(name, 0) + d
    planes_n = max(len(trace["devices"]), 1)
    return [[k[:width], v / 1e9 / planes_n] for k, v in
            sorted(sums.items(), key=lambda kv: -kv[1])[:n]]


class Spans:
    """Labelled [start, end) spans that do not nest (queries of one
    stream; the engine's root spans): ``at(t)`` is the label of the span
    open at ``t`` or None, in O(log n)."""

    def __init__(self, spans):
        self.spans = sorted(spans)
        self.starts = [s for s, _e, _l in self.spans]

    def at(self, t: int):
        i = bisect.bisect_right(self.starts, t) - 1
        # overlapping spans (two streams): look a few back
        for j in range(i, max(i - 4, -1), -1):
            s, e, lab = self.spans[j]
            if s <= t < e:
                return lab
        return None

    def edges(self):
        return [t for s, e, _l in self.spans for t in (s, e)]


def idle_gaps(trace: dict, lo: int, hi: int, label, n: int = 10,
              cuts=()):
    """[[label, seconds]]: the idle time of the first device plane inside
    [lo, hi), by what the host was doing. Each gap between device
    operations is split at ``cuts`` (the times at which the host's state
    changes: a query or an engine span starts or ends), each piece is
    labelled by ``label(start, end)``, and pieces of one label are
    summed; the longest first."""
    _mean, merged = device_busy(trace, lo, hi)
    edges = [lo] + [t for iv in merged for t in iv] + [hi]
    cuts = sorted(c for c in set(cuts) if lo < c < hi)
    sums = {}
    for s, e in zip(edges[0::2], edges[1::2]):
        inner = cuts[bisect.bisect_right(cuts, s):bisect.bisect_left(cuts, e)]
        for a, b in zip([s] + inner, inner + [e]):
            if b > a:
                key = label(a, b)
                sums[key] = sums.get(key, 0) + (b - a)
    return [[k, v / 1e9] for k, v in
            sorted(sums.items(), key=lambda kv: -kv[1])[:n]]
