"""Device-busy time per query, from the trace; one stream only (with
more, the queries' annotations overlap and share the device)."""

from harness import trace as tr


def busy_ns(run):
    """{class: [device-busy ns of each of its queries]} or {}."""
    if run.trace is None or run.streams != 1:
        return {}
    return tr.busy_per_query(run.trace)


def class_busy_ms(run):
    """{class: mean device-busy ms of its queries} or {}."""
    return {cls: sum(ns) / len(ns) / 1e6
            for cls, ns in busy_ns(run).items() if ns}
