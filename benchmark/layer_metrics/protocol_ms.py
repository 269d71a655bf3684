"""Client protocol and coordinator: mean over queries of the client's
latency minus the engine's root spans (parse, plan, optimize, execute)
of that query id. Host clock."""

from ._spans import per_query


def read(run):
    rows = per_query(run)
    if not rows:
        return None
    return sum(lat - sum(s.values()) for lat, s in rows) / len(rows)
