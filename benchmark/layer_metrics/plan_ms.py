"""Parse, plan, optimize: mean over queries of those three root spans."""

from ._spans import per_query


def read(run):
    rows = per_query(run)
    if not rows:
        return None
    return sum(s.get("parse", 0) + s.get("plan", 0) + s.get("optimize", 0)
               for _lat, s in rows) / len(rows)
