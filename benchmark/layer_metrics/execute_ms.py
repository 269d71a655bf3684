"""Execution as the engine sees it: mean over queries of the ``execute``
root span (dispatch, device work, host syncs, result assembly)."""

from ._spans import per_query


def read(run):
    rows = per_query(run)
    if not rows:
        return None
    return sum(s.get("execute", 0) for _lat, s in rows) / len(rows)
