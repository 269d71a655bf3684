"""Shared by the readers of the engine's root spans."""


def per_query(run):
    """[(latency ms, {root span: ms})] of the traced queries."""
    out = []
    for r in run.records:
        spans = run.spans.get(r.query_id)
        if r.ok and spans:
            out.append((r.latency_ms,
                        {n: (e - s) / 1e6 for n, (s, e) in spans.items()}))
    return out
