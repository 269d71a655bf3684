"""From the POST's arrival to the query thread's first line: the
engine's ``submit`` (body read, session, id, registration) and
``queued`` (group selection, admission wait, thread start) root spans,
per executed query."""

from ._phases import per_query_ms


def read(run):
    return per_query_ms(run, "submit", "queued")
