"""From the end of ``execute`` to the answer on the socket: the
engine's ``fetch`` (device-to-host fetch of the rows), ``persist``
(recovery spool, where one is configured) and ``respond`` (payload,
JSON, socket write) root spans, per executed query."""

from ._phases import per_query_ms


def read(run):
    return per_query_ms(run, "fetch", "persist", "respond")
