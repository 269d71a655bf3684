"""Terminal bookkeeping on the query thread AFTER the client was
released (events, history record, rings, learned-stats checkpoint):
the engine's ``finish`` root span, per executed query. In a closed
loop it runs under the GIL beside the next query's submit."""

from ._phases import per_query_ms


def read(run):
    return per_query_ms(run, "finish")
