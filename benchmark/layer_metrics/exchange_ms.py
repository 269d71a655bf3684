"""Exchange: wall time of the ``exchange`` spans (each around one
repartition, broadcast or gather of the mesh executor: its count
program, the blocking read of the counts and the program that moves
the rows), per executed query. ``None`` where the program has no such
phase."""

from ._phases import FAMILY, per_query_ms


def read(run):
    if f'{FAMILY}_count{{phase="exchange"}}' not in run.engine_after:
        return None
    return per_query_ms(run, "exchange")
