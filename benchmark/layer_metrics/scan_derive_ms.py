"""Deriving the lanes of a pushed-down constraint met with fresh
literals from the table's resident base lanes: the ``scan_derive``
spans (one program, its dispatch and the one read of the rows it kept,
exec/scanderive.py), per executed query. 0.0 where the window derived
nothing; a program without the span (one that refills every fresh
constraint, ``scan_fill``) gives ``None``."""

from ._phases import FAMILY, per_query_ms


def read(run):
    if not any(k.startswith(f'{FAMILY}_count{{phase="scan_derive"}}')
               for k in run.engine_after):
        return None
    return per_query_ms(run, "scan_derive")
