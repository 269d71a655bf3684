"""Literals a query binds to its programs as arguments: growth of
``trino_tpu_program_literal_args_total`` (every program kind; the
``args`` attr of each ``dispatch`` span, the slots of a canonical
program that take the query's literals) over the queries the window
executed. 0.0 where every program of the window bakes its literals; a
program without the counter (one that bakes them all) gives ``None``."""

from ._phases import executed, family_growth


def read(run):
    n = executed(run)
    args = family_growth(run, "trino_tpu_program_literal_args_total")
    if n <= 0 or args is None:
        return None
    return args / n
