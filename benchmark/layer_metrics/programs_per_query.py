"""Device programs a query dispatches
(``trino_tpu_device_programs_total``, every kind), per executed
query."""

from ._phases import executed, family_growth


def read(run):
    n = executed(run)
    programs = family_growth(run, "trino_tpu_device_programs_total")
    if n <= 0 or programs is None:
        return None
    return programs / n
