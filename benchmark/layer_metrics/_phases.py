"""Shared by the readers of the engine's phase counters: the spans of
the served path, summed by the program itself into
``trino_tpu_query_phase_seconds{phase=...}`` at ``/metrics`` (one
observation per closed span), read as growth between the window's
start (``run.engine_before``) and its end (``run.engine_after``) and
divided by the queries the window executed (growth of the ``execute``
phase's ``_count``). A program without the family (one older than the
spans) gives ``None`` everywhere, and a run that lacks a metric of its
cell ends without a result (``run.py read_metrics``).
"""

FAMILY = "trino_tpu_query_phase_seconds"


def growth(run, key: str) -> float:
    return run.engine_after.get(key, 0.0) - run.engine_before.get(key, 0.0)


def family_growth(run, name: str):
    """Summed growth of every sample of a labelled counter family, or
    None where the program exports no such family."""
    keys = [k for k in run.engine_after if k.startswith(name + "{")]
    if not keys:
        return None
    return sum(growth(run, k) for k in keys)


def executed(run) -> float:
    """Queries the window executed (0 where the family is absent)."""
    return growth(run, f'{FAMILY}_count{{phase="execute"}}')


def phase_count(run, phase: str) -> float:
    return growth(run, f'{FAMILY}_count{{phase="{phase}"}}')


def phase_seconds(run, *phases: str) -> float:
    """Seconds the window spent in these phases, all queries together;
    a phase that closed no span in the window counts 0."""
    return sum(growth(run, f'{FAMILY}_sum{{phase="{p}"}}') for p in phases)


def per_query_ms(run, *phases: str):
    n = executed(run)
    if n <= 0:
        return None
    return 1e3 * phase_seconds(run, *phases) / n
