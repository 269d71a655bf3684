"""Shared by the readers of the mesh executor's exchange: the program's
own counters of what its exchanges moved
(``trino_tpu_mesh_exchange_bytes_total{kind}``: LIVE rows times the widths
of the lanes sent, whatever implements the exchange and however it
pads), and the collective operations of the device trace. A program
without the counters (one older than the mesh cell) or an untraced run
gives ``None`` everywhere: the metric is left out of the line.

What the trace can tell apart. ``harness/trace.py load`` keeps the
``XLA Ops`` line alone, so an operation is known by its name, not by
the program it ran in. ``all-to-all*`` is issued by ONE program of the
engine, the repartition's ``spmd_exchange`` (its lanes and its
per-destination counts). ``all-gather*`` is issued by the broadcast,
but also by every mesh program that returns its per-shard count
replicated (scan, join count, apply) and by the fused aggregation's
gather of partial rows, under no ``exchange`` span: a reader that sets
time against the bytes of the ``exchange`` counters therefore takes
``all-to-all*`` against the ``repartition`` kind, and nothing else.
"""

from harness import trace as tr

from ._phases import executed, family_growth, growth

BYTES = "trino_tpu_mesh_exchange_bytes_total"
COLLECTIVES = ("all-to-all", "all-gather", "all-reduce", "reduce-scatter",
               "collective-permute")
REPARTITION_OPS = ("all-to-all",)


def bytes_per_query(run, kind=None):
    """Live bytes the window's exchanges moved, per executed query:
    every kind, or the one named."""
    moved = family_growth(run, BYTES)
    n = executed(run)
    if moved is None or n <= 0:
        return None
    if kind is not None:
        moved = growth(run, f'{BYTES}{{kind="{kind}"}}')
    return moved / n


def is_op(name: str, prefixes) -> bool:
    """Whether the trace event ``name`` (an HLO instruction's text,
    ``%name = shape opcode(operands), ...``, or its name alone) is one
    of these operations. By the opcode where the text has it; else by
    the instruction's name, which XLA makes from the opcode
    (``%all-gather.2``) unless the front end named it: jax's
    ``all_to_all`` arrives as ``%all_to_all.21``."""
    if any(f" {p}(" in name or f" {p}-start(" in name
           or f" {p}-done(" in name for p in prefixes):
        return True
    head = name.lstrip("%").split(" ", 1)[0].replace("_", "-")
    return head.startswith(tuple(prefixes))


def traced_queries(run) -> int:
    return len(tr.queries(run.trace)) if run.trace is not None else 0


def op_ns_per_query(run, prefixes=COLLECTIVES):
    """Device time of the operations whose names start with one of
    ``prefixes`` inside the traced window (union of their intervals on
    each device plane, mean over the planes), per traced query."""
    n = traced_queries(run)
    if n == 0 or not run.trace["devices"]:
        return None
    lo, hi = run.trace_window
    per_plane = [
        tr.Busy([e for e in events if is_op(e[0], prefixes)]).inside(lo, hi)
        for events in run.trace["devices"].values()]
    return sum(per_plane) / len(per_plane) / n
