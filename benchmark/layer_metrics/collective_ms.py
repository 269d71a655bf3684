"""Exchange: device time in collective operations (``all-to-all*``,
``all-gather*``, ``all-reduce*``, ``reduce-scatter*``,
``collective-permute*`` on the ``XLA Ops`` line) per traced query, mean
over the device planes. EVERY collective of the window: the exchanges'
own, and the small ones that no ``exchange`` span covers (each mesh
program's replicated per-shard count, the fused aggregation's gather
of partial rows); ``exchange_ici_roofline`` takes the repartition's
alone (``_exchange.py``)."""

from ._exchange import op_ns_per_query


def read(run):
    ns = op_ns_per_query(run)
    return None if ns is None else ns / 1e6
