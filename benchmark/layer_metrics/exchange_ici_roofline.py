"""Exchange: the repartition's share of the interconnect's roofline.
Both sides count the SAME thing, the hash and range repartitions
(``_exchange.py`` says why only they can be told apart in the trace).
Least time = the live bytes the ``repartition`` exchanges moved per
query (the program's counter, so the same whatever implements the
exchange) x (n-1)/n (the share that leaves a chip when rows spread
evenly over n chips) / n chips / one chip's interconnect peak. Over it:
the device time of the ``all-to-all*`` operations, which one program
issues, the one that moves those rows. It sends padded buffers of at
least the live bytes, so the share cannot pass 100.

The peak (``peaks_ici.json``) is ALL of a chip's links as the source
quotes them (four on a v5e); on a 2x2 host a chip has two neighbours,
so about half of it can carry a repartition there and an exchange at
the wire's speed reads about 50."""

import json
import os

from ._exchange import REPARTITION_OPS, bytes_per_query, op_ns_per_query

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def ici_bytes_per_s(device_kind: str):
    with open(os.path.join(HERE, "peaks_ici.json")) as f:
        entry = json.load(f)["devices"].get(device_kind)
    return None if entry is None else float(entry["ici_gbit_per_s"]) / 8 * 1e9


def read(run):
    moved = bytes_per_query(run, kind="repartition")
    busy_ns = op_ns_per_query(run, REPARTITION_OPS)
    n = int(run.device.get("count", 0))
    peak = ici_bytes_per_s(run.device.get("kind", ""))
    if not moved or not busy_ns or n < 2 or peak is None:
        return None
    least_s = moved * (n - 1) / n / n / peak
    return 100.0 * least_s / (busy_ns / 1e9)
