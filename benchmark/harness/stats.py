"""The arithmetic of the end-to-end metrics, over the window's records.

A record is one query of the window: its class, its stream, when it was
sent and when its last page came back (seconds on one monotonic clock),
and whether it finished. Only finished queries have a latency; a failed
one counts in ``failed`` and nowhere else.
"""

import math
from dataclasses import dataclass, field
from typing import List, Optional


@dataclass
class Record:
    cls: str
    stream: int
    start_s: float
    end_s: float
    ok: bool
    query_id: str = ""
    rows: Optional[list] = None
    error: str = ""
    due_s: Optional[float] = None     # open loop: when it was due
    params: Optional[tuple] = None    # its substitution parameters
    tags: dict = field(default_factory=dict)

    @property
    def latency_ms(self) -> float:
        """From when the query was due (open loop) or sent (closed)."""
        t0 = self.start_s if self.due_s is None else self.due_s
        return (self.end_s - t0) * 1000.0


def finished(records: List[Record]) -> List[Record]:
    return [r for r in records if r.ok]


def class_means_ms(records: List[Record]) -> dict:
    """class -> sum of its latencies in the window / its count."""
    sums, counts = {}, {}
    for r in finished(records):
        sums[r.cls] = sums.get(r.cls, 0.0) + r.latency_ms
        counts[r.cls] = counts.get(r.cls, 0) + 1
    return {c: sums[c] / counts[c] for c in sorted(sums)}


def class_p50_ms(records: List[Record]) -> dict:
    """class -> the median of its latencies in the window: what a query
    of that class takes as a rule. One stall in the window leaves it
    where it was (the class's mean and the rate carry the stall)."""
    out = {}
    for cls in sorted({r.cls for r in finished(records)}):
        out[cls] = percentile_ms([r for r in records if r.cls == cls], 50)
    return out


def geomean_ms(records: List[Record]) -> Optional[float]:
    """Geometric mean over the classes of each class's mean latency:
    the statistic of TPC-H's power metric (cl. 5.4.1), so a 2x on a
    short class weighs as a 2x on a long one. Every finished query of
    the window is in its class's mean."""
    means = class_means_ms(records)
    if not means:
        return None
    return math.exp(sum(math.log(m) for m in means.values()) / len(means))


def rate_per_s(records: List[Record], t0_s: float) -> Optional[float]:
    """Finished queries per second: each stream's finished queries over
    the time from the window's start to that stream's last answer,
    summed over the streams. With one stream: all queries over all the
    time."""
    total = 0.0
    for s in sorted({r.stream for r in records}):
        mine = [r for r in records if r.stream == s]
        span = max(r.end_s for r in mine) - t0_s
        if span > 0:
            total += len(finished(mine)) / span
    return total or None


def percentile_ms(records: List[Record], q: float) -> Optional[float]:
    """The q-th percentile (0-100) of all finished latencies, by linear
    interpolation between order statistics."""
    lat = sorted(r.latency_ms for r in finished(records))
    if not lat:
        return None
    pos = (len(lat) - 1) * q / 100.0
    lo = int(math.floor(pos))
    hi = min(lo + 1, len(lat) - 1)
    return lat[lo] + (lat[hi] - lat[lo]) * (pos - lo)
