"""The comparison that decides ``correct``: one served result against
the reference's rows for its query class.

Keys, counts, dates and strings are compared exactly; every DOUBLE cell
by its relative gap to the reference. Nothing is raised: the numbers
are returned, and the harness holds each to its limit.
"""

import math


def gaps(got, want):
    """``(exact_mismatches, max_rel_err)`` of one result. A missing or
    surplus row, a row of the wrong width, a non-number where a DOUBLE
    is due and a NaN each count as one exact mismatch."""
    mismatches = abs(len(got) - len(want))
    worst = 0.0
    for g, w in zip(got, want):
        if len(g) != len(w):
            mismatches += 1
            continue
        for a, b in zip(g, w):
            if isinstance(b, float):
                if isinstance(a, bool) or not isinstance(a, (int, float)) \
                        or math.isnan(a):
                    mismatches += 1
                else:
                    worst = max(worst, abs(a - b) / max(abs(b), 1e-300))
            elif a != b:
                mismatches += 1
    return mismatches, worst
