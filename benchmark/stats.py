"""The arithmetic of the end-to-end metrics and of a bound, in one place."""

from __future__ import annotations

import math
import statistics


def percentile(values, q: float) -> float:
    """The q-th percentile (0-100) by linear interpolation between closest
    ranks, as numpy's default does."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no values")
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def window_metrics(calls) -> dict:
    """calls: (start_s, end_s, rows) of every call of the window, in order;
    `rows` is the signatures of the call's own item where it came back
    right, and 0 where it did not. The rate is taken over all the work that
    came back right and all the time from the first call's start to the last
    call's end; the latencies are of all calls, right or wrong."""
    if not calls:
        raise ValueError("the window made no call")
    elapsed = calls[-1][1] - calls[0][0]
    walls_ms = [(e - s) * 1e3 for s, e, _ in calls]
    return {
        "sigs_per_s": sum(rows for _, _, rows in calls) / elapsed,
        "verify_ms_p50": percentile(walls_ms, 50),
        "verify_ms_p95": percentile(walls_ms, 95),
        "calls": len(calls),
        "window_s": elapsed,
    }


def spread(values) -> float:
    """The distance between the quartiles as a share of the median, by
    statistics.quantiles(n=4): what a bound is set from."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)
