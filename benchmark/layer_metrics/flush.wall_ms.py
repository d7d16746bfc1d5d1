"""Routing and planner (crypto/batch.py): the flush's own wall, from the
flight recorder's flush record (host clock inside the program). Median."""

import statistics


def read(ctx):
    xs = [c["flush"]["total_ms"] for c in ctx.calls if c["flush"]["total_ms"] is not None]
    return statistics.median(xs) if xs else None
