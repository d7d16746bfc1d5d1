"""Routing and planner: the share of the lanes sent to the device that were
padding. Lanes sent = padding + two a row + one base-point lane a chunk, all
from the flush record."""

import statistics


def read(ctx):
    xs = []
    for c in ctx.calls:
        f = c["flush"]
        if f["padding_lanes"] is None or not f["rows"]:
            continue
        lanes = f["padding_lanes"] + 2 * f["rows"] + (f["chunks"] or 1)
        xs.append(100.0 * f["padding_lanes"] / lanes)
    return statistics.median(xs) if xs else None
