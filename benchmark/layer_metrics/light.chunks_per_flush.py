"""Light client over the flush planner (crypto/batch.py
_verify_batch_rlc_streamed): the planner chunks the call's flush was sent in,
`chunks` of the flush record. Three for 33,300 rows on the 24,576-lane
bucket; one would mean the run's rows never went over the planner's budget.
Median over the window's calls."""

import statistics


def read(ctx):
    xs = [c["flush"]["chunks"] for c in ctx.calls if c["flush"].get("chunks") is not None]
    return statistics.median(xs) if xs else None
