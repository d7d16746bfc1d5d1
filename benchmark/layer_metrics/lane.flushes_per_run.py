"""Scheduler (crypto/scheduler.py): how many verify_batch calls the lane made
for one run: the `flushes` attribute of the program's `lane.flush` spans,
summed over a run's tree. One is the least. Median over the whole runs still
in the ring."""

import catchup_spans


def read(ctx):
    return catchup_spans.median_sum_attr(ctx, "lane.flush", "flushes")
