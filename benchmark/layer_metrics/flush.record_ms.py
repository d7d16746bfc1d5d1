"""Routing and planner (crypto/batch.py): the body of the flush record
(`libs/trace.record_flush`: metrics, SLO feed, last-flush table), which runs
after the flush's total has closed: the program's `flush.record` span. Median
over the whole calls still in the ring."""

import program_spans


def read(ctx):
    return program_spans.median_sum_ms(ctx, "flush.record")
