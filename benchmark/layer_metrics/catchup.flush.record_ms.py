"""Routing and planner, in a catch-up run's flush: the body of the flush
record, which runs after the flush's total has closed: the program's
`flush.record` span in the run's tree. What `flush.record_ms` is to a
`commit.verify` call. Median over the whole runs still in the ring."""

import catchup_spans


def read(ctx):
    return catchup_spans.median_sum_ms(ctx, "flush.record")
