"""Scheduler (crypto/scheduler.py): from the submit of a run's ticket on the
caller's thread to the start of the flush that takes it on the dispatch
thread: the program's `lane.wait` span. A run over `catchup_max_rows` is
ready at once, so this is the hand-over; a smaller one waits out
`catchup_max_wait`. Median over the whole runs still in the ring."""

import catchup_spans


def read(ctx):
    return catchup_spans.median_sum_ms(ctx, "lane.wait")
