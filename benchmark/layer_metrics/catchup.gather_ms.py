"""Entry points (blocksync/reactor.py): the row loop of _verify_run_batched
over blocks x validators, which gathers keys, signatures and powers and checks
each commit's block id and height: the program's `catchup.gather` span. Median
over the whole runs still in the flight recorder's ring."""

import catchup_spans


def read(ctx):
    return catchup_spans.median_sum_ms(ctx, "catchup.gather")
