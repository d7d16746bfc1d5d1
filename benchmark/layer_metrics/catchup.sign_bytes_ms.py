"""Entry points (blocksync/reactor.py): the sign bytes of a run, one native
pass a block, all blocks under the program's one `catchup.sign_bytes` span.
Median over the whole runs still in the flight recorder's ring."""

import catchup_spans


def read(ctx):
    return catchup_spans.median_sum_ms(ctx, "catchup.sign_bytes")
