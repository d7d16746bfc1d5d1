"""Entry points (blocksync/reactor.py): the tally of the valid signatures by
power, block by block, and the structural checks' verdicts: the program's
`catchup.tally` span. Median over the whole runs still in the ring."""

import catchup_spans


def read(ctx):
    return catchup_spans.median_sum_ms(ctx, "catchup.tally")
