"""Host prep: precheck and challenge hashing, all chunks of a call together:
the program's `prep.hash` spans (on the prep worker). Median over the whole
calls still in the ring."""

import program_spans


def read(ctx):
    return program_spans.median_sum_ms(ctx, "prep.hash")
