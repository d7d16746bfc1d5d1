"""Host prep: the random coefficients and scalar products, all chunks of a
call together: the program's `prep.scalars` spans. Median over the whole calls
still in the ring."""

import program_spans


def read(ctx):
    return program_spans.median_sum_ms(ctx, "prep.scalars")
