"""Host prep: digit expansion and window sort, all chunks of a call
together: the program's `prep.sort` spans. Median over the whole calls still
in the ring."""

import program_spans


def read(ctx):
    return program_spans.median_sum_ms(ctx, "prep.sort")
