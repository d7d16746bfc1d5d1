"""Host prep, in a catch-up run's flush: the window sort of all chunks: the
program's `prep.sort` spans in the run's tree. What `prep.sort_ms` is to a
`commit.verify` call. Median over the whole runs still in the ring."""

import catchup_spans


def read(ctx):
    return catchup_spans.median_sum_ms(ctx, "prep.sort")
