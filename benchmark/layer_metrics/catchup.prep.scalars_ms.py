"""Host prep, in a catch-up run's flush: the RLC scalars of all chunks: the
program's `prep.scalars` spans in the run's tree. What `prep.scalars_ms` is to
a `commit.verify` call. Median over the whole runs still in the ring."""

import catchup_spans


def read(ctx):
    return catchup_spans.median_sum_ms(ctx, "prep.scalars")
