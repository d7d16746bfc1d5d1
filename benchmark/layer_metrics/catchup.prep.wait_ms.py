"""Host prep, in a catch-up run's flush: the time the dispatch thread stood
blocked on the prep pool: the program's `flush.prep_wait` spans in the run's
tree, summed. What `prep.wait_ms` is to a `commit.verify` call. Median over
the whole runs still in the ring."""

import catchup_spans


def read(ctx):
    return catchup_spans.median_sum_ms(ctx, "flush.prep_wait")
