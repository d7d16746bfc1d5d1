"""Host prep: the time the dispatch thread stood blocked on the prep pool
(and with it, after the head chunk, possibly the device): the program's
`flush.prep_wait` spans, summed over a call. Median over the whole calls still
in the ring."""

import program_spans


def read(ctx):
    return program_spans.median_sum_ms(ctx, "flush.prep_wait")
