"""Host prep, in a catch-up run's flush: precheck and challenge hashing, all
chunks of the run together: the program's `prep.hash` spans (on the prep
worker) in the run's tree. What `prep.hash_ms` is to a `commit.verify` call.
Median over the whole runs still in the ring."""

import catchup_spans


def read(ctx):
    return catchup_spans.median_sum_ms(ctx, "prep.hash")
