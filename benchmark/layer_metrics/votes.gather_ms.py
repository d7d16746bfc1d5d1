"""Vote set (types/vote_set.py VoteSet.flush): the loop over the pending votes
that builds the flush's rows (key bytes, signature, key type, `peer:<id>`
source): the program's `votes.gather` span under the root `votes.flush`. Median
over the whole flushes of the cell's size still in the flight recorder's ring."""

import vote_spans


def read(ctx):
    return vote_spans.median_child_ms(ctx, "votes.gather")
