"""Vote set (types/vote_set.py VoteSet.flush over types/canonical.py
vote_sign_bytes_many): the canonical sign bytes of a step's votes in one batched
pass: the program's `votes.sign_bytes` span under the root `votes.flush`. Median
over the whole flushes of the cell's size still in the flight recorder's ring."""

import vote_spans


def read(ctx):
    return vote_spans.median_child_ms(ctx, "votes.sign_bytes")
