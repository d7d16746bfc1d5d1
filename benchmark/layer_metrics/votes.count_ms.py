"""Vote set (types/vote_set.py VoteSet.flush): the loop that counts the verified
votes one by one (`_add_verified`: the tally by block, the 2/3 majority,
conflicts) and names the failed ones: the program's `votes.count` span under the
root `votes.flush`. Median over the whole flushes of the cell's size still in
the flight recorder's ring."""

import vote_spans


def read(ctx):
    return vote_spans.median_child_ms(ctx, "votes.count")
