"""Vote set (types/vote_set.py VoteSet.make_commit): the commit built from a
step's counted precommits, one CommitSig a validator: the program's
`votes.make_commit` span, a root of its own after the flush. Median over the
spans of the cell's size still in the flight recorder's ring."""

import vote_spans


def read(ctx):
    return vote_spans.median_root_ms(ctx, "votes.make_commit")
