"""Vote set (types/vote_set.py): the time a step's first vote waits in the
deferred queue before the flush that takes it, the batching delay the
deferred path adds to every vote: the program's `votes.pending` span under
the root `votes.flush`. The program's twin of `votes.add_ms`. Median over the
whole flushes of the cell's size still in the flight recorder's ring."""

import vote_spans


def read(ctx):
    return vote_spans.median_child_ms(ctx, "votes.pending")
