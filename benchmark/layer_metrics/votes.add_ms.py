"""Vote set (types/vote_set.py add_vote under consensus/round_state.py
HeightVoteSet): the wall of a step's add_vote calls, one a precommit, each with
the peer that brought it: structural checks, the duplicate look-up, the
pending queue. No span a vote (a span a vote would roll the recorder's ring
over in half a call): the driver's own clock around its loop, carried in its
reading as `add_ms`. Median over the window's calls."""

import vote_spans


def read(ctx):
    return vote_spans.median_reading(ctx, "add_ms")
