"""Entry points (types/validator_set.py verify_commit): the commit a step made,
checked again as the next block's LastCommit gets it and answered from the
verified-row memo: the program's `commit.verify` root (gather, sign bytes, the
memo's pass, tally). Median over the accepted roots of the cell's size still in
the flight recorder's ring."""

import vote_spans


def read(ctx):
    return vote_spans.median_root_ms(ctx, "commit.verify", verdict="accepted")
