"""Entry points (types/validator_set.py): the row loop of verify_commit, which
gathers keys, signatures and powers: the program's `commit.gather` span.
Median over the whole calls still in the flight recorder's ring."""

import program_spans


def read(ctx):
    return program_spans.median_sum_ms(ctx, "commit.gather")
