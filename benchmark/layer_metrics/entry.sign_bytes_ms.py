"""Entry points: building the canonical sign bytes of every row
(`commit.vote_sign_bytes_many`): the program's `commit.sign_bytes` span.
Median over the whole calls still in the ring."""

import program_spans


def read(ctx):
    return program_spans.median_sum_ms(ctx, "commit.sign_bytes")
