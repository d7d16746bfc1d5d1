"""Routing and planner (crypto/batch.py VerifiedRowMemo.digest_rows under
verify_batch): the memo's digests of a call's rows, its two passes summed:
the program's `memo.digest` spans under the roots `votes.flush` (the votes'
flush) and `commit.verify` (the commit answered from the memo). The rest of
`votes.memo_ms` is the look-up and the insert. Median over the window's calls
the ring still covers."""

import call_spans


def read(ctx):
    return call_spans.median_sum_ms(ctx, {"memo.digest"}, roots={"votes.flush", "commit.verify"})
