"""Routing and planner (crypto/batch.py VerifiedRowMemo under verify_batch): what
the verified-row memo costs a call: `memo_ms` of the call's two flush records
summed: the votes' flush (digests of 10,000 rows, a look-up that misses, the
insert of the rows that verified) and the commit's answer (digests again, a
look-up that hits). What it saves is the flush the commit would have made
(`flush.wall_ms` of commit-10k.verify-commit). Median over the window's calls;
None where the program's records carry no `memo_ms`."""

import vote_spans


def read(ctx):
    return vote_spans.median_reading(ctx, "memo_ms")
