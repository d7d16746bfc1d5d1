"""Routing and planner (crypto/batch.py VerifiedRowMemo): of the rows of the
commit a step made, the share the verified-row memo answered: `memo_hits` over
the rows of the commit's flush record. 100 where the votes' flush left every row
in the memo; 0 where the commit was verified again. Median over the window's
calls; None where a call made no second verify_batch."""

import statistics


def read(ctx):
    xs = [100.0 * (c["flush"].get("commit_memo_hits") or 0) / c["flush"]["commit_rows"]
          for c in ctx.calls if c["flush"].get("commit_rows")]
    return statistics.median(xs) if xs else None
