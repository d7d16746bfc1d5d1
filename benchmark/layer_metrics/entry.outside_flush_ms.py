"""Entry points (types/validator_set.py): what verify_commit spends outside
the flush, i.e. row gathering, sign bytes and the tally. Median over the
window's calls of (wall of the call - the flush record's total)."""

import statistics


def read(ctx):
    xs = [(c["end"] - c["start"]) * 1e3 - c["flush"]["total_ms"]
          for c in ctx.calls if c["flush"]["total_ms"] is not None]
    return statistics.median(xs) if xs else None
