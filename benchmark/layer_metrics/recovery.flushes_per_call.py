"""Recovery ladder (crypto/batch.py _ladder_walk): the flushes a forged
call's recovery took after the failed combined check, its checks and its
leaves: `recovery_flushes` of the call's flush reading. Median over every
forged call of the window (read from the calls, not the ring, so a program
without the ladder's spans reads too); None where no call was forged."""

import statistics


def read(ctx):
    xs = [c["flush"].get("recovery_flushes") or 0 for c in ctx.calls if c.get("forged")]
    return statistics.median(xs) if xs else None
