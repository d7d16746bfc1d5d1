"""Recovery ladder (crypto/batch.py _ladder_walk, the device's arm
_bisect_recover): the combined checks of sub-ranges a forged call's ladder
made, summed: its `recovery.check` spans (at 10,000 rows on the chunk bucket
each check runs a whole 24,576-lane chunk, whatever its range). Median over
the window's forged calls the ring still covers; None where there is none,
or on a program that writes no such span."""

import recovery_spans


def read(ctx):
    return recovery_spans.median_sum_ms(ctx, "recovery.check")
