"""Recovery ladder (crypto/batch.py _ladder_walk, leaf _persig_flush): the
per-signature flush that names the bad row at the bottom of a forged call's
ladder: its `recovery.leaf` spans, summed. Median over the window's forged
calls the ring still covers; None where there is none, or on a program that
writes no such span."""

import recovery_spans


def read(ctx):
    return recovery_spans.median_sum_ms(ctx, "recovery.leaf")
