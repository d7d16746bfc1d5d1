"""Recovery ladder (crypto/batch.py _ladder_walk): the rows the combined
checks of a forged call's ladder took, summed: `rows_checked` on its
`recovery.ladder` span (the record's `recovery_rows_checked`). The split
rule sets it from the bad row's place: 20,752 at row 5,000 of 10,000. Median
over the window's forged calls the ring still covers; None where there is
none, or on a program that writes no such span."""

import recovery_spans


def read(ctx):
    return recovery_spans.median_ladder(ctx, "rows_checked")
