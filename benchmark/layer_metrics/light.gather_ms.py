"""Light client (light/verifier.py verify_adjacent_run): the row loop over a
run's headers x validators, each header's for-block rows under the set it
carries, with the commit's structural checks: the program's `light.gather`
span, ONE a run of sequential verification, under the root `light.verify_run`
that the cell's mix states. Median over the whole calls still in the flight
recorder's ring."""

import program_spans


def read(ctx):
    return program_spans.median_sum_ms(ctx, "light.gather")
