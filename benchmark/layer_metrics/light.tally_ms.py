"""Light client (light/verifier.py verify_adjacent_run): the tally of a run's
valid signatures, header by header by the power of its own set: the program's
`light.tally` span, ONE a run of sequential verification, under the root
`light.verify_run` that the cell's mix states. Median over the whole calls
still in the flight recorder's ring."""

import program_spans


def read(ctx):
    return program_spans.median_sum_ms(ctx, "light.tally")
