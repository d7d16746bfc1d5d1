"""Light client (light/verifier.py verify_adjacent_run): the host checks of a
run's headers, header by header against the one before it (adjacency, expiry,
the header's and the set's hash, the NextValidatorsHash link): the program's
`light.header_checks` span, ONE a run of sequential verification, under the
root `light.verify_run` that the cell's mix states. Median over the whole
calls still in the flight recorder's ring."""

import program_spans


def read(ctx):
    return program_spans.median_sum_ms(ctx, "light.header_checks")
