"""Light client (light/verifier.py verify_adjacent_run): the sign bytes of a
run's rows, one native pass a commit (native.vote_sign_bytes): the program's
`light.sign_bytes` span, ONE a run of sequential verification, under the root
`light.verify_run` that the cell's mix states. Median over the whole calls
still in the flight recorder's ring."""

import program_spans


def read(ctx):
    return program_spans.median_sum_ms(ctx, "light.sign_bytes")
