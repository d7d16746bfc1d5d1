"""Light client (light/client.py _verify_run, light/store.py): the verified
headers of a run saved to the store in order, one JSON encoding of a light
block a header: the program's `light.store` span, ONE a run of sequential
verification, under the root `light.verify_run` that the cell's mix states.
Median over the whole calls still in the flight recorder's ring."""

import program_spans


def read(ctx):
    return program_spans.median_sum_ms(ctx, "light.store")
