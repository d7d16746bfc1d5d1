"""Light client (light/client.py, around the run `light.verify_run`): what a
call spends outside its runs: the store's reads and decodes (`light.load`),
the target's own checks (`light.target_checks`), the witnesses
(`light.witness`) and the target's save and prune (`light.save`), summed over
a call. Median over the window's calls the ring still covers."""

import call_spans

AROUND = {"light.load", "light.target_checks", "light.witness", "light.save"}


def read(ctx):
    return call_spans.median_sum_ms(ctx, AROUND)
