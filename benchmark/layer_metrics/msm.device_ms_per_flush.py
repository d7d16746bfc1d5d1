"""Kernels (ops/msm_jax.py, ops/pallas_msm.py): the time the device was busy
inside one call, from the profiler's trace: the union of the device
operations' intervals that fall in the call's span. Median over the traced
calls."""

import statistics


def read(ctx):
    xs = [s * 1e3 for s in ctx.trace["device_s_per_call"] if s > 0]
    return statistics.median(xs) if xs else None
