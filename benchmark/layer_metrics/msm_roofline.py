"""Kernels: the least time the chip could take for one flush's work (work.py:
bytes against the HBM peak, field multiplications as int8 products against
the int8 peak, whichever is longer) over the device time of one flush. It
bounds a claim; the chip publishes no peak for 32-bit vector work, so it
does not rank a kernel."""

import statistics


def read(ctx):
    xs = [s for s in ctx.trace["device_s_per_call"] if s > 0]
    if not xs or ctx.peaks is None:
        return None
    least, _ = ctx.work.least_seconds(ctx.rows, ctx.peaks)
    return 100.0 * least / statistics.median(xs)
