"""Recovery ladder, on the device: what a forged call's ladder cost the
device, its checks' chunks and its leaf: the device time of a traced forged
call less the median device time of the traced clean calls (the union of the
device operations' intervals inside each call's span, from the profiler's
slice). Median over the slice's forged calls; None where it holds no forged
or no clean call."""

import recovery_spans


def read(ctx):
    return recovery_spans.forged_device_ms(ctx)
