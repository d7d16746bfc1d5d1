"""Entry points (every span of the program's flight recorder): what of a call
no span of the program names: the call's wall less the union, clipped to the
call, of every span in the ring but a root that has children. What is left is
the driver's own loop and whatever the program does outside its spans. Median
over the window's calls the ring still covers."""

import call_spans


def read(ctx):
    return call_spans.median_unnamed_ms(ctx)
