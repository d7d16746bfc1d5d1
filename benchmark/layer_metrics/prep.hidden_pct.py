"""Host prep: the share of a flush's prep that ran while the device was
busy with an earlier chunk (the program's own windowed accounting). A flush
of one chunk has nothing to hide behind and reports nothing."""

import statistics


def read(ctx):
    xs = [100.0 * c["flush"]["prep_overlap_ms"] / c["flush"]["prep_ms"]
          for c in ctx.calls
          if c["flush"]["prep_overlap_ms"] is not None and c["flush"]["prep_ms"]]
    return statistics.median(xs) if xs else None
