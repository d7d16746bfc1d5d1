"""Host prep under a streamed flush (crypto/batch.py
_verify_batch_rlc_streamed): the share of a flush's host prep that ran while
the device was busy with an earlier chunk, the flush record's
`prep_overlap_ms` over its `prep_ms`. Only a flush of several chunks can make
it more than 0: the first chunk's prep always runs with the device idle, so
with three chunks two thirds is the most. Median over the window's calls."""

import statistics


def read(ctx):
    xs = [100.0 * c["flush"]["prep_overlap_ms"] / c["flush"]["prep_ms"] for c in ctx.calls
          if c["flush"].get("prep_overlap_ms") is not None and c["flush"].get("prep_ms")]
    return statistics.median(xs) if xs else None
