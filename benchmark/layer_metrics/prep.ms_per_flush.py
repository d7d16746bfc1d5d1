"""Host prep (native/batchhost.c, _prep_stream_chunk): hashing, scalars and
window sort of one flush, all chunks together. Median of the flush record's
prep span."""

import statistics


def read(ctx):
    xs = [c["flush"]["prep_ms"] for c in ctx.calls if c["flush"]["prep_ms"] is not None]
    return statistics.median(xs) if xs else None
