"""Host prep, in a catch-up run's flush: from the start of the run's
`verify_batch` to the end of its first `dispatch` span: everything the host
does before the device has anything to do. What `prep.first_dispatch_ms` is to
a `commit.verify` call. Median over the whole runs still in the ring."""

import catchup_spans


def read(ctx):
    return catchup_spans.median_first_dispatch_ms(ctx)
