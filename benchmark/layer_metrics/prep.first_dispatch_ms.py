"""Host prep: from the start of `verify_batch` to the end of the call's first
`dispatch` span: everything the host does before the device has anything to
do. Median over the whole calls still in the ring."""

import program_spans


def read(ctx):
    return program_spans.median_first_dispatch_ms(ctx)
