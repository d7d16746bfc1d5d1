"""Host runtime (the interpreter's garbage collector, timed by the flight
recorder's hook in tendermint_tpu/libs/trace.py): the collector's pauses in a
call, whichever generation and wherever they land: the `gc_ms` stamp of the
last root span closed before the call's end, less that of the last root
closed before its start. Mean over the window's calls the ring still covers:
a full collection lands in one call of several, and a median would hide it.
None where no root carries the stamp (a program without the hook)."""

import call_spans


def read(ctx):
    return call_spans.mean_gc_ms(ctx)
