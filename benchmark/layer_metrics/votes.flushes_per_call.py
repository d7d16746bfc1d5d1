"""Scheduler (crypto/scheduler.py votes lane under VoteSet.flush): the
verify_batch calls of one step that verified rows, on the device or the host
(those answered whole from the memo are not flushes). One is the least: the
step's votes in ONE flush, the commit from the memo; two would mean the commit
was verified again. Median over the window's calls."""

import vote_spans


def read(ctx):
    return vote_spans.median_reading(ctx, "device_flushes")
