"""Routing and planner (crypto/provenance.py under verify_batch): the
provenance scorer over a call's tagged rows: the quarantine look-up and each
source's verdicts recorded, the program's `provenance.score` spans summed
over a call. Median over the window's calls the ring still covers."""

import call_spans


def read(ctx):
    return call_spans.median_sum_ms(ctx, {"provenance.score"})
