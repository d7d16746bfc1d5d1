"""Light client (light/client.py _verify_run): the flushes a run of
sequential verification cost, `flushes` on the program's root span
`light.verify_run` (the `flush_count` of the run's accumulator). One is the
least, and what a run is for: a client that verified a header a flush would
read 333 here. Median over the accepted runs of the cell's size still in the
flight recorder's ring; None under 30 of them, or where the program writes
no such root."""

import statistics

import program_spans


def read(ctx):
    xs = []
    for e in program_spans.ring():
        attrs = e.get("attrs") or {}
        if (e["name"] == "light.verify_run" and attrs.get("rows") == ctx.rows
                and attrs.get("verdict") == "accepted" and "flushes" in attrs):
            xs.append(attrs["flushes"])
    return statistics.median(xs) if len(xs) >= program_spans.MIN_CALLS else None
