"""The recovery ladder's spans in the program's flight recorder
(tendermint_tpu/crypto/batch.py `_ladder_walk`: one `recovery.ladder` a
recovery, one `recovery.check` a combined check of a sub-range, one
`recovery.leaf` a per-signature leaf) put down to the window's FORGED calls
by time, as call_spans.py puts spans down to every call: `c["forged"]` is
the comparison's finding that the call's item holds a bad signature. Read in
the benchmark's process after the window, straight from the ring.

Only the forged calls the ring still covers count (each starts after the
close of every event among the ring's oldest `call_spans.EDGE`), and only
those with a ladder in them. A reader gets the median over them, and None
where there is none: so on a program that writes no such span (the parent
of the change that added them), and in a cell that forges nothing. A median
over every call would be a clean call's, which walks no ladder. There is no
floor of calls, and `call_spans.covered`, whose floor is 30 calls, is not
used: a traced run's window holds fewer of this cell (the profiler's slice
is reduced inside the window: 29 and 48 calls, 3 and 6 of them forged, on
the chip), and one forged call already gives the ladder's counts, which the
split rule fixes.

Also here, since it is read only of forged calls: a forged call's device
time less a clean call's, from the traced slice."""

from __future__ import annotations

import bisect
import statistics

import call_spans
import program_spans

NAMES = ("recovery.ladder", "recovery.check", "recovery.leaf")


def forged_calls(ctx) -> list:
    """Per forged call the ring covers and in which a ladder was walked: its
    `recovery.*` events, in the order they started."""
    got = getattr(ctx, "_recovery_calls", None)
    if got is None:
        forged = {(int(c["start"] * 1e9), int(c["end"] * 1e9))
                  for c in ctx.calls if c.get("forged")}
        ring = [e for e in program_spans.ring() if "t0_ns" in e]
        edge = max((e["t0_ns"] + int(round(e.get("dur_ms", 0.0) * 1e6))
                    for e in ring[:call_spans.EDGE]), default=None)
        events = sorted((e for e in ring if e["name"] in NAMES and "dur_ms" in e),
                        key=lambda e: e["t0_ns"])
        starts = [e["t0_ns"] for e in events]
        got = []
        for t0, t1 in sorted(forged):
            if edge is None or t0 < edge:
                continue
            mine = events[bisect.bisect_left(starts, t0):bisect.bisect_right(starts, t1)]
            if any(e["name"] == "recovery.ladder" for e in mine):
                got.append(mine)
        ctx._recovery_calls = got
    return got


def median_sum_ms(ctx, name: str):
    """Median over the forged calls of the summed duration of their `name`
    spans; None where there is no forged call."""
    calls = forged_calls(ctx)
    if not calls:
        return None
    return statistics.median(sum(e["dur_ms"] for e in c if e["name"] == name) for c in calls)


def median_ladder(ctx, attr: str):
    """Median over the forged calls of a `recovery.ladder` count (`checks`,
    `leaves`, `flushes`, `rows_checked`, `leaf_rows`), summed over the call's
    ladders; None where there is no forged call."""
    calls = forged_calls(ctx)
    if not calls:
        return None
    return statistics.median(sum((e.get("attrs") or {}).get(attr, 0) for e in c
                                 if e["name"] == "recovery.ladder") for c in calls)


def forged_device_ms(ctx):
    """Of the traced slice: median over its forged calls of the device time
    of the call less the median device time of its clean calls; None where
    the slice holds no forged or no clean call."""
    trace = getattr(ctx, "trace", None)
    if not trace:
        return None
    per_call = list(zip(ctx.calls, trace["device_s_per_call"]))
    forged = [s * 1e3 for c, s in per_call if c.get("forged")]
    clean = [s * 1e3 for c, s in per_call if not c.get("forged") and s > 0]
    if not forged or not clean:
        return None
    clean_ms = statistics.median(clean)
    return statistics.median(ms - clean_ms for ms in forged)
