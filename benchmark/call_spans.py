"""Spans of the program's flight recorder (tendermint_tpu/libs/trace.py) put
down to the window's calls by time, for the per-layer readers that divide a
call's whole wall, roots and all: `ctx.calls` start and end on
`time.perf_counter`, the clock of the ring's `t0_ns`. Read in the benchmark's
process after the window, straight from the ring.

A call counts where the ring still covers it: it starts after the close of
every event among the ring's oldest (`EDGE` of them: events are written as
they close, a collector's span and an interval a little after). A span
belongs to the call its start falls in. A program without the spans a reader
asks for (the parent of the PR that added them) gives no number, and the
reader returns None; so does a window with fewer than MIN_CALLS calls
covered."""

from __future__ import annotations

import bisect
import statistics

import program_spans

MIN_CALLS = program_spans.MIN_CALLS
EDGE = 32  # the ring's oldest events, whose closes bound what may have rolled out


def _end_ns(e) -> int:
    return e["t0_ns"] + int(round(e.get("dur_ms", 0.0) * 1e6))


def _events(ctx) -> list:
    got = getattr(ctx, "_call_span_events", None)
    if got is None:
        got = ctx._call_span_events = [e for e in program_spans.ring() if "t0_ns" in e]
    return got


def covered(ctx) -> list:
    """(start_ns, end_ns) of each window call the ring still covers; [] under
    MIN_CALLS of them."""
    events = _events(ctx)
    if not events:
        return []
    edge = max(_end_ns(e) for e in events[:EDGE])
    out = []
    for c in ctx.calls:
        if c.get("start") is None or c.get("end") is None:
            continue
        t0, t1 = int(c["start"] * 1e9), int(c["end"] * 1e9)
        if t0 >= edge:
            out.append((t0, t1))
    return out if len(out) >= MIN_CALLS else []


def _per_call(calls, spans) -> list:
    """Per call, the (t0_ns, dur_ms) of `spans` (sorted by start) that start
    in it."""
    out, j = [], 0
    for t0, t1 in calls:
        while j < len(spans) and spans[j][0] < t0:
            j += 1
        k, mine = j, []
        while k < len(spans) and spans[k][0] <= t1:
            mine.append(spans[k])
            k += 1
        out.append(mine)
    return out


def median_sum_ms(ctx, names, roots=None):
    """Median over the covered calls of the summed duration of the spans
    named in `names` (under a root named in `roots`, where given); None
    where no covered call has one."""
    calls = covered(ctx)
    events = _events(ctx)
    root_name = {e["span"]: e["name"] for e in events if e.get("parent") is None}
    spans = sorted((e["t0_ns"], e["dur_ms"]) for e in events
                   if e["name"] in names and "dur_ms" in e
                   and (roots is None or root_name.get(e.get("root")) in roots))
    per = _per_call(calls, spans)
    if not any(per):
        return None
    return statistics.median(sum(d for _, d in mine) for mine in per)


def median_unnamed_ms(ctx):
    """Median over the covered calls of the call's wall less the union,
    clipped to the call, of every span in the ring but a root that has
    children (its children name its time)."""
    calls = covered(ctx)
    events = _events(ctx)
    if not calls:
        return None
    parents = {e["root"] for e in events if e.get("root") != e.get("span")}
    spans = sorted((e["t0_ns"], _end_ns(e)) for e in events if "dur_ms" in e
                   and not (e.get("parent") is None and e["span"] in parents))
    xs, j = [], 0
    for t0, t1 in calls:
        while j < len(spans) and spans[j][0] < t0 - 10**10:  # no span outlasts 10 s
            j += 1
        named, edge = 0, t0
        for s0, s1 in spans[j:]:
            if s0 >= t1:
                break
            s0, s1 = max(s0, edge), min(s1, t1)
            if s1 > s0:
                named += s1 - s0
                edge = s1
        xs.append((t1 - t0 - named) / 1e6)
    return statistics.median(xs)


def mean_gc_ms(ctx):
    """Mean over the covered calls of the collector's pauses in a call: the
    `gc_ms` stamp of the last root closed before the call's end, less that
    of the last root closed before its start. None where no root carries
    the stamp."""
    stamps = sorted((_end_ns(e), e["gc_ms"]) for e in _events(ctx)
                    if e.get("parent") is None and "gc_ms" in e)
    if not stamps:
        return None
    ends = [end for end, _ in stamps]
    xs = []
    for t0, t1 in covered(ctx):
        before, upto = bisect.bisect_right(ends, t0) - 1, bisect.bisect_right(ends, t1) - 1
        if before >= 0:
            xs.append(stamps[upto][1] - stamps[before][1])
    return statistics.fmean(xs) if len(xs) >= MIN_CALLS else None
