"""Spans of the program's own flight recorder (tendermint_tpu/libs/trace.py),
per call of the entry, for the per-layer readers that divide a call's time
from the inside. Read in the benchmark's process after the window, straight
from the ring.

Every ring event carries `root`, the id of the outermost span open when it
began. A call of the entry is a root of the name the cell's traffic file
states under `root_span` (where it states none: `commit.verify`); kept are
the roots whose `rows` is the cell's and whose `verdict` is `accepted` (the
comparison after the window pushes masks, probes and refusals through the
same ring), and that the ring still holds whole: children are written before
their root, so a call whose first child (`root_first_span` of the traffic
file; `commit.gather`) has rolled over is dropped. So a cell whose entry
opens another root lists itself under these readers' `workloads` and states
its root, with no reader of its own. A program without such spans (the
parent of the PR that added them) gives no call, and every reader returns
None."""

from __future__ import annotations

import statistics

ROOT = "commit.verify"          # where the traffic file states no `root_span`
FIRST_CHILD = "commit.gather"  # written first: there, the call is whole (`root_first_span`)
MIN_CALLS = 30


def ring() -> list:
    try:
        from tendermint_tpu.libs.trace import tracer

        return tracer.dump()
    except Exception:  # no such program, or no recorder: nothing to read
        return []


def whole_calls(events, rows: int, root_name: str = ROOT, first_child: str = FIRST_CHILD) -> list:
    """Per kept call: span name -> [(t0_ns, dur_ms), ...] in ring order."""
    by_root: dict = {}
    for e in events:
        if e.get("root") is not None:
            by_root.setdefault(e["root"], []).append(e)
    out = []
    for root_id, evs in by_root.items():
        root = next((e for e in evs if e.get("span") == root_id), None)
        if root is None or root["name"] != root_name:
            continue
        attrs = root.get("attrs") or {}
        if attrs.get("rows") != rows or attrs.get("verdict") != "accepted":
            continue
        if not any(e["name"] == first_child for e in evs):
            continue
        spans: dict = {}
        for e in evs:
            if "dur_ms" in e and "t0_ns" in e:
                spans.setdefault(e["name"], []).append((e["t0_ns"], e["dur_ms"]))
        out.append(spans)
    return out


def calls_of(ctx) -> list:
    """The window's whole calls still in the ring; [] under MIN_CALLS."""
    got = getattr(ctx, "_program_span_calls", None)
    if got is None:
        traffic = getattr(ctx, "traffic", None) or {}
        got = whole_calls(ring(), ctx.rows, traffic.get("root_span", ROOT),
                          traffic.get("root_first_span", FIRST_CHILD))
        if len(got) < MIN_CALLS:
            got = []
        ctx._program_span_calls = got
    return got


def median_sum_ms(ctx, name: str):
    """Median over the calls of the summed duration of every `name` span of
    a call; None where no call has one."""
    calls = calls_of(ctx)
    if not any(name in c for c in calls):
        return None
    return statistics.median(sum(d for _, d in c.get(name, ())) for c in calls)


def median_first_dispatch_ms(ctx):
    """Median over the calls of: start of `verify_batch` to the end of the
    call's first `dispatch`."""
    xs = []
    for c in calls_of(ctx):
        if "verify_batch" in c and "dispatch" in c:
            t0, dur = min(c["dispatch"])
            xs.append((t0 - c["verify_batch"][0][0]) / 1e6 + dur)
    return statistics.median(xs) if xs else None
