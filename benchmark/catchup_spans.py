"""Spans of the program's flight recorder (tendermint_tpu/libs/trace.py) per
catch-up run, for the per-layer readers of a cell whose entry is the
block-sync reactor. The same reading as program_spans.py makes of a
`commit.verify` call, of another root: a call is a root named
`catchup.verify_run`, and everything the run caused hangs in its tree, also
what ran on the scheduler's dispatch thread (`lane.wait`, `lane.flush`, the
`verify_batch` under it), by the scheduler's explicit parent.

The flush of a run is the program's ordinary flush, so its prep stages,
its first dispatch and its flush record are read here too, under names of
their own (`catchup.prep.*`, `catchup.flush.record_ms`): program_spans.py's
readers pick calls by the root `commit.verify`, which a run does not open.

Kept are the roots whose `rows` is the cell's and whose `verdict` is
`accepted` (the comparison after the window pushes refused runs through the
same ring) and that the ring still holds whole: children are written before
their root, so a run whose first child (`catchup.gather`) has rolled over is
dropped. A program without such spans (the parent of the PR that added them)
gives no call, and every reader returns None."""

from __future__ import annotations

import statistics

import program_spans

ROOT = "catchup.verify_run"
FIRST_CHILD = "catchup.gather"  # written first: there, the run is whole
MIN_CALLS = program_spans.MIN_CALLS


def whole_runs(events, rows: int) -> list:
    """Per kept run: span name -> [(dur_ms, attrs, t0_ns), ...] in ring order."""
    by_root: dict = {}
    for e in events:
        if e.get("root") is not None:
            by_root.setdefault(e["root"], []).append(e)
    out = []
    for root_id, evs in by_root.items():
        root = next((e for e in evs if e.get("span") == root_id), None)
        if root is None or root["name"] != ROOT:
            continue
        attrs = root.get("attrs") or {}
        if attrs.get("rows") != rows or attrs.get("verdict") != "accepted":
            continue
        if not any(e["name"] == FIRST_CHILD for e in evs):
            continue
        spans: dict = {}
        for e in evs:
            if "dur_ms" in e:
                spans.setdefault(e["name"], []).append(
                    (e["dur_ms"], e.get("attrs") or {}, e.get("t0_ns")))
        out.append(spans)
    return out


def runs_of(ctx) -> list:
    """The window's whole runs still in the ring; [] under MIN_CALLS."""
    got = getattr(ctx, "_catchup_span_runs", None)
    if got is None:
        got = whole_runs(program_spans.ring(), ctx.rows)
        if len(got) < MIN_CALLS:
            got = []
        ctx._catchup_span_runs = got
    return got


def median_sum_ms(ctx, name: str):
    """Median over the runs of the summed duration of every `name` span of a
    run; None where no run has one."""
    runs = runs_of(ctx)
    if not any(name in r for r in runs):
        return None
    return statistics.median(sum(d for d, _, _ in r.get(name, ())) for r in runs)


def median_sum_attr(ctx, name: str, attr: str):
    """Median over the runs of the sum of the attribute `attr` over every
    `name` span of a run; None where no run has one that carries it."""
    xs = [sum(a[attr] for _, a, _ in r[name] if attr in a) for r in runs_of(ctx)
          if any(attr in a for _, a, _ in r.get(name, ()))]
    return statistics.median(xs) if xs else None


def median_first_dispatch_ms(ctx):
    """Median over the runs of: start of the run's `verify_batch` (on the
    dispatch thread, under `lane.flush`) to the end of its first `dispatch`."""
    xs = []
    for r in runs_of(ctx):
        if "verify_batch" in r and "dispatch" in r:
            dur, _, t0 = min(r["dispatch"], key=lambda s: s[2])
            xs.append((t0 - min(t for _, _, t in r["verify_batch"])) / 1e6 + dur)
    return statistics.median(xs) if xs else None
