"""Spans of the program's flight recorder (tendermint_tpu/libs/trace.py) per
precommit step, for the per-layer readers of a cell whose entry is a node in
consensus (entries/vote_commit.py). One call of that entry opens three roots,
one after another: `votes.flush` (a VoteSet.flush: `votes.gather`,
`votes.sign_bytes`, the votes lane's `lane.flush` / `verify_batch`,
`votes.count`), `votes.make_commit`, and `commit.verify` (the commit checked
again, from the memo).

Kept of `votes.flush` are the roots whose `rows` is the cell's, that
committed every row and failed none (the comparison after the window pushes a
step short of power and one with wrong votes through the same ring), and
that the ring still holds whole: children are written before their root, so
a flush whose first child (`votes.gather`) has rolled over is dropped. Of the
other two roots, those whose `rows` is the cell's (and, of `commit.verify`,
whose verdict is `accepted`). A program without such spans (the parent of the
PR that added them) gives no call, and every reader returns None."""

from __future__ import annotations

import statistics

import program_spans

ROOT = "votes.flush"
FIRST_CHILD = "votes.gather"  # written first: there, the flush's tree is whole
MIN_CALLS = program_spans.MIN_CALLS


def _events(ctx) -> list:
    got = getattr(ctx, "_vote_span_events", None)
    if got is None:
        got = ctx._vote_span_events = program_spans.ring()
    return got


def median_child_ms(ctx, name: str):
    """Median over the kept flushes of the summed duration of every `name`
    span under a flush's root; None under MIN_CALLS of them."""
    by_root: dict = {}
    for e in _events(ctx):
        if e.get("root") is not None:
            by_root.setdefault(e["root"], []).append(e)
    xs = []
    for root_id, evs in by_root.items():
        root = next((e for e in evs if e.get("span") == root_id), None)
        if root is None or root["name"] != ROOT:
            continue
        attrs = root.get("attrs") or {}
        if (attrs.get("rows") != ctx.rows or attrs.get("committed") != ctx.rows
                or attrs.get("failed") != 0):
            continue
        durs = [e["dur_ms"] for e in evs if e["name"] == name and "dur_ms" in e]
        if durs and any(e["name"] == FIRST_CHILD for e in evs):
            xs.append(sum(durs))
    return statistics.median(xs) if len(xs) >= MIN_CALLS else None


def median_root_ms(ctx, name: str, **attrs):
    """Median duration of the spans `name` whose `rows` is the cell's and
    whose attributes hold `attrs`; None under MIN_CALLS of them."""
    want = dict(attrs, rows=ctx.rows)
    xs = [e["dur_ms"] for e in _events(ctx)
          if e["name"] == name and "dur_ms" in e
          and all((e.get("attrs") or {}).get(k) == v for k, v in want.items())]
    return statistics.median(xs) if len(xs) >= MIN_CALLS else None


def median_reading(ctx, key: str):
    """Median over the window's calls of the driver's reading `key`; None
    where no call carries it."""
    xs = [c["flush"][key] for c in ctx.calls if c["flush"].get(key) is not None]
    return statistics.median(xs) if xs else None
