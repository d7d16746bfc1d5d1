#!/usr/bin/env python3
"""Each device-idle gap of a kept slice (`benchmark/run.py --keep-trace DIR`
writes DIR/slice.xplane.pb.gz) put down twice: to the benchmark's positional
name (benchmark/tracing.py `_phases`: before, in or after the `bench:flush`
of a `bench:call`) and to the innermost `tm:` span of the program open on the
host at that moment (tendermint_tpu/tools/profile_report.py `idle_by_span`'s
rule: the thread with the most `tm:` time, flattened). Prints the table of
milliseconds a call by (positional name, span), and how much of the slice's
idle time lies under `tm:gc.collect`:

    python tools/proof/pr38/idle_names.py <slice.xplane.pb.gz> [...] [--json OUT]"""

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "benchmark")]

import tracing  # noqa: E402

from tendermint_tpu.tools import profile_report as pr  # noqa: E402

NO_SPAN = pr.NO_SPAN


def cut(a, b, intervals):
    for s, e, name in intervals:
        if e > a and s < b:
            yield max(a, s), min(b, e), name


def fill(a, b, pieces):
    """`pieces` of [a, b) with the holes between them as NO_SPAN."""
    out, at = [], a
    for s, e, name in pieces:
        if s > at:
            out.append((at, s, NO_SPAN))
        out.append((s, e, name))
        at = e
    if b > at:
        out.append((at, b, NO_SPAN))
    return out


def flatten(spans, lo, hi) -> list:
    """One thread's nested spans as (start, end, innermost name) over [lo, hi)."""
    spans = sorted(spans, key=lambda e: (e["ts_us"], -e["dur_us"]))
    flat, stack, cur = [], [], lo

    def emit(until):
        nonlocal cur
        if until > cur:
            flat.append((cur, until, stack[-1][1] if stack else NO_SPAN))
            cur = until

    for e in spans:
        if e["ts_us"] + e["dur_us"] <= lo or e["ts_us"] >= hi:
            continue
        while stack and stack[-1][0] <= e["ts_us"]:
            emit(min(stack[-1][0], hi))
            stack.pop()
        emit(max(e["ts_us"], lo))
        stack.append((e["ts_us"] + e["dur_us"], e["name"][len(pr.TM_PREFIX):]))
    while stack:
        emit(min(stack[-1][0], hi))
        stack.pop()
    emit(hi)
    return flat


def table(path: str) -> dict:
    events = pr.load_events(path)
    host = [e for e in events if not e["plane"].startswith("/device:")]
    bench = [(e["ts_us"], e["ts_us"] + e["dur_us"], e["name"][len(tracing.SPAN_PREFIX):])
             for e in host if e["name"].startswith(tracing.SPAN_PREFIX)]
    (lo, hi), = [(s, e) for s, e, n in bench if n == "slice"]
    busy = []
    for plane in sorted({e["plane"] for e in events if e["plane"].startswith("/device:")}):
        ops = [e for e in events if e["plane"] == plane and e["dur_us"] > 0
               and e["thread"] in pr.DEVICE_OP_LINES]
        if ops:
            busy = pr._union((e["ts_us"], e["ts_us"] + e["dur_us"]) for e in ops)
            break
    idle, at = [], lo
    for s, e in busy:
        if e <= lo or s >= hi:
            continue
        if s > at:
            idle.append((at, s))
        at = max(at, e)
    if hi > at:
        idle.append((at, hi))
    by_thread = {}
    for e in host:
        if e["name"].startswith(pr.TM_PREFIX):
            by_thread.setdefault((e["pid"], e["tid"]), []).append(e)
    # every thread's spans flattened to its innermost open span; an instant
    # where the thread with the most `tm:` time has none open goes to the
    # next thread that has one (the light client's run is on an executor
    # thread, the work around it on the event loop's)
    threads = sorted(by_thread.values(), key=lambda evs: -sum(
        b - a for a, b in pr._union((x["ts_us"], x["ts_us"] + x["dur_us"]) for x in evs)))
    flats = [flatten(evs, lo, hi) for evs in threads]
    flat = flats[0]
    for other in flats[1:]:
        merged = []
        for s0, s1, name in flat:
            merged += [(s0, s1, name)] if name != NO_SPAN else fill(s0, s1, list(cut(s0, s1, other)))
        flat = merged
    phases = tracing._phases(sorted(bench), lo, hi)
    cells = {}
    for g0, g1 in idle:
        for p0, p1, phase in cut(g0, g1, phases):
            for s0, s1, span in cut(p0, p1, flat):
                cells[(phase, span)] = cells.get((phase, span), 0.0) + (s1 - s0) / 1e3
    calls = sum(1 for _, _, n in bench if n == "call")
    gc_us = sum(s1 - s0 for g0, g1 in idle for s0, s1, n in cut(g0, g1, flat) if n == "gc.collect")
    gc_all = sum(e["dur_us"] for e in host if e["name"] == pr.TM_PREFIX + "gc.collect"
                 and lo <= e["ts_us"] < hi)
    return {"slice": path, "calls": calls, "window_ms": (hi - lo) / 1e3,
            "idle_ms": sum(b - a for a, b in idle) / 1e3,
            "gc_collect_idle_ms": gc_us / 1e3, "gc_collect_ms": gc_all / 1e3,
            "gc_collects": sum(1 for e in host if e["name"] == pr.TM_PREFIX + "gc.collect"
                               and lo <= e["ts_us"] < hi),
            "rows": [{"positional": p, "span": s, "ms_per_call": ms / max(calls, 1)}
                     for (p, s), ms in sorted(cells.items(), key=lambda kv: (kv[0][0], -kv[1]))]}


def main():
    args = sys.argv[1:]
    out = None
    if "--json" in args:
        k = args.index("--json")
        out, args = args[k + 1], args[:k] + args[k + 2:]
    got = [table(p) for p in args]
    for t in got:
        print(f"## {t['slice']}: {t['calls']} calls, window {t['window_ms']:.1f} ms, "
              f"device idle {t['idle_ms']:.1f} ms; tm:gc.collect {t['gc_collects']} x, "
              f"{t['gc_collect_ms']:.2f} ms, {t['gc_collect_idle_ms']:.2f} ms of it idle")
        print("| positional name (benchmark/tracing.py) | innermost tm: span | idle ms a call |")
        print("| --- | --- | --- |")
        for r in t["rows"]:
            if r["ms_per_call"] >= 0.05:
                print(f"| {r['positional']} | `{r['span']}` | {r['ms_per_call']:.2f} |")
    if out:
        with open(out, "w") as f:
            json.dump(got, f, indent=1)


if __name__ == "__main__":
    main()
