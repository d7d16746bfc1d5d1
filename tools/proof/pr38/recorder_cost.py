#!/usr/bin/env python3
"""What the flight recorder costs a call, measured directly in one process:

    python tools/proof/pr38/recorder_cost.py --workload W --seed N [--pairs 6] [--window 8] [--arms off,on] [--rehearse N]

Builds the cell as benchmark/run.py does (data from the seed, the entry,
its warm-up), then runs `--pairs` rounds of one window of `--window`
seconds an arm, the arms' order rotated from round to round. Arms: `off`,
the recorder off (`tracer.configure(enabled=False)`: no span, no GC hook,
what TMTPU_TRACE=0 gives); `on`, the default; `base`, the recorder on
without what PR 38 added (no GC hook, so no stamp and no `gc.collect`; no
`votes.pending`, `memo.digest`, `provenance.score` or `light.*` span around
the run). Prints one JSON line: per window the calls, the median and p95 of
their walls; per round each arm's median less the first arm's."""

import argparse
import gc
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "benchmark")]

import data  # noqa: E402
import spec  # noqa: E402
import stats  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--pairs", type=int, default=6)
    ap.add_argument("--window", type=float, default=8.0)
    ap.add_argument("--arms", default="off,on")
    ap.add_argument("--rehearse", type=int, default=0)
    a = ap.parse_args()
    if a.rehearse:
        os.environ.setdefault("TMTPU_CRYPTO_BACKEND", "cpu")
    from tendermint_tpu.libs import trace
    from tendermint_tpu.ops.aot_cache import configure_compile_cache

    configure_compile_cache()
    cell = spec.Cell(spec.load_benchmark(ROOT), a.workload)
    entry = cell.entry()
    assert entry.native_ready()
    entry.configure(cell.traffic)
    vals = data.make_validators(a.seed, cell.config, a.rehearse or None)
    ring = data.make_ring(a.seed, cell.config, cell.traffic, vals)
    eprobes = data.entry_probes(a.seed, cell.config, cell.traffic, ring, vals)
    state = entry.build(cell.config, vals, ring + [item for _, item in eprobes])
    for i in range(int(cell.traffic["warmup_calls"])):
        entry.call(state, i % len(ring))
        entry.flush_reading()
    gc.collect()
    arms = a.arms.split(",")
    span, interval, since = trace.span, trace.interval, trace.since
    added = {"memo.digest", "provenance.score", "light.load", "light.target_checks",
             "light.witness", "light.save"}

    def arm(name):
        trace.tracer.configure(enabled=name != "off")
        base = name == "base"
        if base:
            trace._hook_gc(False)
        trace.since = (lambda n: None) if base else since
        trace.span = (lambda n, parent=None, **kw: trace.NOOP if n in added
                      else span(n, parent, **kw)) if base else span
        trace.interval = (lambda n, *x, **kw: None if n in added
                          else interval(n, *x, **kw)) if base else interval

    windows, i = [], 0
    for p in range(a.pairs):
        for name in arms[p % len(arms):] + arms[:p % len(arms)]:
            arm(name)
            walls, bad = [], 0
            end = time.perf_counter() + a.window
            while time.perf_counter() < end:
                t0 = time.perf_counter()
                verdict = entry.call(state, i % len(ring))
                walls.append((time.perf_counter() - t0) * 1e3)
                entry.flush_reading()
                bad += verdict != "accepted"
                i += 1
            windows.append({"round": p, "arm": name, "calls": len(walls), "not_accepted": bad,
                            "p50_ms": statistics.median(walls),
                            "p95_ms": stats.percentile(walls, 95)})
    arm("on")
    p50 = {(w["round"], w["arm"]): w["p50_ms"] for w in windows}
    less = {f"{x}_less_{arms[0]}_ms": [p50[(p, x)] - p50[(p, arms[0])] for p in range(a.pairs)]
            for x in arms[1:]}
    if "base" in arms and "on" in arms:
        less["on_less_base_ms"] = [p50[(p, "on")] - p50[(p, "base")] for p in range(a.pairs)]
    first = statistics.median(p50[(p, arms[0])] for p in range(a.pairs))
    print(json.dumps({"workload": a.workload, "seed": a.seed, "windows": windows, "less": less,
                      "median_less_ms": {k: statistics.median(v) for k, v in less.items()},
                      f"median_{arms[0]}_p50_ms": first,
                      "share_of_p50": {k: statistics.median(v) / first for k, v in less.items()}}),
          flush=True)

if __name__ == "__main__":
    main()
