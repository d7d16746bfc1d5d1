#!/usr/bin/env python3
"""Prints what benchmark/prove.py's summary leaves out of the .jsonl files it
wrote: per run its end-to-end or per-layer metrics, the forged calls' notes
(count, walls, recovery flushes, paths, device ms), the device's busy and
window seconds, peak memory and the checks that failed; then per cell the
median and spread of each end-to-end metric over the untraced runs (the
first run of a file, which loads the programs, left out of `setup_s`).

    python tools/proof/pr41/show.py <file.jsonl> [...]"""

import json
import os
import statistics
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", "..", "..",
                                "benchmark"))
import stats  # noqa: E402

NOTES = ("forged_calls", "forged_ms_p50", "forged_ms_max", "clean_ms_p50", "recovery_flushes_p50",
         "recovery_flushes_max", "forged_paths", "forged_device_ms", "clean_device_ms_p50",
         "first_off_path", "judge_s")

for path in sys.argv[1:]:
    by: dict = {}
    for line in open(path):
        r = json.loads(line)
        res = r.get("result") or {}
        metrics = {k: v["value"] for k, v in (res.get("metrics") or {}).items()}
        dev = res.get("device") or {}
        failing = {k: v for k, (v, limit) in (res.get("checks") or {}).items() if v > limit}
        print(f"{os.path.basename(path)} {r['workload']} seed {r['seed']} trace {r['trace']} "
              f"rc {r['rc']} wall {r['wall_s']} correct {res.get('correct')} "
              f"samples {res.get('samples')} failing {failing}")
        print("  metrics", json.dumps(metrics))
        print("  notes", json.dumps({k: res.get("notes", {}).get(k) for k in NOTES
                                     if k in res.get("notes", {})}))
        print("  device", json.dumps({k: dev.get(k) for k in ("kind", "busy_s", "window_s",
                                                             "memory_peak_bytes")}),
              "setup_split", json.dumps(res.get("setup_split")), "flush", json.dumps(res.get("flush")))
        if r["trace"] and "breakdown" in res:
            print("  breakdown", json.dumps(res["breakdown"])[:3000])
        if "stderr_tail" in r:
            print("  stderr", r["stderr_tail"][-1500:])
        if not r["trace"]:
            for k, v in metrics.items():
                if not (k == "setup_s" and r.get("first")):
                    by.setdefault(r["workload"], {}).setdefault(k, []).append(v)
    for cell, metrics in by.items():
        for k, xs in metrics.items():
            line = f"{cell} {k}: n={len(xs)} median={statistics.median(xs):.6g}"
            if len(xs) >= 3:
                line += f" spread={stats.spread(xs):.4%}"
            print(line + " values=" + " ".join(f"{x:.6g}" for x in xs))
