#!/usr/bin/env python3
"""All runs of one cell in ONE chip call: a parent that stays off JAX starts
one run.py process per run, one after another, all sharing the checkout's
compile cache, and keeps every result line.

    chiprun --timeout 3000 -- python benchmark/prove.py \\
        --workload commit-10k.verify-commit --seeds 11,12,13,14,15,16 --sets 2 \\
        --trace-seeds 21,22,23 --out chiprun_out/commit-10k.jsonl

runs the seeds once per set with --trace 0 (the same seeds in every set: what
a bound is set from), then the trace seeds with --trace 1, and prints per
metric each set's median and spread (quartile distance over median). The
first run of a call compiles, or loads, every program and is marked `first`;
its set-up is left out of setup_s's figures."""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

import stats  # noqa: E402


def one_run(workload, seed, seconds, trace, extra, timeout):
    cmd = [sys.executable, os.path.join(HERE, "run.py"), "--workload", workload,
           "--seed", str(seed), "--seconds", str(seconds), "--trace", str(trace)] + extra
    t0 = time.time()
    try:
        p = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True, timeout=timeout)
        rc, out, err = p.returncode, p.stdout, p.stderr
    except subprocess.TimeoutExpired as e:
        rc, out, err = 124, e.stdout or "", e.stderr or ""
        out = out.decode() if isinstance(out, bytes) else out
        err = err.decode() if isinstance(err, bytes) else err
    rec = {"workload": workload, "seed": seed, "trace": trace, "rc": rc,
           "wall_s": round(time.time() - t0, 2)}
    lines = [ln for ln in out.splitlines() if ln.strip()]
    try:
        rec["result"] = json.loads(lines[-1])
    except (IndexError, ValueError):
        rec["stdout_tail"] = out[-2000:]
    if rc != 0 or not rec.get("result", {}).get("correct"):
        rec["stderr_tail"] = err[-4000:]
    return rec


def summarise(records, out=sys.stdout):
    by_set: dict = {}
    for r in records:
        res = r.get("result")
        if not res or r["trace"] or "set" not in r:
            continue
        for name, m in res["metrics"].items():
            if name == "setup_s" and r.get("first"):
                continue
            by_set.setdefault(name, {}).setdefault(r["set"], []).append(m["value"])
    for name, sets in by_set.items():
        for k, values in sorted(sets.items()):
            line = f"{name} set {k}: n={len(values)} median={statistics.median(values):.6g}"
            if len(values) >= 3:
                line += f" spread={stats.spread(values):.4%}"
            print(line + " values=" + " ".join(f"{v:.6g}" for v in values), file=out)
    bad = [r for r in records if r["rc"] != 0 or not r.get("result", {}).get("correct")]
    print(f"runs={len(records)} not_correct_or_failed={len(bad)}", file=out)
    for r in bad:
        print(f"  seed {r['seed']} trace {r['trace']} rc {r['rc']}: "
              f"{json.dumps(r.get('result', {}).get('checks'))}", file=out)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--sets", type=int, default=1)
    ap.add_argument("--trace-seeds", default="")
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--out", required=True)
    ap.add_argument("--timeout", type=float, default=1500.0)
    ap.add_argument("--keep-trace", default="")
    ap.add_argument("extra", nargs="*", help="after --: passed on to run.py")
    args = ap.parse_args(argv)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        seconds = args.seconds or json.load(f)["run_seconds"]
    plan = [(int(s), 0, k) for k in range(args.sets) for s in args.seeds.split(",") if s]
    plan += [(int(s), 1, None) for s in args.trace_seeds.split(",") if s]
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    records = []
    with open(args.out, "a") as f:
        for n, (seed, trace, k) in enumerate(plan):
            extra = list(args.extra)
            if trace and args.keep_trace and not any(r["trace"] for r in records):
                extra += ["--keep-trace", args.keep_trace]
            rec = one_run(args.workload, seed, seconds, trace, extra, args.timeout)
            rec["first"] = n == 0
            if k is not None:
                rec["set"] = k
            records.append(rec)
            f.write(json.dumps(rec) + "\n")
            f.flush()
            res = rec.get("result", {})
            print(f"[{n + 1}/{len(plan)}] seed {seed} trace {trace} rc {rec['rc']} "
                  f"wall {rec['wall_s']} s correct {res.get('correct')} "
                  + " ".join(f"{a}={b['value']:.6g}" for a, b in res.get("metrics", {}).items())
                  + " host: " + " ".join(
                      f"{a}={res.get('host', {}).get(a, float('nan')):.3g}"
                      for a in ("own_busy_cores", "others_busy_cores", "switched_out"))
                  + " spans: " + " ".join(f"{a}={b:.4g}" for a, b in res.get("spans_p50", {}).items()),
                  flush=True)
    summarise(records)
    return 0 if all(r["rc"] == 0 and r.get("result", {}).get("correct") for r in records) else 1


if __name__ == "__main__":
    sys.exit(main())
