#!/usr/bin/env python3
"""A throwaway cell of forged commits on the chip, every run in ONE call: the
configuration commit-10k, which states the recovery ladder's contract
(`expect_recovery`), under mixes that forge one commit in eight of a ring of
16 (the accepted `verify-commit` mix otherwise), laid into a copy of
benchmark/ as a later change would add them. The copy is `_forged/` at the
checkout's root; every run is one run.py process, and all share the
program's compile cache, so only the first compiles.

    python benchmark/proof/forged/forged_cell.py \\
        --plan drawn:11,12,13,14,15,16 --plan 0.5:21,22,23,24,25,26 \\
        --plan 0.0:31,32 --plan 0.9999:41,42 --trace 0.5:51 \\
        --out <dir>/forged.jsonl

on the machine that holds the chip, from the checkout's root (about 40
minutes with three accepted cells beside it; the first run compiles).

A plan entry is `<seat>:<seeds>`: `drawn` (no `tampered_seats`: the forged
row is the seed's draw, as before seats) or a share in [0, 1) (the mix seats
every forged row there). `--trace` runs are traced, with --keep-trace beside
the out file. `--accepted CELL:SEED` runs a cell of the checkout's own
BENCHMARK.json once, traced. `--rehearse N` passes through to run.py (the
CPU, N validators), to walk this script without a chip.

Prints each run's forged calls (count, wall median and maximum, the clean
calls' median, recovery flushes, paths), its checks, and per plan entry the
median and spread of each end-to-end metric (the first run's set-up, which
compiles, left out)."""

from __future__ import annotations

import argparse
import json
import os
import shutil
import statistics
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(os.path.dirname(HERE))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

import prove  # noqa: E402
import spec  # noqa: E402
import stats  # noqa: E402

COPY = os.path.join(ROOT, "_forged")
CONFIG = "commit-10k"
FORGED = {"ring_commits": 16, "tampered_one_in": 8, "warmup_calls": 16}
NOTES = ("forged_calls", "forged_ms_p50", "forged_ms_max", "clean_ms_p50", "recovery_flushes_p50",
         "recovery_flushes_max", "forged_paths", "forged_device_ms", "clean_device_ms_p50",
         "first_off_path", "judge_s")


def mix_name(seat: str) -> str:
    return "forged-" + seat


def lay_copy(seats: list) -> dict:
    """benchmark/ copied to _forged/benchmark with one mix and one cell a seat."""
    shutil.rmtree(COPY, ignore_errors=True)
    here = os.path.join(COPY, "benchmark")
    shutil.copytree(BENCH, here, ignore=shutil.ignore_patterns("__pycache__", "testdata", "proof"))
    bm = spec.load_benchmark(ROOT)
    base = spec.load_json(spec.traffic_path(here, "verify-commit"))
    for seat in seats:
        mix = dict(base, name=mix_name(seat), **FORGED)
        if seat != "drawn":
            mix["tampered_seats"] = [float(seat)]
        with open(spec.traffic_path(here, mix["name"]), "w") as f:
            json.dump(mix, f, indent=1)
        bm["workloads"].append({"name": f"{CONFIG}.{mix['name']}", "config": CONFIG,
                                "traffic": mix["name"], "chips": 1, "why": "throwaway"})
    # every per-layer metric of the accepted cell on the same configuration reads the new ones too
    new = [w["name"] for w in bm["workloads"] if w["traffic"].startswith("forged-")]
    for m in bm["per_layer"]:
        if f"{CONFIG}.verify-commit" in m["workloads"]:
            m["workloads"] = m["workloads"] + new
    with open(os.path.join(COPY, "BENCHMARK.json"), "w") as f:
        json.dump(bm, f, indent=1)
    faults = spec.lint(bm, COPY, here)
    if faults:
        sys.exit("the copy does not lint: " + "; ".join(faults))
    return bm


def one_run(workload, seed, seconds, trace, extra, timeout, copy=True):
    if not copy:
        return prove.one_run(workload, seed, seconds, trace, extra, timeout)
    cmd = [sys.executable, os.path.join(COPY, "benchmark", "run.py"), "--workload", workload,
           "--seed", str(seed), "--seconds", str(seconds), "--trace", str(trace)] + extra
    env = dict(os.environ, PYTHONPATH=ROOT)
    t0 = time.time()
    try:
        p = subprocess.run(cmd, cwd=COPY, env=env, capture_output=True, text=True, timeout=timeout)
        rc, out, err = p.returncode, p.stdout, p.stderr
    except subprocess.TimeoutExpired as e:
        rc = 124
        out = e.stdout.decode() if isinstance(e.stdout, bytes) else (e.stdout or "")
        err = e.stderr.decode() if isinstance(e.stderr, bytes) else (e.stderr or "")
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


def show(rec) -> str:
    res = rec.get("result", {})
    notes = res.get("notes", {})
    metrics = res.get("metrics") or res.get("rehearsal_readings") or {}
    said = {k: notes[k] for k in NOTES if k in notes}
    failing = {k: v for k, (v, limit) in res.get("checks", {}).items() if v > limit}
    return (f"{rec['workload']} seed {rec['seed']} trace {rec['trace']} rc {rec['rc']} "
            f"wall {rec['wall_s']} s correct {res.get('correct')} failing {failing} "
            + " ".join(f"{a}={b['value']:.6g}" for a, b in metrics.items())
            + f" notes {json.dumps(said)}"
            + (f" breakdown {json.dumps(res['breakdown'])}" if "breakdown" in res else ""))


def spreads(records, out=sys.stdout) -> None:
    by: dict = {}
    for r in records:
        res = r.get("result")
        if not res or r["trace"]:
            continue
        for name, m in (res.get("metrics") or res.get("rehearsal_readings") or {}).items():
            if name == "setup_s" and r.get("first"):
                continue
            by.setdefault(r["workload"], {}).setdefault(name, []).append(m["value"])
    for cell, metrics in by.items():
        for name, values in metrics.items():
            line = f"{cell} {name}: n={len(values)} median={statistics.median(values):.6g}"
            if len(values) >= 3:
                line += f" spread={stats.spread(values):.4%}"
            print(line + " values=" + " ".join(f"{v:.6g}" for v in values), file=out)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--plan", action="append", default=[], metavar="SEAT:SEEDS")
    ap.add_argument("--trace", action="append", default=[], metavar="SEAT:SEEDS")
    ap.add_argument("--accepted", action="append", default=[], metavar="CELL:SEED")
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--rehearse", type=int, default=0)
    ap.add_argument("--timeout", type=float, default=1500.0)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    seconds = args.seconds or spec.load_benchmark(ROOT)["run_seconds"]
    runs = []
    for entries, trace in ((args.plan, 0), (args.trace, 1)):
        for entry in entries:
            seat, seeds = entry.split(":")
            runs += [(f"{CONFIG}.{mix_name(seat)}", int(s), trace, True) for s in seeds.split(",")]
    for entry in args.accepted:
        cell, seed = entry.split(":")
        runs.append((cell, int(seed), 1, False))
    lay_copy(sorted({w.split(".", 1)[1][len("forged-"):] for w, _, _, copy in runs if copy}))
    extra = ["--rehearse", str(args.rehearse)] if args.rehearse else []
    out_dir = os.path.dirname(os.path.abspath(args.out))
    os.makedirs(out_dir, exist_ok=True)
    records = []
    with open(args.out, "a") as f:
        for n, (workload, seed, trace, copy) in enumerate(runs):
            keep = ["--keep-trace", os.path.join(out_dir, f"slice-{workload}-{seed}")] \
                if trace and not args.rehearse else []
            rec = one_run(workload, seed, seconds, trace, extra + keep, args.timeout, copy)
            rec["first"] = n == 0
            records.append(rec)
            f.write(json.dumps(rec) + "\n")
            f.flush()
            print(f"[{n + 1}/{len(runs)}] " + show(rec), flush=True)
            if "stderr_tail" in rec:
                print(rec["stderr_tail"][-1500:], flush=True)
    spreads(records)
    bad = [r for r in records if r["rc"] != 0 or not r.get("result", {}).get("correct")]
    print(f"runs={len(records)} not_correct_or_failed={len(bad)}")
    return 0 if not bad else 1


if __name__ == "__main__":
    sys.exit(main())
