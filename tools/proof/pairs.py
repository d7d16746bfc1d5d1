#!/usr/bin/env python3
"""Parent against change in ONE chip call, as pairs in alternating order:
    python tools/proof/pairs.py --workload W --seeds a,b,c --parent _parent [--change _proof/final] --out chiprun_out/W.pairs.jsonl
The parent process stays off JAX; each run is one benchmark/run.py process in
its own checkout (the parent's under --parent, the change's here)."""
import argparse, json, os, statistics, subprocess, sys, time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))
import stats  # noqa: E402


def one(root, workload, seed, seconds, trace):
    cmd = [sys.executable, "benchmark/run.py", "--workload", workload, "--seed", str(seed),
           "--seconds", str(seconds), "--trace", str(trace)]
    t0 = time.time()
    p = subprocess.run(cmd, cwd=root, capture_output=True, text=True, timeout=2400)
    rec = {"rc": p.returncode, "wall_s": round(time.time() - t0, 1)}
    try:
        rec["result"] = json.loads(p.stdout.strip().splitlines()[-1])
    except Exception:
        rec["stderr_tail"] = p.stderr[-3000:]
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--trace-seed", default="")
    ap.add_argument("--parent", default="_parent")
    ap.add_argument("--change", default="", help="the change's checkout, under the root "
                    "(a `git archive $(git write-tree)` copy); default: the root itself")
    ap.add_argument("--out", required=True)
    a = ap.parse_args()
    seconds = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))["run_seconds"]
    sides = {"parent": os.path.join(ROOT, a.parent), "change": os.path.join(ROOT, a.change)}
    os.makedirs(os.path.dirname(os.path.abspath(a.out)), exist_ok=True)
    recs = []
    plan = [(int(s), 0) for s in a.seeds.split(",") if s] + \
           [(int(s), 1) for s in a.trace_seed.split(",") if s]
    with open(a.out, "a") as f:
        for k, (seed, trace) in enumerate(plan):
            order = ["parent", "change"] if k % 2 == 0 else ["change", "parent"]
            for side in order:
                rec = one(sides[side], a.workload, seed, seconds, trace)
                rec.update(side=side, seed=seed, trace=trace, workload=a.workload, first=k == 0)
                recs.append(rec)
                f.write(json.dumps(rec) + "\n")
                f.flush()
                res = rec.get("result", {})
                print(f"{side} seed {seed} trace {trace} rc {rec['rc']} wall {rec['wall_s']} "
                      f"correct {res.get('correct')} failed {res.get('failed')} "
                      + " ".join(f"{n}={m['value']:.6g}" for n, m in res.get("metrics", {}).items()),
                      flush=True)
    names = sorted({n for r in recs if not r["trace"] for n in r.get("result", {}).get("metrics", {})})
    for n in names:
        med = {}
        for side in sides:
            xs = [r["result"]["metrics"][n]["value"] for r in recs
                  if r["side"] == side and not r["trace"] and "result" in r
                  and not (n == "setup_s" and r["first"])]
            if xs:
                med[side] = statistics.median(xs)
                sp = stats.spread(xs) if len(xs) >= 3 else float("nan")
                print(f"{n} {side}: n={len(xs)} median={med[side]:.6g} spread={sp:.3%} "
                      + " ".join(f"{x:.6g}" for x in xs))
        if len(med) == 2:
            print(f"{n}: change/parent - 1 = {med['change'] / med['parent'] - 1:+.3%}")
            pairs = {}
            for r in recs:
                if not r["trace"] and "result" in r:
                    pairs.setdefault(r["seed"], {})[r["side"]] = r["result"]["metrics"][n]["value"]
            print("  pairs: " + " ".join(f"{v['change'] / v['parent'] - 1:+.2%}"
                                         for v in pairs.values() if len(v) == 2))
    bad = [r for r in recs if r["rc"] != 0 or not r.get("result", {}).get("correct")]
    print(f"runs={len(recs)} not_correct_or_failed={len(bad)}")
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
