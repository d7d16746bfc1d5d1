set -x
# From the change's committed files in _proof/final/ (a `git archive $(git write-tree)` copy), one compile cache for
# all: 1. commit-10k.tampered traced once (the six per-layer metrics); 2. a second set of six untraced runs of it, each
# in-process with the slow calls' spans kept (stalls.py); 3. the five accepted cells, traced once each.
echo JAX_COMPILATION_CACHE_DIR=${JAX_COMPILATION_CACHE_DIR:-unset}
export JAX_COMPILATION_CACHE_DIR=${JAX_COMPILATION_CACHE_DIR:-$PWD/_proof/jaxcache}
O=$PWD/chiprun_out/pr41
mkdir -p $O
(cd _proof/final && python benchmark/prove.py --workload commit-10k.tampered \
  --trace-seeds 2147541211 --out $O/tampered2.jsonl)
echo TRACED_RC=$?
for seed in 2147541201 2147541202 2147541203 2147541204 2147541205 2147541206; do
  (cd _proof/final && python tools/proof/pr41/stalls.py --workload commit-10k.tampered --seed $seed \
    --seconds 25 --trace 0 2>/dev/null) | tail -n 2 >> $O/stalls.out
  echo STALLS_RC $seed $?
done
seed=2147541141
for cell in commit-10k.verify-commit hub-175.catchup live-10k.vote-commit light-seq-100.sequence \
            commit-1024.verify-commit; do
  (cd _proof/final && python benchmark/prove.py --workload $cell --trace-seeds $seed --out $O/accepted.jsonl)
  echo ACCEPTED_RC $cell $?
  seed=$((seed + 1))
done
python tools/proof/pr41/show.py $O/tampered2.jsonl $O/accepted.jsonl
python - <<'PY'
import json
for line in open("chiprun_out/pr41/stalls.out"):
    if line.startswith("CALLS"):
        d = json.loads(line[6:])
        print("CALLS", {k: d[k] for k in ("calls", "clean_ms_p50", "forged_ms_p50", "clean_ms_max", "forged_ms_max")},
              "gaps", d["gaps"][:5])
        for s in d["slow"]:
            print("  SLOW", json.dumps(s)[:1500])
    else:
        r = json.loads(line)
        print("RESULT", r["seed"], r["correct"], r["samples"], {k: v["value"] for k, v in r["metrics"].items()})
PY
