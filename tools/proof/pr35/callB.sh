set -x
# PR 35, call B: the cells that should not move, parent f4efa38 (_parent/, as in call A) against the tree: `hub-175.catchup` (the one
# older cell that may reach crypto/merkle.py) two untraced pairs and a traced one, `commit-10k.verify-commit` and
# `commit-1024.verify-commit` one untraced and one traced pair each; then the committed files alone (_proof/final/: a `git archive
# $(git write-tree)` copy): the claimed cell, traced, on a seed of its own.
echo JAX_COMPILATION_CACHE_DIR=${JAX_COMPILATION_CACHE_DIR:-unset}
export JAX_COMPILATION_CACHE_DIR=${JAX_COMPILATION_CACHE_DIR:-/root/repo/.jax_cache}
mkdir -p chiprun_out/pr35
time python tools/proof/pairs.py --workload hub-175.catchup --seeds 2147494831,2147494832 --trace-seed 2147494833 \
  --out chiprun_out/pr35/B.pairs.hub-175.catchup.jsonl
echo PAIRS_RC_hub=$?
seed=2147494841
for W in commit-10k.verify-commit commit-1024.verify-commit; do
  time python tools/proof/pairs.py --workload $W --seeds $seed --trace-seed $((seed + 1)) --out chiprun_out/pr35/B.pairs.$W.jsonl
  echo PAIRS_RC_$W=$?
  seed=$((seed + 10))
done
(cd _proof/final && time timeout 900 python benchmark/run.py --workload light-seq-100.sequence --seed 2147494861 --seconds 25 --trace 1 \
  > ../../chiprun_out/pr35/B.final.traced.out 2> ../../chiprun_out/pr35/B.final.traced.err
echo FINAL_TRACED_RC=$?; grep -E "^check|^benchmark:" ../../chiprun_out/pr35/B.final.traced.err | tr '\n' ';'; echo; head -c 3000 ../../chiprun_out/pr35/B.final.traced.out)
python - <<'PY'
import glob, json
for path in sorted(glob.glob("chiprun_out/pr35/B.pairs.*.jsonl")):
    for line in open(path):
        r = json.loads(line)
        res = r.get("result", {})
        if r["trace"]:
            print(r["workload"], r["side"], "traced", res.get("correct"), json.dumps({k: v["value"] for k, v in res.get("metrics", {}).items()}))
PY
