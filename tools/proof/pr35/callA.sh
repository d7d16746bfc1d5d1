set -x
# PR 35, call A: the claimed cell. Parent f4efa38 (_parent/: a `git archive` copy with this PR's BENCHMARK.json and benchmark/ laid
# over it, as the driver lays them) against the tree, six untraced pairs in alternating order and one traced pair, one compile cache
# for both sides; then the two controls ISSUE 35 names, on the change: each has to read not `correct`.
echo JAX_COMPILATION_CACHE_DIR=${JAX_COMPILATION_CACHE_DIR:-unset}
export JAX_COMPILATION_CACHE_DIR=${JAX_COMPILATION_CACHE_DIR:-/root/repo/.jax_cache}
mkdir -p chiprun_out/pr35
C=light-seq-100.sequence
time python tools/proof/pairs.py --workload $C \
  --seeds 2147494801,2147494802,2147494803,2147494804,2147494805,2147494806 \
  --trace-seed 2147494811 --out chiprun_out/pr35/A.pairs.jsonl
echo PAIRS_RC=$?
seed=2147494821
for X in links_unchecked unsent_third; do
  time timeout 900 python benchmark/run.py --workload $C --seed $seed --seconds 25 --trace 0 --control $X \
    > chiprun_out/pr35/A.$X.out 2> chiprun_out/pr35/A.$X.err
  echo RC_$X=$?; grep -E "^check|^benchmark:" chiprun_out/pr35/A.$X.err | tr '\n' ';'; echo
  python - $X <<'PY'
import json, sys
o = json.loads(open(f"chiprun_out/pr35/A.{sys.argv[1]}.out").read().strip().splitlines()[-1])
print(sys.argv[1], "correct", o["correct"], "attempted", o["attempted"], "failed", o["failed"], o["flush"], json.dumps(o["notes"]["entry_probes"]), o["notes"]["judge_s"])
PY
  seed=$((seed + 1))
done
python - <<'PY'
import json
for line in open("chiprun_out/pr35/A.pairs.jsonl"):
    r = json.loads(line)
    res = r.get("result", {})
    if r["trace"]:
        print(r["side"], "traced", json.dumps({k: v["value"] for k, v in res.get("metrics", {}).items()}))
        print(r["side"], "notes", json.dumps({k: res.get("notes", {}).get(k) for k in ("entry_probes", "judge_s")}), res.get("device"), res.get("memory_peak_bytes"))
PY
