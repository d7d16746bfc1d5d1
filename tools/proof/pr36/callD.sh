set -x
# PR 36, call D: the committed files alone (_proof/final/: `git archive $(git write-tree)`), the new cell, one traced run.
echo JAX_COMPILATION_CACHE_DIR=${JAX_COMPILATION_CACHE_DIR:-unset}
export JAX_COMPILATION_CACHE_DIR=${JAX_COMPILATION_CACHE_DIR:-/root/repo/.jax_cache}
mkdir -p chiprun_out/pr36
cd _proof/final
time timeout 1500 python benchmark/run.py --workload live-10k.vote-commit --seed 2147495501 --seconds 25 --trace 1 \
  > /root/repo/chiprun_out/pr36/D.final.out 2> /root/repo/chiprun_out/pr36/D.final.err
echo RC=$?
cd /root/repo
grep -E "^check|^benchmark:" chiprun_out/pr36/D.final.err | tr '\n' ';'; echo
python - <<'PY'
import json
o = json.loads(open("chiprun_out/pr36/D.final.out").read().strip().splitlines()[-1])
print("correct", o["correct"], "attempted", o["attempted"], "failed", o["failed"], o["flush"])
print(json.dumps({k: v["value"] for k, v in o["metrics"].items()}))
print(o["device"], o["setup_split"], o["notes"]["entry_probes"], o["notes"]["judge_s"])
PY
