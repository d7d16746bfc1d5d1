set -x
# PR 36, call C: what the memo costs and saves (one process, the cell's driver, memo on and off in alternating blocks), then
# the two accepted cells on whose path the span and record changes sit, parent 0b19e64 (_parent/, this PR's benchmark files
# laid over it) against the tree: one untraced and one traced pair each.
echo JAX_COMPILATION_CACHE_DIR=${JAX_COMPILATION_CACHE_DIR:-unset}
export JAX_COMPILATION_CACHE_DIR=${JAX_COMPILATION_CACHE_DIR:-/root/repo/.jax_cache}
mkdir -p chiprun_out/pr36
time python tools/proof/pr36/memo_ab.py 24 3
for C in commit-10k.verify-commit commit-1024.verify-commit; do
  time python tools/proof/pairs.py --workload $C --seeds 2147495401,2147495402 --trace-seed 2147495411 \
    --out chiprun_out/pr36/C.$C.pairs.jsonl
  python - $C <<'PY'
import json, sys
for line in open(f"chiprun_out/pr36/C.{sys.argv[1]}.pairs.jsonl"):
    r = json.loads(line); res = r.get("result", {})
    print(r["side"], r["seed"], r["trace"], r["rc"], res.get("correct"), json.dumps({k: v["value"] for k, v in res.get("metrics", {}).items()}),
          json.dumps(res.get("spans_p50")), json.dumps(res.get("device")))
PY
done
