set -x
# PR 36, call B: the new cell over two sets of six seeds (a seed of its own each), untraced, then one traced run; the spreads of
# each set against half the bounds (sigs_per_s 4%, verify_ms_p50 4%, verify_ms_p95 6.5%).
echo JAX_COMPILATION_CACHE_DIR=${JAX_COMPILATION_CACHE_DIR:-unset}
export JAX_COMPILATION_CACHE_DIR=${JAX_COMPILATION_CACHE_DIR:-/root/repo/.jax_cache}
mkdir -p chiprun_out/pr36
C=live-10k.vote-commit
time python benchmark/prove.py --workload $C --seeds 2147495101,2147495102,2147495103,2147495104,2147495105,2147495106 \
  --out chiprun_out/pr36/B.set1.jsonl
time python benchmark/prove.py --workload $C --seeds 2147495201,2147495202,2147495203,2147495204,2147495205,2147495206 \
  --trace-seeds 2147495211 --out chiprun_out/pr36/B.set2.jsonl
python - <<'PY'
import json
for f in ("chiprun_out/pr36/B.set1.jsonl", "chiprun_out/pr36/B.set2.jsonl"):
    for line in open(f):
        r = json.loads(line); res = r.get("result", {})
        print(r["seed"], r["trace"], r["rc"], res.get("correct"), res.get("samples"), json.dumps(res.get("spans_p50")),
              json.dumps(res.get("setup_split")), res.get("notes", {}).get("judge_s"))
        if r["trace"]:
            print(json.dumps({k: v["value"] for k, v in res.get("metrics", {}).items()}))
            print(json.dumps(res.get("device")), json.dumps(res.get("breakdown")))
PY
