set -x
# PR 37, call A: the claimed cell. Parent 60e4356 (_parent/: a `git archive` copy with this PR's BENCHMARK.json and benchmark/ laid
# over it, as the driver lays them; both are the parent's byte for byte) against the committed files alone (_proof/final/: a
# `git archive $(git write-tree)` copy), six untraced pairs in alternating order and one traced pair, one compile cache for both
# sides; then, in that copy, what the store holds after one call, read back (store_readback.py).
echo JAX_COMPILATION_CACHE_DIR=${JAX_COMPILATION_CACHE_DIR:-unset}
export JAX_COMPILATION_CACHE_DIR=${JAX_COMPILATION_CACHE_DIR:-/root/repo/.jax_cache}
mkdir -p chiprun_out/pr37
C=light-seq-100.sequence
time python tools/proof/pairs.py --workload $C \
  --seeds 2147496101,2147496102,2147496103,2147496104,2147496105,2147496106 \
  --trace-seed 2147496111 --change _proof/final --out chiprun_out/pr37/A.pairs.jsonl
echo PAIRS_RC=$?
(cd _proof/final && time python tools/proof/pr37/store_readback.py --seed 2147496121) > chiprun_out/pr37/A.readback.out 2> chiprun_out/pr37/A.readback.err
echo READBACK_RC=$?; cat chiprun_out/pr37/A.readback.out; tail -n 5 chiprun_out/pr37/A.readback.err
python - <<'PY'
import json
for line in open("chiprun_out/pr37/A.pairs.jsonl"):
    r = json.loads(line)
    res = r.get("result", {})
    print(r["side"], r["seed"], r["trace"], r["rc"], res.get("correct"), res.get("samples"), json.dumps(res.get("spans_p50")))
    if r["trace"]:
        print(r["side"], "traced", json.dumps({k: v["value"] for k, v in res.get("metrics", {}).items()}))
        print(r["side"], "notes", json.dumps({k: res.get("notes", {}).get(k) for k in ("entry_probes", "judge_s")}), res.get("device"), res.get("memory_peak_bytes"))
        print(r["side"], "breakdown", json.dumps(res.get("breakdown"))[:3000])
PY
