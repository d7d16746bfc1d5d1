set -x
# The memo digest pass measured on the chip, run from the root of a checkout. Parent fbba282 (_parent/: a `git archive`
# copy; the benchmark files are the same on both sides) against the committed files alone (_proof/final/: a
# `git archive $(git write-tree)` copy). The claimed cell first: six untraced pairs
# in alternating order and one traced pair; then the digest pass alone on the chip's host, native against the hashlib loop,
# in one process; last one untraced pair of commit-10k.verify-commit, where the memo is off. One compile cache for all.
echo JAX_COMPILATION_CACHE_DIR=${JAX_COMPILATION_CACHE_DIR:-unset}
export JAX_COMPILATION_CACHE_DIR=${JAX_COMPILATION_CACHE_DIR:-$PWD/.jax_cache}
O=chiprun_out/pr39
mkdir -p $O
time python tools/proof/pairs.py --workload live-10k.vote-commit \
  --seeds 2147500101,2147500102,2147500103,2147500104,2147500105,2147500106 \
  --trace-seed 2147500111 --change _proof/final --out $O/live.pairs.jsonl
echo PAIRS_RC=$?
(cd _proof/final && python tools/proof/pr39/digest_micro.py) > $O/micro.out 2>&1
echo MICRO_RC=$?; cat $O/micro.out
python - <<'PY'
import json
for line in open("chiprun_out/pr39/live.pairs.jsonl"):
    r = json.loads(line)
    res = r.get("result", {})
    print(r["side"], r["seed"], r["trace"], r["rc"], r["wall_s"], res.get("correct"), res.get("samples"),
          json.dumps(res.get("spans_p50")))
    if r["trace"]:
        print(r["side"], "traced", json.dumps({k: v["value"] for k, v in res.get("metrics", {}).items()}))
        print(r["side"], "notes", json.dumps({k: res.get("notes", {}).get(k) for k in ("judge_s",)}),
              res.get("device"), res.get("memory_peak_bytes"))
        print(r["side"], "breakdown", json.dumps(res.get("breakdown"))[:2500])
PY
time python tools/proof/pairs.py --workload commit-10k.verify-commit --seeds 2147500201 \
  --change _proof/final --out $O/commit10k.pairs.jsonl
echo PAIRS10K_RC=$?
