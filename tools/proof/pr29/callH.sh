set -x
# second session, call 2: everything on the change's side from _proof/final, a `git archive $(git write-tree)` copy of the final tree
echo JAX_COMPILATION_CACHE_DIR=${JAX_COMPILATION_CACHE_DIR:-unset}; du -sh ${JAX_COMPILATION_CACHE_DIR:-/nonexistent}
mkdir -p chiprun_out
OUT=$PWD/chiprun_out
# 1. the new cell from the committed files alone: one traced run, then a second set of six fresh seeds
( cd _proof/final && python benchmark/prove.py --workload hub-175.catchup --trace-seeds 3000000701 \
    --seeds 2147488301,2147488302,2147488303,2147488304,2147488305,2147488306 --sets 1 \
    --out $OUT/hub-175.h.jsonl --timeout 1200 ; echo HUB_RC=$? )
python tools/proof/judge_times.py chiprun_out/hub-175.h.jsonl
# 2. the accepted cells, parent against change, alternating pairs
python tools/proof/pairs.py --workload commit-10k.verify-commit --seeds 2147488401,2147488402 --change _proof/final --out chiprun_out/commit-10k.h.pairs.jsonl
echo PAIRS_10K_RC=$?
python tools/proof/pairs.py --workload commit-1024.verify-commit --seeds 2147488501,2147488502 --change _proof/final --out chiprun_out/commit-1024.h.pairs.jsonl
echo PAIRS_1024_RC=$?
# 3. the new cell once more, now after runs of another kind: does its set-up depend on what ran before it?
( cd _proof/final && python benchmark/prove.py --workload hub-175.catchup --seeds 2147488307 \
    --out $OUT/hub-175.h.after.jsonl --timeout 1200 ; echo HUB_AFTER_RC=$? )
python tools/proof/judge_times.py chiprun_out/hub-175.h.after.jsonl
du -sh ${JAX_COMPILATION_CACHE_DIR:-/nonexistent}
