set -x
# PR 34, call B: the new cell's two sets of six runs (the same seeds in both) and two traced runs, then one traced pair of each
# accepted cell, parent 99f9bcf (_parent/: a `git archive` copy with this PR's benchmark files laid over it) against the tree.
echo JAX_COMPILATION_CACHE_DIR=${JAX_COMPILATION_CACHE_DIR:-unset}
export JAX_COMPILATION_CACHE_DIR=${JAX_COMPILATION_CACHE_DIR:-/root/repo/.jax_cache}
mkdir -p chiprun_out/pr34
time python benchmark/prove.py --workload light-seq-100.sequence \
  --seeds 2147494421,2147494422,2147494423,2147494424,2147494425,2147494426 --sets 2 \
  --trace-seeds 2147494431,2147494432 --out chiprun_out/pr34/B.sets.jsonl --timeout 900
echo SETS_RC=$?
for W in commit-10k.verify-commit hub-175.catchup commit-1024.verify-commit; do
  time python tools/proof/pairs.py --workload $W --seeds "" --trace-seed 2147494441 --out chiprun_out/pr34/B.pairs.$W.jsonl
  echo PAIRS_RC_$W=$?
done
