set -x
# The cell commit-10k.tampered on the chip, run from the root of a checkout that holds the change's committed files in
# _proof/final/ (a `git archive $(git write-tree)` copy) and the parent's in _parent/ (a `git archive` copy of the parent
# commit with the change's BENCHMARK.json and benchmark/ laid over it, so that both sides run the same benchmark files).
# One compile cache for all.
# 1. the change: six untraced runs and one traced; 2. the parent on the same cell, once untraced and once traced;
# 3. a fresh process: prewarm(10000), then a node's first valid and first forged 10,000-signature commit.
echo JAX_COMPILATION_CACHE_DIR=${JAX_COMPILATION_CACHE_DIR:-unset}
export JAX_COMPILATION_CACHE_DIR=${JAX_COMPILATION_CACHE_DIR:-$PWD/_proof/jaxcache}
O=$PWD/chiprun_out/pr41
mkdir -p $O
(cd _proof/final && time python benchmark/prove.py --workload commit-10k.tampered \
  --seeds 2147541101,2147541102,2147541103,2147541104,2147541105,2147541106 \
  --trace-seeds 2147541111 --out $O/tampered.jsonl)
echo CHANGE_RC=$?
(cd _parent && time python benchmark/prove.py --workload commit-10k.tampered \
  --seeds 2147541121 --trace-seeds 2147541122 --out $O/parent.jsonl)
echo PARENT_RC=$?
(cd _proof/final && time python tools/proof/pr41/prewarm_leaf.py --seed 2147541131) > $O/prewarm.out 2>&1
echo PREWARM_RC=$?
tail -n 6 $O/prewarm.out
python tools/proof/pr41/show.py $O/tampered.jsonl $O/parent.jsonl
