set -x
# PR 31, call 4: the final tree (_proof/final, a `git archive $(git write-tree)` copy) against its parent in the
# benchmark's own harness, after call 3's repair: set-up at 1024 first, then one pair in each large cell, then the control.
echo JAX_COMPILATION_CACHE_DIR=${JAX_COMPILATION_CACHE_DIR:-unset}
export JAX_COMPILATION_CACHE_DIR=${JAX_COMPILATION_CACHE_DIR:-/root/repo/.jax_cache}
mkdir -p chiprun_out
time python tools/proof/pairs.py --workload commit-1024.verify-commit --seeds 2147490601,2147490602,2147490603 --change _proof/final --out chiprun_out/pr31.commit-1024.final.jsonl
echo PAIRS_1024_RC=$?
time python tools/proof/pairs.py --workload commit-10k.verify-commit --seeds 2147490701,2147490702 --change _proof/final --out chiprun_out/pr31.commit-10k.final.jsonl
echo PAIRS_10K_RC=$?
time python tools/proof/pairs.py --workload hub-175.catchup --seeds 2147490801,2147490802 --change _proof/final --out chiprun_out/pr31.hub-175.final.jsonl
echo PAIRS_HUB_RC=$?
(cd _proof/final && python benchmark/prove.py --workload commit-1024.verify-commit --seeds 2147490901 --out ../../chiprun_out/pr31.commit-1024.final.control.jsonl --timeout 900 -- --control unsent_third)
echo CONTROL_1024_RC=$?
