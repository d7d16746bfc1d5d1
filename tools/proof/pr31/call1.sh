set -x
# PR 31, call 1: the staged tree (_proof/final, a `git archive $(git write-tree)` copy) against its parent (_parent/, a `git archive HEAD` copy), each cell once or twice a side
# and one traced pair; then the control and what one call leaves in the ring, on the change. One compile cache for
# both sides, so that the change's first run shows what an edit of ops/msm_jax.py costs a machine that kept the parent's.
echo JAX_COMPILATION_CACHE_DIR=${JAX_COMPILATION_CACHE_DIR:-unset}
export JAX_COMPILATION_CACHE_DIR=${JAX_COMPILATION_CACHE_DIR:-/root/repo/.jax_cache}
mkdir -p chiprun_out
time python tools/proof/pairs.py --workload commit-1024.verify-commit --seeds 2147490101,2147490102 --trace-seed 3000000711 --change _proof/final --out chiprun_out/pr31.commit-1024.pairs.jsonl
echo PAIRS_1024_RC=$?
time python tools/proof/pairs.py --workload commit-10k.verify-commit --seeds 2147490201,2147490202 --trace-seed 3000000721 --change _proof/final --out chiprun_out/pr31.commit-10k.pairs.jsonl
echo PAIRS_10K_RC=$?
time python tools/proof/pairs.py --workload hub-175.catchup --seeds 2147490301 --trace-seed 3000000731 --change _proof/final --out chiprun_out/pr31.hub-175.pairs.jsonl
echo PAIRS_HUB_RC=$?
du -sh $JAX_COMPILATION_CACHE_DIR
for w in commit-1024.verify-commit commit-10k.verify-commit hub-175.catchup; do
  python benchmark/prove.py --workload $w --seeds 2147490401 --out chiprun_out/pr31.$w.control.jsonl --timeout 900 -- --control unsent_third
  echo CONTROL_${w}_RC=$?
done
python tools/proof/judge_times.py chiprun_out/pr31.*.control.jsonl
python tools/proof/call_tree.py commit-1024.verify-commit commit.verify > chiprun_out/pr31.commit-1024.tree.json; echo TREE_1024_RC=$?; tail -c 1500 chiprun_out/pr31.commit-1024.tree.json
python tools/proof/call_tree.py commit-10k.verify-commit commit.verify > chiprun_out/pr31.commit-10k.tree.json; echo TREE_10K_RC=$?; tail -c 1500 chiprun_out/pr31.commit-10k.tree.json
python tools/proof/call_tree.py hub-175.catchup catchup.verify_run > chiprun_out/pr31.hub-175.tree.json; echo TREE_HUB_RC=$?; tail -c 1500 chiprun_out/pr31.hub-175.tree.json
