set -x
# PR 32, call 3: the final tree (_proof/final, a `git archive $(git write-tree)` copy: the committed files are enough) against its parent
# (_parent/). Three more alternating pairs in each claimed cell (six in all with calls 1 and 2), one more at 1024, a seed to each pair;
# then the control on the program's own path in all three cells, which has to read not correct.
echo JAX_COMPILATION_CACHE_DIR=${JAX_COMPILATION_CACHE_DIR:-unset}
export JAX_COMPILATION_CACHE_DIR=${JAX_COMPILATION_CACHE_DIR:-/root/repo/.jax_cache}
mkdir -p chiprun_out
time python tools/proof/pairs.py --workload commit-10k.verify-commit --seeds 2147491104,2147491105,2147491106 --change _proof/final --out chiprun_out/pr32.commit-10k.final.jsonl
echo PAIRS_10K_RC=$?
time python tools/proof/pairs.py --workload hub-175.catchup --seeds 2147491204,2147491205,2147491206 --change _proof/final --out chiprun_out/pr32.hub-175.final.jsonl
echo PAIRS_HUB_RC=$?
time python tools/proof/pairs.py --workload commit-1024.verify-commit --seeds 2147491304 --change _proof/final --out chiprun_out/pr32.commit-1024.final.jsonl
echo PAIRS_1024_RC=$?
for w in commit-10k.verify-commit hub-175.catchup commit-1024.verify-commit; do
  (cd _proof/final && python benchmark/prove.py --workload $w --seeds 2147491401 --out ../../chiprun_out/pr32.$w.control.jsonl --timeout 900 -- --control unsent_third)
  echo CONTROL_${w}_RC=$?
done
python tools/proof/judge_times.py chiprun_out/pr32.*.control.jsonl
du -sh $JAX_COMPILATION_CACHE_DIR
