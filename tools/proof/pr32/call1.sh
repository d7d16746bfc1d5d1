set -x
# PR 32, call 1 (after uptree_micro.py, the stage alone): commit-10k.verify-commit. One traced run a side with the slice kept and
# profile_report.py's stage table of each (the parent d33ba66 under _parent/, a `git archive` copy; the change is the tree itself),
# then three alternating untraced pairs, a seed to each pair. One compile cache for both sides.
echo JAX_COMPILATION_CACHE_DIR=${JAX_COMPILATION_CACHE_DIR:-unset}
export JAX_COMPILATION_CACHE_DIR=${JAX_COMPILATION_CACHE_DIR:-/root/repo/.jax_cache}
du -sh $JAX_COMPILATION_CACHE_DIR
mkdir -p chiprun_out
time python benchmark/prove.py --workload commit-10k.verify-commit --trace-seeds 3000000811 \
  --out chiprun_out/pr32.commit-10k.change.traced.jsonl --keep-trace $PWD/chiprun_out/pr32.commit-10k.change.trace --timeout 2400
echo TRACED_CHANGE_RC=$?
python tools/profile_report.py chiprun_out/pr32.commit-10k.change.trace/slice.xplane.pb.gz > chiprun_out/pr32.commit-10k.change.profile.txt 2>&1; head -c 4500 chiprun_out/pr32.commit-10k.change.profile.txt
(cd _parent && time python benchmark/prove.py --workload commit-10k.verify-commit --trace-seeds 3000000811 \
  --out ../chiprun_out/pr32.commit-10k.parent.traced.jsonl --keep-trace $PWD/../chiprun_out/pr32.commit-10k.parent.trace --timeout 2400)
echo TRACED_PARENT_RC=$?
python tools/profile_report.py chiprun_out/pr32.commit-10k.parent.trace/slice.xplane.pb.gz > chiprun_out/pr32.commit-10k.parent.profile.txt 2>&1; head -c 4500 chiprun_out/pr32.commit-10k.parent.profile.txt
time python tools/proof/pairs.py --workload commit-10k.verify-commit --seeds 2147491101,2147491102,2147491103 --out chiprun_out/pr32.commit-10k.pairs.jsonl
echo PAIRS_10K_RC=$?
du -sh $JAX_COMPILATION_CACHE_DIR
