set -x
# PR 32, call 2: hub-175.catchup (the other claimed cell) and commit-1024.verify-commit (the guard). In each: one traced run a side with the
# slice kept and its stage table, then alternating untraced pairs, a seed to each pair. Parent d33ba66 under _parent/, the change the tree itself.
echo JAX_COMPILATION_CACHE_DIR=${JAX_COMPILATION_CACHE_DIR:-unset}
export JAX_COMPILATION_CACHE_DIR=${JAX_COMPILATION_CACHE_DIR:-/root/repo/.jax_cache}
du -sh $JAX_COMPILATION_CACHE_DIR
mkdir -p chiprun_out
for w in hub-175.catchup commit-1024.verify-commit; do
  time python benchmark/prove.py --workload $w --trace-seeds 3000000821 \
    --out chiprun_out/pr32.$w.change.traced.jsonl --keep-trace $PWD/chiprun_out/pr32.$w.change.trace --timeout 2400
  echo TRACED_CHANGE_${w}_RC=$?
  python tools/profile_report.py chiprun_out/pr32.$w.change.trace/slice.xplane.pb.gz > chiprun_out/pr32.$w.change.profile.txt 2>&1; head -c 3000 chiprun_out/pr32.$w.change.profile.txt
  (cd _parent && time python benchmark/prove.py --workload $w --trace-seeds 3000000821 \
    --out ../chiprun_out/pr32.$w.parent.traced.jsonl --keep-trace $PWD/../chiprun_out/pr32.$w.parent.trace --timeout 2400)
  echo TRACED_PARENT_${w}_RC=$?
  python tools/profile_report.py chiprun_out/pr32.$w.parent.trace/slice.xplane.pb.gz > chiprun_out/pr32.$w.parent.profile.txt 2>&1; head -c 3000 chiprun_out/pr32.$w.parent.profile.txt
done
time python tools/proof/pairs.py --workload hub-175.catchup --seeds 2147491201,2147491202,2147491203 --out chiprun_out/pr32.hub-175.pairs.jsonl
echo PAIRS_HUB_RC=$?
time python tools/proof/pairs.py --workload commit-1024.verify-commit --seeds 2147491301,2147491302,2147491303 --out chiprun_out/pr32.commit-1024.pairs.jsonl
echo PAIRS_1024_RC=$?
du -sh $JAX_COMPILATION_CACHE_DIR
