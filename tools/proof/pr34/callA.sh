set -x
# PR 34, call A: the new cell's first runs on the chip. (a) the parent 99f9bcf (a `git archive` copy under _parent/ with this
# PR's benchmark files laid over it): does it fail at once, or end, or hang? (b) what one timed-size call leaves in the ring;
# (c) the change: one traced run cold, one untraced warm. One compile cache for both sides.
echo JAX_COMPILATION_CACHE_DIR=${JAX_COMPILATION_CACHE_DIR:-unset}
export JAX_COMPILATION_CACHE_DIR=${JAX_COMPILATION_CACHE_DIR:-/root/repo/.jax_cache}
du -sh $JAX_COMPILATION_CACHE_DIR
mkdir -p chiprun_out/pr34
C=light-seq-100.sequence
(cd _parent && time timeout 600 python benchmark/run.py --workload $C --seed 2147494401 --seconds 25 --trace 0 \
  > ../chiprun_out/pr34/A.parent.out 2> ../chiprun_out/pr34/A.parent.err; echo PARENT_RC=$?; tail -c 600 ../chiprun_out/pr34/A.parent.err)
time python benchmark/run.py --workload $C --seed 2147494411 --seconds 25 --trace 1 --keep-trace $PWD/chiprun_out/pr34/A.trace \
  > chiprun_out/pr34/A.change.traced.out 2> chiprun_out/pr34/A.change.traced.err
echo TRACED_RC=$?; tail -c 1500 chiprun_out/pr34/A.change.traced.err; tail -c 6000 chiprun_out/pr34/A.change.traced.out
time python tools/proof/call_tree.py $C light.verify_run > chiprun_out/pr34/A.call_tree.json 2> chiprun_out/pr34/A.call_tree.err
echo TREE_RC=$?; tail -c 400 chiprun_out/pr34/A.call_tree.err; head -c 3000 chiprun_out/pr34/A.call_tree.json
time python benchmark/run.py --workload $C --seed 2147494412 --seconds 25 --trace 0 \
  > chiprun_out/pr34/A.change.out 2> chiprun_out/pr34/A.change.err
echo UNTRACED_RC=$?; tail -c 1200 chiprun_out/pr34/A.change.err; tail -c 5000 chiprun_out/pr34/A.change.out
python tools/profile_report.py chiprun_out/pr34/A.trace/slice.xplane.pb.gz > chiprun_out/pr34/A.profile.txt 2>&1; head -c 3000 chiprun_out/pr34/A.profile.txt
du -sh $JAX_COMPILATION_CACHE_DIR
