set -x
# PR 34, call E (after the review): the executor hand-over in _verify_sequential and the entry's bound by what it sees. (a) the
# parent 99f9bcf (_parent/: a `git archive` copy with this PR's benchmark files laid over it): it has to end at its first warm-up
# call, exit code 1, on the count of flushes; (b) the change: two sets of six runs on the same seeds and one traced run;
# (c) one set of six with a window of 50 s, for what twice the samples do to verify_ms_p95's spread.
echo JAX_COMPILATION_CACHE_DIR=${JAX_COMPILATION_CACHE_DIR:-unset}
export JAX_COMPILATION_CACHE_DIR=${JAX_COMPILATION_CACHE_DIR:-/root/repo/.jax_cache}
mkdir -p chiprun_out/pr34
C=light-seq-100.sequence
(cd _parent && time timeout 900 python benchmark/run.py --workload $C --seed 2147494501 --seconds 25 --trace 1 \
  > ../chiprun_out/pr34/E.parent.out 2> ../chiprun_out/pr34/E.parent.err; echo PARENT_RC=$?; tail -c 700 ../chiprun_out/pr34/E.parent.err; wc -c ../chiprun_out/pr34/E.parent.out)
time python benchmark/prove.py --workload $C \
  --seeds 2147494511,2147494512,2147494513,2147494514,2147494515,2147494516 --sets 2 \
  --trace-seeds 2147494521 --out chiprun_out/pr34/E.sets.jsonl --timeout 900
echo SETS_RC=$?
time python benchmark/prove.py --workload $C --seconds 50 \
  --seeds 2147494531,2147494532,2147494533,2147494534,2147494535,2147494536 --sets 1 \
  --out chiprun_out/pr34/E.long.jsonl --timeout 900
echo LONG_RC=$?
# (d) the committed files alone: a `git archive $(git write-tree)` copy under _proof/final/, on the machine's compile cache
(cd _proof/final && time timeout 900 python benchmark/run.py --workload $C --seed 2147494541 --seconds 25 --trace 1 \
  > ../../chiprun_out/pr34/E.final.traced.out 2> ../../chiprun_out/pr34/E.final.traced.err
echo FINAL_TRACED_RC=$?; grep -E "^check|^benchmark:" ../../chiprun_out/pr34/E.final.traced.err | tr '\n' ';'; echo; head -c 2500 ../../chiprun_out/pr34/E.final.traced.out
time timeout 900 python benchmark/run.py --workload commit-1024.verify-commit --seed 2147494542 --seconds 25 --trace 1 \
  > ../../chiprun_out/pr34/E.final.1024.out 2> ../../chiprun_out/pr34/E.final.1024.err
echo FINAL_1024_RC=$?; grep -E "^check|^benchmark:" ../../chiprun_out/pr34/E.final.1024.err | tr '\n' ';'; echo; head -c 1500 ../../chiprun_out/pr34/E.final.1024.out)
