set -x
echo JAX_COMPILATION_CACHE_DIR=${JAX_COMPILATION_CACHE_DIR:-unset}
# the parent commit, as committed: it has no such cell and has to fail at once
( cd _parent && time timeout 120 python3 benchmark/run.py --workload hub-175.catchup --seed 2147487001 --seconds 25 --trace 0 ; echo PARENT_RC=$? )
# the new cell on the change: one traced run (cold: compiles), then two untraced
time python benchmark/prove.py --workload hub-175.catchup --seeds 2147487101,2147487102 --sets 1 \
  --trace-seeds 3000000501 --out chiprun_out/hub-175.first.jsonl --keep-trace chiprun_out/hub-175.trace --timeout 2400
echo PROVE_RC=$?
