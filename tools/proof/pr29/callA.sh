set -x
echo JAX_COMPILATION_CACHE_DIR=${JAX_COMPILATION_CACHE_DIR:-unset}
# 1. the parent commit, as committed: it has no such cell and has to fail at once
( cd _parent && time timeout 120 python3 benchmark/run.py --workload hub-175.catchup --seed 2147487001 --seconds 25 --trace 0 ; echo PARENT_RC=$? )
# 2. the new cell on the change: one traced run first (cold: compiles or loads); stop if it is not sound
time python benchmark/prove.py --workload hub-175.catchup --trace-seeds 3000000501 \
  --out chiprun_out/hub-175.first.jsonl --keep-trace chiprun_out/hub-175.trace --timeout 2400
rc=$?; echo FIRST_RC=$rc
if [ $rc -ne 0 ]; then tail -c 6000 chiprun_out/hub-175.first.jsonl; exit $rc; fi
# 3. two sets of six seeds, two more traced seeds
time python benchmark/prove.py --workload hub-175.catchup \
  --seeds 2147487101,2147487102,2147487103,2147487104,2147487105,2147487106 --sets 2 \
  --trace-seeds 3000000502,3000000503 --out chiprun_out/hub-175.prove.jsonl --timeout 1200
echo PROVE_RC=$?
# 4. the control on the driver's own path: has to read not correct
time python benchmark/prove.py --workload hub-175.catchup --seeds 2147487201 \
  --out chiprun_out/hub-175.control.jsonl --timeout 1200 -- --control unsent_third
echo CONTROL_RC=$?
