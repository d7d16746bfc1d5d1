set -x
# second session, call 1: the ladder on the chunk bucket, the bounded lane, the six readers of a run's flush
echo JAX_COMPILATION_CACHE_DIR=${JAX_COMPILATION_CACHE_DIR:-unset}; du -sh ${JAX_COMPILATION_CACHE_DIR:-/nonexistent}
mkdir -p chiprun_out
# 1. the parent's program under this PR's benchmark files: the driver asks the lane and ends the run at once
rm -rf _proof/overlay && mkdir -p _proof && cp -r _parent _proof/overlay && cp BENCHMARK.json _proof/overlay/ && cp -r benchmark/. _proof/overlay/benchmark/
( cd _proof/overlay && time timeout 300 python3 benchmark/run.py --workload hub-175.catchup --seed 2147488001 --seconds 25 --trace 0 > ../../chiprun_out/g.overlay.json 2> ../../chiprun_out/g.overlay.err; echo OVERLAY_RC=$? ; tail -c 600 ../../chiprun_out/g.overlay.err )
# 2. the new cell, traced, once; stop if it is not sound
time python benchmark/prove.py --workload hub-175.catchup --trace-seeds 3000000601 \
  --out chiprun_out/hub-175.g.first.jsonl --keep-trace chiprun_out/hub-175.g.trace --timeout 2400
rc=$?; echo FIRST_RC=$rc
python tools/proof/judge_times.py chiprun_out/hub-175.g.first.jsonl
if [ $rc -ne 0 ]; then tail -c 6000 chiprun_out/hub-175.g.first.jsonl; exit $rc; fi
# 3. one set of six fresh seeds
time python benchmark/prove.py --workload hub-175.catchup \
  --seeds 2147488101,2147488102,2147488103,2147488104,2147488105,2147488106 --sets 1 \
  --out chiprun_out/hub-175.g.jsonl --timeout 1200
echo PROVE_RC=$?
# 4. the control on the driver's own path: has to read not correct
time python benchmark/prove.py --workload hub-175.catchup --seeds 2147488201 \
  --out chiprun_out/hub-175.g.control.jsonl --timeout 1200 -- --control unsent_third
echo CONTROL_RC=$?
python tools/proof/judge_times.py chiprun_out/hub-175.g.jsonl chiprun_out/hub-175.g.control.jsonl
du -sh ${JAX_COMPILATION_CACHE_DIR:-/nonexistent}
