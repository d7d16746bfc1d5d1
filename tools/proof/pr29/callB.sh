set -x
echo JAX_COMPILATION_CACHE_DIR=${JAX_COMPILATION_CACHE_DIR:-unset}; ls ${JAX_COMPILATION_CACHE_DIR:-/nonexistent} | head -5; du -sh ${JAX_COMPILATION_CACHE_DIR:-/nonexistent}
mkdir -p chiprun_out
# 0. does the thread of a process's first flush matter for the time its programs take to load?
python tools/proof/first_flush_thread.py thread > chiprun_out/thread.thread.json; tail -c 2500 chiprun_out/thread.thread.json
python tools/proof/first_flush_thread.py main > chiprun_out/thread.main.json; tail -c 2500 chiprun_out/thread.main.json
# 1. one run = one tree, at the timed size, on the chip
time python tools/proof/call_tree.py hub-175.catchup catchup.verify_run > chiprun_out/hub-175.tree.json; echo TREE_RC=$?; tail -c 3000 chiprun_out/hub-175.tree.json
# 2. the new cell: the control on the driver's own path, then two more traced seeds
time python benchmark/prove.py --workload hub-175.catchup --seeds 2147487201 \
  --out chiprun_out/hub-175.control.jsonl --timeout 1200 -- --control unsent_third
echo CONTROL_RC=$?
time python benchmark/prove.py --workload hub-175.catchup --trace-seeds 3000000502,3000000503 \
  --out chiprun_out/hub-175.traced.jsonl --timeout 1200
echo TRACED_RC=$?
# 3. the accepted cells, parent against change, alternating pairs, one traced pair each
time python tools/proof/pairs.py --workload commit-10k.verify-commit --seeds 2147487501,2147487502,2147487503 --trace-seed 3000000511 --out chiprun_out/commit-10k.pairs.jsonl
echo PAIRS_10K_RC=$?
time python tools/proof/pairs.py --workload commit-1024.verify-commit --seeds 2147487601,2147487602,2147487603 --trace-seed 3000000521 --out chiprun_out/commit-1024.pairs.jsonl
echo PAIRS_1024_RC=$?
# 4. the parent's program under this PR's benchmark files: the new cell, traced
rm -rf _proof/overlay && cp -r _parent _proof/overlay && cp BENCHMARK.json _proof/overlay/ && cp -r benchmark/. _proof/overlay/benchmark/
( cd _proof/overlay && time python3 benchmark/run.py --workload hub-175.catchup --seed 2147487401 --seconds 25 --trace 1 > ../../chiprun_out/overlay.hub-175.json 2> ../../chiprun_out/overlay.hub-175.err; echo OVERLAY_NEW_RC=$? )
tail -c 2500 chiprun_out/overlay.hub-175.json
