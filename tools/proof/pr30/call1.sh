set -x
echo JAX_COMPILATION_CACHE_DIR=${JAX_COMPILATION_CACHE_DIR:-unset}; du -sh ${JAX_COMPILATION_CACHE_DIR:-/nonexistent}
mkdir -p chiprun_out
# 1. the premise first: one traced run of the change at 10k with the slice kept, and the stage table of it
time python benchmark/prove.py --workload commit-10k.verify-commit --trace-seeds 3000000601 \
  --out chiprun_out/pr30.commit-10k.first.jsonl --keep-trace chiprun_out/pr30.commit-10k.trace --timeout 2400
echo FIRST_RC=$?
python tools/profile_report.py chiprun_out/pr30.commit-10k.trace/slice.xplane.pb.gz > chiprun_out/pr30.commit-10k.profile.txt 2>&1; head -c 5000 chiprun_out/pr30.commit-10k.profile.txt
# 2. parent 9e52428 against change, alternating pairs, one traced pair a cell
time python tools/proof/pairs.py --workload commit-10k.verify-commit --seeds 2147489101,2147489102,2147489103 --trace-seed 3000000611 --out chiprun_out/pr30.commit-10k.pairs.jsonl
echo PAIRS_10K_RC=$?
time python tools/proof/pairs.py --workload hub-175.catchup --seeds 2147489201,2147489202,2147489203 --trace-seed 3000000621 --out chiprun_out/pr30.hub-175.pairs.jsonl
echo PAIRS_HUB_RC=$?
