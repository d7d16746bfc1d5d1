set -x
mkdir -p chiprun_out
# 1. what one call leaves in the ring, both large cells (events a call, the flush record)
python tools/proof/call_tree.py commit-10k.verify-commit commit.verify > chiprun_out/pr30.commit-10k.tree.json; echo TREE_10K_RC=$?; tail -c 2500 chiprun_out/pr30.commit-10k.tree.json
python tools/proof/call_tree.py hub-175.catchup catchup.verify_run > chiprun_out/pr30.hub-175.tree.json; echo TREE_HUB_RC=$?; tail -c 2500 chiprun_out/pr30.hub-175.tree.json
# 2. the control of the claim: commit-1024 never enters the changed function
time python tools/proof/pairs.py --workload commit-1024.verify-commit --seeds 2147489401,2147489402,2147489403 --trace-seed 3000000631 --out chiprun_out/pr30.commit-1024.pairs.jsonl
echo PAIRS_1024_RC=$?
# 3. three more pairs in each claimed cell (six in all with call 1), a seed to each pair
time python tools/proof/pairs.py --workload commit-10k.verify-commit --seeds 2147489104,2147489105,2147489106 --out chiprun_out/pr30.commit-10k.pairs2.jsonl
echo PAIRS_10K_RC=$?
time python tools/proof/pairs.py --workload hub-175.catchup --seeds 2147489204,2147489205,2147489206 --out chiprun_out/pr30.hub-175.pairs2.jsonl
echo PAIRS_HUB_RC=$?
# 4. the control on the program's own path still reads not correct in both large cells
time python benchmark/prove.py --workload commit-10k.verify-commit --seeds 2147489501 --out chiprun_out/pr30.commit-10k.control.jsonl --timeout 1200 -- --control unsent_third
echo CONTROL_10K_RC=$?
time python benchmark/prove.py --workload hub-175.catchup --seeds 2147489502 --out chiprun_out/pr30.hub-175.control.jsonl --timeout 1200 -- --control unsent_third
echo CONTROL_HUB_RC=$?
python tools/proof/judge_times.py chiprun_out/pr30.commit-10k.control.jsonl chiprun_out/pr30.hub-175.control.jsonl
