set -x
mkdir -p chiprun_out
# the committed files alone: the change's side runs from _proof/final, a `git archive $(git write-tree)` copy of the final tree
time python tools/proof/pairs.py --workload commit-10k.verify-commit --seeds 2147489601,2147489602 --trace-seed 3000000641 --change _proof/final --out chiprun_out/pr30.commit-10k.final.jsonl
echo FINAL_10K_RC=$?
time python tools/proof/pairs.py --workload hub-175.catchup --seeds 2147489701,2147489702 --trace-seed 3000000651 --change _proof/final --out chiprun_out/pr30.hub-175.final.jsonl
echo FINAL_HUB_RC=$?
time python tools/proof/pairs.py --workload commit-1024.verify-commit --seeds 2147489801 --change _proof/final --out chiprun_out/pr30.commit-1024.final.jsonl
echo FINAL_1024_RC=$?
