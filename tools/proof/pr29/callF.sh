python tools/proof/pairs.py --workload commit-10k.verify-commit --seeds 2147487504,2147487505,2147487506 --out chiprun_out/commit-10k.pairs2.jsonl
echo PAIRS_RC=$?
