"""Verdict rule `tally_valid_power_run`, the rule of the block-sync reactor
(`blocksync/reactor._verify_run_batched`): every for-block signature of every
commit of a run is verified, the valid ones are tallied by voting power block
by block, and the run is refused at the first block whose valid power is not
over 2/3 of the set's. One wrong signature alone refuses nothing, and no
index of a signature is named. It is neither VerifyCommit (which refuses a
commit for one wrong signature) nor upstream's VerifyCommitLight (which stops
at 2/3 of the power). Only the rule: sign bytes and the verify of a row are
reference.py's; nothing of the program is imported. The same rule as the
selftest's fixture `tests/fixtures/references/tally_valid_power.py`, under a
name of its own because the fixture's is laid over this directory and taken
away again by tests/test_room.py."""


def verdict(mask, signers, powers, total_power, blocks) -> str:
    """`mask` and `signers` (validator indices) are the run's rows in block
    order; `blocks` gives each block's `height` and `rows`."""
    at = 0
    for k, block in enumerate(blocks):
        end = at + block["rows"]
        tallied = sum(powers[i] for ok, i in zip(mask[at:end], signers[at:end]) if ok)
        if tallied * 3 <= total_power * 2:
            return f"refused at block #{k}"
        at = end
    return "accepted"
