"""Verdict rule `sequential_run`, the rule of the light client's sequential
verification (`light/client.py` `_verify_sequential`, upstream's
`verifySequential` over `VerifyAdjacent`) over a run of signed headers at
consecutive heights above a trusted one. Header by header in height order:
the header has to be the one the header before it committed to (`link_ok`,
the reference's own finding: its hash is the commit's block id, its
`validators_hash` is the hash of the set that signed and the
`next_validators_hash` of its predecessor, chain id equal, height one more,
time later); then the valid for-block signatures of its commit have to hold
over 2/3 of the power of the set of ITS OWN height (the block's `powers` and
`total_power`, never the trusted root's). The run is refused at the first
height that fails either, and the verdict says which and where. One wrong
signature alone refuses nothing, and every for-block signature is checked:
this is not upstream's VerifyCommitLight, which stops at 2/3 of the power and
leaves the rest unseen. Only the rule: hashes, sign bytes and the one OpenSSL
verify a row are reference.py's; nothing of the program is imported. The
same rule as the fixture `tests/fixtures/references/adjacent_run.py`, under
the deployment's own name."""


def verdict(mask, signers, powers, total_power, blocks) -> str:
    """`mask` and `signers` (seats in each block's own set) are the run's
    rows as one list in block order; `blocks` gives each header's `height`,
    `rows`, `powers`, `total_power` and `link_ok`. The arguments `powers` and
    `total_power` are the trusted root's and weigh nothing here."""
    at = 0
    for k, block in enumerate(blocks):
        if not block["link_ok"]:
            return f"broken link at block #{k}"
        end = at + block["rows"]
        seats = block["powers"]
        valid = sum(seats[i] for ok, i in zip(mask[at:end], signers[at:end]) if ok)
        if valid * 3 <= block["total_power"] * 2:
            return f"not enough power at block #{k}"
        at = end
    return "accepted"
