"""Verdict rule `adjacent_run`, the rule of sequential light-client
verification over a run of signed headers: header by header in height order,
a header has to be the one the header before it committed to (`link_ok`, the
reference's own finding: its hash is the commit's block id, its
`validators_hash` is the hash of the set that signed and the
`next_validators_hash` of its predecessor, height and time rise), and the
valid for-block signatures of its commit have to hold over 2/3 of the power
of ITS OWN set (`powers`, `total_power` of the block, not the trusted
root's). The run is refused at the first height that fails either, and the
verdict says which. One wrong signature alone refuses nothing. Only the rule:
hashes, sign bytes and the verify of a row are reference.py's; nothing of the
program is imported."""


def verdict(mask, signers, powers, total_power, blocks) -> str:
    """`mask` and `signers` (seats in each block's own set) are the run's
    rows in block order; `blocks` gives each header's `height`, `rows`,
    `powers`, `total_power` and `link_ok`. `powers` and `total_power` are the
    trusted root's and weigh nothing here."""
    at = 0
    for k, block in enumerate(blocks):
        end = at + block["rows"]
        if not block["link_ok"]:
            return f"broken link at block #{k}"
        own = block["powers"]
        tallied = sum(own[i] for ok, i in zip(mask[at:end], signers[at:end]) if ok)
        if tallied * 3 <= block["total_power"] * 2:
            return f"not enough power at block #{k}"
        at = end
    return "accepted"
