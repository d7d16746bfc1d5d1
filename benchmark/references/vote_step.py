"""Verdict rule `vote_step`, the rule of a node in consensus over one height's
precommit step (`types/vote_set.go` AddVote and MakeCommit, as
`consensus/state.go` addVote drives them): every vote's signature is
verified, the valid votes for the block are tallied by voting power, and the
block is committed where they hold over 2/3 of the set's power. The commit is
then the valid votes' alone and has to pass VerifyCommit, which is the
driver's to show; here it is `accepted`. One wrong signature alone refuses
nothing: the vote is dropped. A step that does not reach 2/3 makes no commit,
and the refusal names the power seen, the power needed and the validator
indices whose signatures were wrong. Only the rule: sign bytes and the one
OpenSSL verify a row are reference.py's; nothing of the program is
imported."""


def _ranges(indices) -> str:
    """Sorted indices as `#a-b, #c`: exact, and short where they lie together."""
    out, run = [], []
    for i in sorted(indices):
        if run and i == run[-1] + 1:
            run.append(i)
            continue
        if run:
            out.append(run)
        run = [i]
    if run:
        out.append(run)
    return ", ".join(f"#{r[0]}" if len(r) == 1 else f"#{r[0]}-{r[-1]}" for r in out)


def verdict(mask, signers, powers, total_power, blocks) -> str:
    """`mask` and `signers` (validator indices) are the step's votes, one row
    a vote that arrived; `blocks` names the one height."""
    valid = sum(powers[i] for ok, i in zip(mask, signers) if ok)
    needed = total_power * 2 // 3
    if valid > needed:
        return "accepted"
    wrong = _ranges(i for ok, i in zip(mask, signers) if not ok)
    return (f"no commit: valid power for the block {valid} of {total_power}, over {needed} "
            f"needed; wrong signatures: {wrong or 'none'}")
