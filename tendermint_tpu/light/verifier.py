"""Stateless light-client verification math.

reference: light/verifier.go — VerifyNonAdjacent (:32), VerifyAdjacent (:95),
Verify dispatch (:139), VerifyBackwards (:160), verifyNewHeaderAndVals (:176),
HeaderExpired (:210).

Both commit checks ride the framework's batched verification path
(types/validator_set.py verify_commit_light / verify_commit_light_trusting),
so a bisection step verifies all signatures of a 10k-validator commit in one
device batch instead of the reference's serial loop. Sequential verification
goes one step further (verify_adjacent_run): VerifyAdjacent is split into its
host part (check_adjacent) and its commit part, and a run of adjacent headers
does the first header by header and the second once, all the run's
signatures in one flush.
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

from tendermint_tpu.libs import trace as _trace
from tendermint_tpu.types.light import LightBlock, SignedHeader
from tendermint_tpu.types.validator_set import (
    CommitVerifyError,
    Fraction,
    NotEnoughVotingPowerError,
    ValidatorSet,
    leaf_memo_counts,
)

# 1/3 — the default trust level (reference: light/trust_options.go,
# DefaultTrustLevel light/verifier.go:21)
DEFAULT_TRUST_LEVEL = Fraction(1, 3)


class LightError(Exception):
    pass


class ErrOldHeaderExpired(LightError):
    """Trusted header is outside the trusting period
    (reference: light/errors.go ErrOldHeaderExpired)."""

    def __init__(self, expired_at_ns: int, now_ns: int):
        self.expired_at_ns = expired_at_ns
        self.now_ns = now_ns
        super().__init__(f"old header has expired at {expired_at_ns} (now: {now_ns})")


class ErrNewValSetCantBeTrusted(LightError):
    """< trust-level of the trusted valset signed the new header — the caller
    should bisect (reference: light/errors.go ErrNewValSetCantBeTrusted)."""


class ErrInvalidHeader(LightError):
    """New header can't be trusted for a non-recoverable reason."""


def validate_trust_level(level: Fraction) -> None:
    """reference: light/verifier.go:222 ValidateTrustLevel — must be in (1/3, 1]."""
    if (
        level.numerator * 3 < level.denominator
        or level.numerator > level.denominator
        or level.denominator == 0
    ):
        raise ValueError(f"trustLevel must be within (1/3, 1], given {level}")


def header_expired(h: SignedHeader, trusting_period_ns: int, now_ns: int) -> bool:
    """reference: light/verifier.go:210 HeaderExpired."""
    return h.header.time_ns + trusting_period_ns <= now_ns


def _verify_new_header_and_vals(
    untrusted: SignedHeader,
    untrusted_vals: ValidatorSet,
    trusted: SignedHeader,
    now_ns: int,
    max_clock_drift_ns: int,
) -> None:
    """reference: light/verifier.go:176 verifyNewHeaderAndVals."""
    try:
        untrusted.validate_basic(trusted.header.chain_id)
    except ValueError as e:
        raise ErrInvalidHeader(f"untrusted header invalid: {e}") from e

    if untrusted.height <= trusted.height:
        raise ErrInvalidHeader(
            f"expected new header height {untrusted.height} to be greater than "
            f"one of old header {trusted.height}"
        )
    if untrusted.header.time_ns <= trusted.header.time_ns:
        raise ErrInvalidHeader(
            f"expected new header time {untrusted.header.time_ns} to be after "
            f"old header time {trusted.header.time_ns}"
        )
    if untrusted.header.time_ns >= now_ns + max_clock_drift_ns:
        raise ErrInvalidHeader(
            f"new header has a time from the future {untrusted.header.time_ns} "
            f"(now: {now_ns}; max clock drift: {max_clock_drift_ns})"
        )
    vh = untrusted_vals.hash()
    if untrusted.header.validators_hash != vh:
        raise ErrInvalidHeader(
            f"expected new header validators ({untrusted.header.validators_hash.hex()}) "
            f"to match those supplied ({vh.hex()})"
        )


def verify_non_adjacent(
    chain_id: str,
    trusted: SignedHeader,
    trusted_next_vals: ValidatorSet,
    untrusted: SignedHeader,
    untrusted_vals: ValidatorSet,
    trusting_period_ns: int,
    now_ns: int,
    max_clock_drift_ns: int,
    trust_level: Fraction = DEFAULT_TRUST_LEVEL,
) -> None:
    """Skipping verification (reference: light/verifier.go:32 VerifyNonAdjacent).

    Trusts the new header if +trust_level of the *trusted* valset signed it
    (batched verify_commit_light_trusting) AND +2/3 of the new valset signed it
    (batched verify_commit_light)."""
    if untrusted.height == trusted.height + 1:
        raise ValueError("headers must be non adjacent in height")
    if header_expired(trusted, trusting_period_ns, now_ns):
        raise ErrOldHeaderExpired(trusted.header.time_ns + trusting_period_ns, now_ns)
    _verify_new_header_and_vals(untrusted, untrusted_vals, trusted, now_ns, max_clock_drift_ns)

    # PIPELINED: submit both batch verifications before syncing either — the
    # trusting-set and new-set checks are independent device calls, so their
    # round trips overlap instead of paying 2 serial RTTs (the reference
    # runs them serially, light/verifier.go:56,80).
    try:
        fin_trusting = trusted_next_vals.begin_verify_commit_light_trusting(
            chain_id, untrusted.commit, trust_level
        )
        fin_light = untrusted_vals.begin_verify_commit_light(
            chain_id, untrusted.commit.block_id, untrusted.height, untrusted.commit
        )
    except CommitVerifyError as e:
        raise ErrInvalidHeader(f"invalid commit: {e}") from e

    try:
        fin_trusting()
    except NotEnoughVotingPowerError as e:
        # recoverable: the caller should bisect (reference: light/verifier.go:73)
        raise ErrNewValSetCantBeTrusted(str(e)) from e
    except CommitVerifyError as e:
        # any other commit defect (double vote, malformed sig) is terminal
        raise ErrInvalidHeader(f"invalid commit: {e}") from e

    try:
        fin_light()
    except CommitVerifyError as e:
        raise ErrInvalidHeader(f"invalid commit: {e}") from e


def check_adjacent(
    chain_id: str,
    trusted: SignedHeader,
    untrusted: SignedHeader,
    untrusted_vals: ValidatorSet,
    trusting_period_ns: int,
    now_ns: int,
    max_clock_drift_ns: int,
) -> None:
    """The host part of VerifyAdjacent (reference: light/verifier.go:95):
    everything but the commit's signatures. The new header is one height on,
    the trusted one has not expired, the new header and its set are sound
    (_verify_new_header_and_vals), and the set is the one the trusted header
    committed to (NextValidatorsHash)."""
    if untrusted.height != trusted.height + 1:
        raise ValueError("headers must be adjacent in height")
    if header_expired(trusted, trusting_period_ns, now_ns):
        raise ErrOldHeaderExpired(trusted.header.time_ns + trusting_period_ns, now_ns)
    _verify_new_header_and_vals(untrusted, untrusted_vals, trusted, now_ns, max_clock_drift_ns)

    if untrusted.header.validators_hash != trusted.header.next_validators_hash:
        raise ErrInvalidHeader(
            f"expected old header next validators "
            f"({trusted.header.next_validators_hash.hex()}) to match those from "
            f"new header ({untrusted.header.validators_hash.hex()})"
        )


def _invalid_commit(e: CommitVerifyError) -> ErrInvalidHeader:
    """What VerifyAdjacent raises for a commit its set refuses."""
    err = ErrInvalidHeader(f"invalid commit: {e}")
    err.__cause__ = e
    return err


def verify_adjacent(
    chain_id: str,
    trusted: SignedHeader,
    untrusted: SignedHeader,
    untrusted_vals: ValidatorSet,
    trusting_period_ns: int,
    now_ns: int,
    max_clock_drift_ns: int,
) -> None:
    """Sequential verification (reference: light/verifier.go:95 VerifyAdjacent).

    The new valset is pinned by the trusted header's NextValidatorsHash."""
    check_adjacent(
        chain_id, trusted, untrusted, untrusted_vals,
        trusting_period_ns, now_ns, max_clock_drift_ns,
    )
    try:
        untrusted_vals.verify_commit_light(
            chain_id, untrusted.commit.block_id, untrusted.height, untrusted.commit
        )
    except CommitVerifyError as e:
        raise _invalid_commit(e)


def verify_adjacent_run(
    chain_id: str,
    trusted: SignedHeader,
    run: Sequence[LightBlock],
    trusting_period_ns: int,
    now_ns: int,
    max_clock_drift_ns: int,
    accumulator,
    root=_trace.NOOP,
) -> Tuple[int, Optional[Exception]]:
    """VerifyAdjacent over a run of light blocks at consecutive heights above
    `trusted`, with the meaning of one verify_adjacent a header in height
    order: every host check (check_adjacent) header by header against the
    header before it, then the for-block rows of every header under the set
    that header carries, ALL of them in one flush of `accumulator` (a
    crypto/batch.FlushAccumulator, or the scheduler's light-lane one), then
    the valid ones tallied header by header by that set's power.

    Returns (verified, failure): the first `verified` headers of `run` are
    verified; `failure` is what verify_adjacent raises for the header after
    them, or None where the whole run is. A stage that fails at a header
    leaves that header and everything above it out of the later stages, so
    the failure kept is the lowest height's, and at one height the one the
    one-header path meets first.

    Spans: ONE a stage a run, under the caller's `root` (`light.verify_run`,
    which gets `rows`, `sets` and `flushes`), never one a header: a span a
    header would roll the recorder's ring over in a few calls. The leaves the
    header checks' set hashes asked for and found hashed (`set_leaves`,
    `set_leaf_hits`: the process's counts, read before and after the loop)
    go on `light.header_checks` and beside `rows` on the root."""
    from tendermint_tpu.crypto.batch import (
        accumulate_flushes,
        verify_batch_finish,
        verify_batch_submit,
    )

    failure: Optional[Exception] = None
    with _trace.span("light.header_checks") as sp:
        leaves0, hits0 = leaf_memo_counts()
        prev = trusted
        for k, lb in enumerate(run):
            try:
                check_adjacent(
                    chain_id, prev, lb.signed_header, lb.validator_set,
                    trusting_period_ns, now_ns, max_clock_drift_ns,
                )
            except Exception as e:
                failure, run = e, run[:k]
                break
            prev = lb.signed_header
        leaves1, hits1 = leaf_memo_counts()
        leaf_counts = dict(set_leaves=leaves1 - leaves0, set_leaf_hits=hits1 - hits0)
        sp.set(headers=len(run), **leaf_counts)
    pubkeys, sigs, key_types = [], [], []
    blocks = []  # per header: (commit, idxs, powers, its first row)
    with _trace.span("light.gather") as sp:
        for k, lb in enumerate(run):
            commit, vals = lb.signed_header.commit, lb.validator_set
            try:
                vals._check_commit_for(commit.block_id, lb.height, commit)
            except CommitVerifyError as e:
                failure, run = _invalid_commit(e), run[:k]
                break
            start = len(sigs)
            idxs, powers = vals.for_block_rows(commit, pubkeys, sigs, key_types)
            blocks.append((commit, idxs, powers, start))
        sp.set(rows=len(sigs))
    root.set(rows=len(sigs), sets=len({lb.header.validators_hash for lb in run}), **leaf_counts)
    if not blocks:
        return 0, failure
    msgs = []
    with _trace.span("light.sign_bytes", rows=len(sigs), headers=len(blocks)):
        for commit, idxs, _powers, _start in blocks:
            msgs.extend(commit.vote_sign_bytes_many(chain_id, idxs))
    with accumulate_flushes(accumulator):
        handle = verify_batch_submit(pubkeys, msgs, sigs, key_types=key_types)
    accumulator.flush()
    mask = verify_batch_finish(handle)
    root.set(flushes=accumulator.flush_count)
    with _trace.span("light.tally"):
        for k, (lb, (_commit, _idxs, powers, start)) in enumerate(zip(run, blocks)):
            tallied = sum(p for ok, p in zip(mask[start : start + len(powers)], powers) if ok)
            needed = lb.validator_set.total_voting_power() * 2 // 3
            if tallied <= needed:
                return k, _invalid_commit(NotEnoughVotingPowerError(tallied, needed))
    return len(run), failure


def verify(
    chain_id: str,
    trusted: SignedHeader,
    trusted_next_vals: ValidatorSet,
    untrusted: SignedHeader,
    untrusted_vals: ValidatorSet,
    trusting_period_ns: int,
    now_ns: int,
    max_clock_drift_ns: int,
    trust_level: Fraction = DEFAULT_TRUST_LEVEL,
) -> None:
    """Dispatch on adjacency (reference: light/verifier.go:139 Verify)."""
    if untrusted.height != trusted.height + 1:
        verify_non_adjacent(
            chain_id, trusted, trusted_next_vals, untrusted, untrusted_vals,
            trusting_period_ns, now_ns, max_clock_drift_ns, trust_level,
        )
    else:
        verify_adjacent(
            chain_id, trusted, untrusted, untrusted_vals,
            trusting_period_ns, now_ns, max_clock_drift_ns,
        )


def verify_backwards(chain_id: str, untrusted: SignedHeader, trusted: SignedHeader) -> None:
    """Verify an older header against a trusted newer one via the hash chain
    (reference: light/verifier.go:160 VerifyBackwards)."""
    if untrusted.header.chain_id != chain_id:
        raise ErrInvalidHeader("header belongs to another chain")
    if untrusted.header.time_ns >= trusted.header.time_ns:
        raise ErrInvalidHeader(
            f"expected older header time {untrusted.header.time_ns} to be before "
            f"newer header time {trusted.header.time_ns}"
        )
    if untrusted.hash() != trusted.header.last_block_id.hash:
        raise ErrInvalidHeader(
            f"older header hash {untrusted.hash().hex()} does not match trusted "
            f"header's last block {trusted.header.last_block_id.hash.hex()}"
        )
