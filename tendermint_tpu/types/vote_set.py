"""VoteSet: tallies votes of one (height, round, type) (reference: types/vote_set.go).

Tracks one canonical vote per validator, per-block power sums, 2/3 majority
detection, conflict detection (→ DuplicateVoteEvidence material) and
peer-claimed majorities (used by the consensus reactor's VoteSetBits gossip).
The add path mirrors the reference's addVerifiedVote exactly
(reference: types/vote_set.go:229-290): a conflicting vote is still tracked
under its block key when a peer claims that block has 2/3, and the canonical
vote is replaced when the conflict is FOR the established maj23 block.

Signature verification: votes are verified on arrival through the host path by
default; `defer_verification=True` accumulates unverified votes and `flush()`
batch-verifies them on the TPU in one kernel call — the mode the consensus
vote-storm path uses (north star: SURVEY.md §3.3). Conflicts discovered during
flush are queued and retrievable via pop_conflicts().
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional, Set, Tuple

from tendermint_tpu.crypto.batch import verify_batch
from tendermint_tpu.libs import trace as _trace
from tendermint_tpu.types.basic import BlockID, SignedMsgType
from tendermint_tpu.types.validator_set import ValidatorSet
from tendermint_tpu.types.vote import Vote


class VoteSetError(Exception):
    pass


class ConflictingVotesError(VoteSetError):
    def __init__(self, vote_a: Vote, vote_b: Vote):
        super().__init__("conflicting votes from validator")
        self.vote_a = vote_a  # existing
        self.vote_b = vote_b  # new


@dataclass
class _BlockVotes:
    peer_maj23: bool
    votes: List[Optional[Vote]]
    sum: int = 0

    def add_verified(self, idx: int, vote: Vote, power: int) -> None:
        if self.votes[idx] is None:
            self.votes[idx] = vote
            self.sum += power

    def get_by_index(self, idx: int) -> Optional[Vote]:
        return self.votes[idx]


class VoteSet:
    def __init__(
        self,
        chain_id: str,
        height: int,
        round_: int,
        signed_msg_type: SignedMsgType,
        val_set: ValidatorSet,
        defer_verification: bool = False,
    ):
        if height == 0:
            raise ValueError("cannot make VoteSet for height == 0")
        self.chain_id = chain_id
        self.height = height
        self.round = round_
        self.signed_msg_type = signed_msg_type
        self.val_set = val_set
        self.defer_verification = defer_verification

        n = val_set.size()
        self._votes: List[Optional[Vote]] = [None] * n
        self._votes_bit_array: List[bool] = [False] * n
        self._sum = 0
        self._maj23: Optional[BlockID] = None
        self._votes_by_block: Dict[bytes, _BlockVotes] = {}
        self._peer_maj23s: Dict[str, BlockID] = {}
        # deferred-verification queue: (idx, vote, validator, peer_id)
        self._pending: List[tuple] = []
        # the first queued vote's reading (trace.since), for `votes.pending`
        self._pending_since: Optional[_trace.Since] = None
        self._pending_seen: Set[Tuple[int, bytes, bytes]] = set()
        self._conflicts: List[ConflictingVotesError] = []

    # -- introspection ------------------------------------------------------

    def size(self) -> int:
        return self.val_set.size()

    def bit_array(self) -> List[bool]:
        return list(self._votes_bit_array)

    def bit_array_by_block_id(self, block_id: BlockID) -> Optional[List[bool]]:
        bv = self._votes_by_block.get(block_id.key())
        if bv is None:
            return None
        return [v is not None for v in bv.votes]

    def get_by_index(self, idx: int) -> Optional[Vote]:
        return self._votes[idx]

    def get_by_address(self, address: bytes) -> Optional[Vote]:
        idx, _ = self.val_set.get_by_address(address)
        return self._votes[idx] if idx >= 0 else None

    def list_votes(self) -> List[Vote]:
        return [v for v in self._votes if v is not None]

    def has_two_thirds_majority(self) -> bool:
        return self._maj23 is not None

    def two_thirds_majority(self) -> Optional[BlockID]:
        return self._maj23

    def has_two_thirds_any(self) -> bool:
        return self._sum > self.val_set.total_voting_power() * 2 // 3

    def has_all(self) -> bool:
        return self._sum == self.val_set.total_voting_power()

    def sum_power(self) -> int:
        return self._sum

    def pop_conflicts(self) -> List[ConflictingVotesError]:
        out, self._conflicts = self._conflicts, []
        return out

    def pending_count(self) -> int:
        """Number of deferred (accepted-but-unverified) votes awaiting flush()."""
        return len(self._pending)

    # -- adding votes -------------------------------------------------------

    def _get_vote(self, idx: int, block_key: bytes) -> Optional[Vote]:
        """Existing vote by idx for this block key, canonical or conflict-tracked
        (reference: types/vote_set.go getVote)."""
        existing = self._votes[idx]
        if existing is not None and existing.block_id.key() == block_key:
            return existing
        bv = self._votes_by_block.get(block_key)
        if bv is not None:
            return bv.get_by_index(idx)
        return None

    def add_vote(self, vote: Vote, peer_id: str = ""):
        """Returns a truthy value if the vote was newly accepted: True when
        verified-and-committed, the string "pending" when queued for
        deferred batch verification (NOT yet verified — callers must not
        gossip/advertise it until flush() commits it). Raises VoteSetError
        on invalid votes and ConflictingVotesError on equivocation
        (reference: types/vote_set.go:143-290).

        peer_id: the gossiping peer, when known — deferred votes carry it
        as row provenance (crypto/provenance.py "peer:<id>" tags) so a
        peer whose votes fail batch verification gets quarantined and
        punished instead of poisoning every later vote flush; "" means a
        locally originated/replayed vote."""
        if vote is None:
            raise VoteSetError("nil vote")
        idx = vote.validator_index
        if idx < 0:
            raise VoteSetError("index < 0")
        if not vote.signature:
            raise VoteSetError("no signature")
        if (
            vote.height != self.height
            or vote.round != self.round
            or vote.type != self.signed_msg_type
        ):
            raise VoteSetError(
                f"expected {self.height}/{self.round}/{self.signed_msg_type}, got "
                f"{vote.height}/{vote.round}/{vote.type}"
            )
        addr, val = self.val_set.get_by_index(idx)
        if val is None:
            raise VoteSetError(f"cannot find validator {idx} in valSet of size {self.size()}")
        if addr != vote.validator_address:
            raise VoteSetError("validator address does not match index")

        block_key = vote.block_id.key()
        existing = self._get_vote(idx, block_key)
        if existing is not None:
            if existing.signature == vote.signature:
                return False  # duplicate
            raise VoteSetError("non-deterministic signature for the same block")

        if self.defer_verification:
            seen_key = (idx, block_key, vote.signature)
            if seen_key in self._pending_seen:
                return False
            self._pending_seen.add(seen_key)
            # carry the resolved Validator so flush() skips a second
            # get_by_index per vote, and the gossiping peer for provenance
            if not self._pending:
                self._pending_since = _trace.since("votes.pending")
            self._pending.append((idx, vote, val, peer_id))
            return "pending"

        if not self._verify_now(vote, val.pub_key):
            raise VoteSetError(f"invalid signature from validator {idx}")
        added, conflicting = self._add_verified(idx, vote, val.voting_power, block_key)
        if conflicting is not None:
            raise ConflictingVotesError(conflicting, vote)
        return added

    def _verify_now(self, vote: Vote, pub_key) -> bool:
        return pub_key.verify(vote.sign_bytes(self.chain_id), vote.signature)

    def flush(self) -> Tuple[List[Vote], List[int]]:
        """Batch-verify all deferred votes in one device call; commits the
        valid ones through the same conflict-aware path as add_vote. Returns
        (committed votes — safe to publish/gossip now, indices of votes that
        FAILED verification); conflicts discovered are available via
        pop_conflicts().

        One span tree a flush (`votes.flush`: the queue's wait `votes.pending`,
        gather, sign bytes, the scheduler's `lane.flush` / `verify_batch`,
        count), never a span a vote: add_vote opens none, and reads the clock
        only for the vote that finds the queue empty."""
        if not self._pending:
            return [], []
        since, self._pending_since = self._pending_since, None
        t1_ns = since.end() if since is not None else 0
        with _trace.span(
            "votes.flush", height=self.height, round=self.round,
            type=SignedMsgType(self.signed_msg_type).name.lower(), rows=len(self._pending),
        ) as root:
            if since is not None:
                # from the first vote queued to the flush that takes it: the
                # batching delay the deferred path adds to every vote
                _trace.interval("votes.pending", since.t0_ns, t1_ns, parent=root,
                                rows=len(self._pending))
            committed, failed = self._flush_pending()
            root.set(committed=len(committed), failed=len(failed))
        return committed, failed

    def _flush_pending(self) -> Tuple[List[Vote], List[int]]:
        from tendermint_tpu.types import canonical

        pubkeys, sigs, key_types, sources = [], [], [], []
        with _trace.span("votes.gather"):
            for _idx, vote, val, peer_id in self._pending:
                pubkeys.append(val.pub_key.bytes())
                sigs.append(vote.signature)
                key_types.append(val.pub_key.type_name())
                sources.append(f"peer:{peer_id}" if peer_id else "lane:votes")
        # One batched sign-bytes pass (shared type/height/round/chain_id;
        # profiled: the per-vote builder was 72% of flush time).
        with _trace.span("votes.sign_bytes"):
            msgs = canonical.vote_sign_bytes_many(
                self.chain_id,
                self.signed_msg_type,
                self.height,
                self.round,
                ((vote.block_id, vote.timestamp_ns) for _, vote, _, _ in self._pending),
            )
        # key_types matters: in a mixed validator set an sr25519 vote
        # verified under ed25519 rules always fails (marker bit forces
        # s >= L) — dropping valid votes on the deferred path would be a
        # liveness break (mirrors validator_set.py batched Verify*).
        # Global verification scheduler (crypto/scheduler.py): the deferred
        # vote flush rides the VOTES lane — it PREEMPTS queued bulk work
        # (light/admission/catch-up rows never inflate a vote flush's wall)
        # and its verdicts are byte-identical to the direct call (the
        # combined flush recovers the exact per-row mask). Process-global
        # default (last node wins, the tracer model): VoteSet has no wiring
        # path from the Node; with no scheduler the direct path is
        # unchanged.
        from tendermint_tpu.crypto import scheduler as _scheduler

        sched = _scheduler.default_scheduler()
        if sched is not None:
            mask = sched.verify_rows("votes", pubkeys, msgs, sigs, key_types,
                                     sources)
        else:
            mask = verify_batch(pubkeys, msgs, sigs, key_types=key_types,
                                sources=sources)
        committed = []
        failed = []
        with _trace.span("votes.count") as sp:
            conflicts = len(self._conflicts)
            for ok, (idx, vote, val, _peer) in zip(mask, self._pending):
                if not ok:
                    failed.append(idx)
                    continue
                block_key = vote.block_id.key()
                # Re-check: an earlier pending vote may have committed already.
                if self._get_vote(idx, block_key) is not None:
                    continue
                added, conflicting = self._add_verified(idx, vote, val.voting_power, block_key)
                if added:
                    committed.append(vote)
                if conflicting is not None:
                    self._conflicts.append(ConflictingVotesError(conflicting, vote))
            sp.set(conflicts=len(self._conflicts) - conflicts)
        self._pending.clear()
        self._pending_seen.clear()
        return committed, failed

    def _add_verified(
        self, idx: int, vote: Vote, power: int, block_key: Optional[bytes] = None
    ) -> Tuple[bool, Optional[Vote]]:
        """Mirror of reference addVerifiedVote (types/vote_set.go:229-290).
        Assumes the signature is already verified. `block_key` is accepted
        from callers that already computed it (the add path computes it for
        duplicate detection; recomputing here was measurable under storms)."""
        if block_key is None:
            block_key = vote.block_id.key()
        conflicting: Optional[Vote] = None

        existing = self._votes[idx]
        if existing is not None:
            conflicting = existing
            # Replace the canonical vote if the new one is for the maj23 block.
            if self._maj23 is not None and self._maj23.key() == block_key:
                self._votes[idx] = vote
                self._votes_bit_array[idx] = True
            # sum is NOT incremented for a replacement
        else:
            self._votes[idx] = vote
            self._votes_bit_array[idx] = True
            self._sum += power

        bv = self._votes_by_block.get(block_key)
        if bv is not None:
            if conflicting is not None and not bv.peer_maj23:
                return False, conflicting
        else:
            if conflicting is not None:
                return False, conflicting
            bv = _BlockVotes(peer_maj23=False, votes=[None] * self.size())
            self._votes_by_block[block_key] = bv

        quorum = self.val_set.total_voting_power() * 2 // 3 + 1
        orig_sum = bv.sum
        bv.add_verified(idx, vote, power)
        if orig_sum < quorum <= bv.sum and self._maj23 is None:
            self._maj23 = vote.block_id
            # Promote all votes under this block to canonical.
            for i, bvote in enumerate(bv.votes):
                if bvote is not None:
                    self._votes[i] = bvote
                    self._votes_bit_array[i] = True
        return True, conflicting

    def set_peer_maj23(self, peer_id: str, block_id: BlockID) -> None:
        """Record a peer's claim that a block has 2/3 (reference:
        types/vote_set.go:291-330)."""
        existing = self._peer_maj23s.get(peer_id)
        if existing is not None and existing != block_id:
            raise VoteSetError(f"setPeerMaj23: conflicting blockID from peer {peer_id}")
        self._peer_maj23s[peer_id] = block_id
        key = block_id.key()
        bv = self._votes_by_block.get(key)
        if bv is None:
            bv = _BlockVotes(peer_maj23=True, votes=[None] * self.size())
            self._votes_by_block[key] = bv
        else:
            bv.peer_maj23 = True

    def make_commit(self):
        """Build a Commit from 2/3 precommits for a block
        (reference: types/vote_set.go:578-602 MakeCommit)."""
        from tendermint_tpu.types.block import Commit, CommitSig
        from tendermint_tpu.types.basic import BlockIDFlag

        if self.signed_msg_type != SignedMsgType.PRECOMMIT:
            raise VoteSetError("cannot MakeCommit() unless VoteSet.Type is PRECOMMIT")
        if self._maj23 is None:
            raise VoteSetError("cannot MakeCommit() unless a blockhash has +2/3")
        with _trace.span("votes.make_commit", height=self.height) as sp:
            sigs = []
            absent = 0
            for vote in self._votes:
                if vote is not None and vote.block_id == self._maj23:
                    sigs.append(
                        CommitSig(
                            BlockIDFlag.COMMIT,
                            vote.validator_address,
                            vote.timestamp_ns,
                            vote.signature,
                        )
                    )
                elif vote is not None and vote.block_id.is_zero():
                    sigs.append(
                        CommitSig(
                            BlockIDFlag.NIL,
                            vote.validator_address,
                            vote.timestamp_ns,
                            vote.signature,
                        )
                    )
                else:
                    # No vote, or one for a different block: absent in the commit.
                    sigs.append(CommitSig.absent_sig())
                    absent += 1
            sp.set(rows=len(sigs) - absent)
            return Commit(self.height, self.round, self._maj23, tuple(sigs))
