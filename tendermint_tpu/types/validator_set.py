"""Validator, ValidatorSet: proposer selection and BATCHED commit verification.

Re-implements the reference's types/validator.go + types/validator_set.go:
- weighted-round-robin proposer selection with priority centering/rescaling
  (reference: types/validator_set.go:113-247)
- validator-set updates with the H+2 semantics handled by the state layer
  (reference: types/validator_set.go:474-637)
- VerifyCommit / VerifyCommitLight / VerifyCommitLightTrusting
  (reference: types/validator_set.go:662,719,772)

THE key TPU-native departure: the reference verifies commit signatures in a
serial for-loop, one scalar ed25519 verify per validator
(reference: types/validator_set.go:680-702). Here every Verify* call gathers
all (pubkey, sign-bytes, signature) triples and flushes them through
crypto.batch.verify_batch — one vmap'd kernel launch over the validator axis.

Documented divergence: the Light/LightTrusting variants verify all relevant
signatures in one batch and tally only the valid ones, instead of the
reference's sequential early-exit at 2/3 — acceptance requires the same
+2/3 (or trust-level) threshold of *valid* signatures, but a commit whose
early signature is bad and later ones are good is accepted here if the valid
tally clears the threshold (the reference fails fast). This is strictly a
liveness-friendly relaxation; safety is unchanged.
"""

from __future__ import annotations

import contextlib
import threading
from dataclasses import dataclass
from fractions import Fraction
from typing import Dict, List, Optional, Sequence, Tuple

from tendermint_tpu.crypto import tmhash
from tendermint_tpu.crypto.batch import verify_batch
from tendermint_tpu.crypto.keys import PubKey
from tendermint_tpu.crypto.merkle import hash_from_leaf_hashes, leaf_hash
from tendermint_tpu.libs import protowire as pw
from tendermint_tpu.libs import trace as _trace

INT64_MAX = 2**63 - 1
MAX_TOTAL_VOTING_POWER = INT64_MAX // 8
PRIORITY_WINDOW_SIZE_FACTOR = 2


# A validator's Merkle leaf hash, SHA-256(0x00 || SimpleValidator{pub_key,
# voting_power}), by VALUE: the key is everything simple_bytes() reads, so a
# power changed in place is another key and proposer_priority has no part. A
# chain's set at one height is its predecessor's but for the validators that
# changed, so ValidatorSet.hash() finds nearly every leaf here. An entry weighs
# about 270 bytes (the key's tuple, bytes and int, the 32-byte hash, the dict's
# slot; measured with tracemalloc): 17 MB at the bound, which holds a
# 10,000-key set six times over. At the bound the lot goes. Reads and writes
# are single dict operations (the light service hashes sets from many
# threads); the lock is the counts'.
_LEAF_MEMO_BOUND = 65_536
_leaf_memo: Dict[Tuple[str, bytes, int], bytes] = {}
_leaf_counts = [0, 0]  # leaves asked for by set hashes; of them, found in the memo
_leaf_counts_lock = threading.Lock()


def leaf_memo_counts() -> Tuple[int, int]:
    """(leaves asked for, leaves answered from the memo) by every
    ValidatorSet.hash() of the process so far: a reader takes the difference
    of two readings (the `set_leaves` / `set_leaf_hits` of `light.header_checks`)."""
    with _leaf_counts_lock:
        return _leaf_counts[0], _leaf_counts[1]


class CommitVerifyError(Exception):
    pass


class NotEnoughVotingPowerError(CommitVerifyError):
    def __init__(self, got: int, needed: int):
        super().__init__(f"invalid commit -- insufficient voting power: got {got}, needed more than {needed}")
        self.got = got
        self.needed = needed


def _clip64(x: int) -> int:
    return max(-(2**63), min(INT64_MAX, x))


@dataclass
class Validator:
    pub_key: PubKey
    voting_power: int
    address: bytes = b""
    proposer_priority: int = 0

    def __post_init__(self):
        if not self.address:
            self.address = self.pub_key.address()

    def copy(self) -> "Validator":
        return Validator(self.pub_key, self.voting_power, self.address, self.proposer_priority)

    def validate_basic(self) -> None:
        if self.pub_key is None:
            raise ValueError("validator does not have a public key")
        if self.voting_power < 0:
            raise ValueError("validator has negative voting power")
        if len(self.address) != tmhash.TRUNCATED_SIZE:
            raise ValueError("validator address is the wrong size")

    def compare_proposer_priority(self, other: "Validator") -> "Validator":
        """Higher priority wins; tie broken by ascending address
        (reference: types/validator.go:64-84)."""
        if self.proposer_priority > other.proposer_priority:
            return self
        if self.proposer_priority < other.proposer_priority:
            return other
        if self.address < other.address:
            return self
        if self.address > other.address:
            return other
        raise ValueError("cannot compare identical validators")

    def simple_bytes(self) -> bytes:
        """SimpleValidator proto encoding used in ValidatorSet.Hash
        (reference: types/validator.go ToProto + types/validator_set.go Hash)."""
        pk = pw.Writer()
        if self.pub_key.type_name() == "ed25519":
            pk.bytes_field(1, self.pub_key.bytes())
        elif self.pub_key.type_name() == "sr25519":
            pk.bytes_field(3, self.pub_key.bytes())
        elif self.pub_key.type_name() == "bls12_381":
            pk.bytes_field(4, self.pub_key.bytes())
        else:
            raise ValueError(f"unsupported key type {self.pub_key.type_name()}")
        w = pw.Writer()
        w.message_field(1, pk.bytes(), always=True)
        w.varint_field(2, self.voting_power)
        return w.bytes()


@contextlib.contextmanager
def _commit_span(entry: str, commit, height: int, done: str = "accepted"):
    """The root span of one Verify* call (`commit.verify`): the entry that
    was called, the rows it gathered (set by the body once known) and, on
    exit, the verdict: `done`, or the class of the error it raised."""
    with _trace.span(
        "commit.verify", entry=entry, rows=len(commit.signatures), height=height
    ) as sp:
        try:
            yield sp
        except BaseException as e:
            sp.set(verdict=type(e).__name__)
            raise
        sp.set(verdict=done)


def _sign_bytes_spanned(commit, chain_id: str, idxs) -> list:
    """commit.vote_sign_bytes_many under its span (`commit.sign_bytes`)."""
    with _trace.span("commit.sign_bytes", rows=len(idxs)) as sp:
        msgs, builder = commit.vote_sign_bytes_built(chain_id, idxs)
        if sp.recording:  # 16 ns a row, inside the span; nothing when off
            sp.set(bytes=sum(map(len, msgs)), builder=builder)
    return msgs


class ValidatorSet:
    """Sorted validator set + proposer. Sorting: descending voting power,
    ties by ascending address (reference: types/validator_set.go ValidatorsByVotingPower)."""

    def __init__(self, validators: Sequence[Validator], proposer: Optional[Validator] = None):
        self.validators: List[Validator] = sorted(
            (v.copy() for v in validators),
            key=lambda v: (-v.voting_power, v.address),
        )
        self._total_voting_power: Optional[int] = None
        self._by_address: Dict[bytes, int] = {
            v.address: i for i, v in enumerate(self.validators)
        }
        if len(self._by_address) != len(self.validators):
            raise ValueError("duplicate validator address")
        self.proposer: Optional[Validator] = proposer
        if self.proposer is None and self.validators:
            self.proposer = self._compute_proposer()

    # -- basic accessors ----------------------------------------------------

    def __len__(self) -> int:
        return len(self.validators)

    def size(self) -> int:
        return len(self.validators)

    def is_nil_or_empty(self) -> bool:
        return len(self.validators) == 0

    def has_address(self, address: bytes) -> bool:
        return address in self._by_address

    def get_by_address(self, address: bytes) -> Tuple[int, Optional[Validator]]:
        idx = self._by_address.get(address)
        if idx is None:
            return -1, None
        return idx, self.validators[idx]

    def get_by_index(self, index: int) -> Tuple[bytes, Optional[Validator]]:
        if index < 0 or index >= len(self.validators):
            return b"", None
        v = self.validators[index]
        return v.address, v

    def total_voting_power(self) -> int:
        if self._total_voting_power is None:
            tot = 0
            for v in self.validators:
                tot = _clip64(tot + v.voting_power)
            self._total_voting_power = tot
        return self._total_voting_power

    def copy(self) -> "ValidatorSet":
        vs = ValidatorSet.__new__(ValidatorSet)
        vs.validators = [v.copy() for v in self.validators]
        vs._total_voting_power = self._total_voting_power
        vs._by_address = dict(self._by_address)
        vs.proposer = self.proposer.copy() if self.proposer else None
        return vs

    def validate_basic(self) -> None:
        if self.is_nil_or_empty():
            raise ValueError("validator set is nil or empty")
        for v in self.validators:
            v.validate_basic()
        if self.proposer is None:
            raise ValueError("proposer failed validate basic, error: nil validator")
        self.proposer.validate_basic()

    def hash(self) -> bytes:
        """Merkle root of SimpleValidator encodings (reference:
        types/validator_set.go Hash), each leaf hashed once a distinct (key
        type, key bytes, voting power) and looked up thereafter (_leaf_memo)."""
        hashes, hits = [], 0
        for v in self.validators:
            pk = v.pub_key
            key = (pk.type_name(), pk.bytes(), v.voting_power)
            h = _leaf_memo.get(key)
            if h is None:
                h = leaf_hash(v.simple_bytes())
                if len(_leaf_memo) >= _LEAF_MEMO_BOUND:
                    _leaf_memo.clear()
                _leaf_memo[key] = h
            else:
                hits += 1
            hashes.append(h)
        with _leaf_counts_lock:
            _leaf_counts[0] += len(hashes)
            _leaf_counts[1] += hits
        return hash_from_leaf_hashes(hashes)

    # -- proposer selection -------------------------------------------------

    def _compute_proposer(self) -> Validator:
        res = self.validators[0]
        for v in self.validators[1:]:
            res = res.compare_proposer_priority(v)
        return res

    def get_proposer(self) -> Validator:
        if not self.validators:
            raise ValueError("empty validator set")
        if self.proposer is None:
            self.proposer = self._compute_proposer()
        return self.proposer

    def _compute_avg_proposer_priority(self) -> int:
        n = len(self.validators)
        s = sum(v.proposer_priority for v in self.validators)
        # Go big.Int.Div is Euclidean (non-negative remainder), which for a
        # positive divisor equals Python floor division.
        return s // n

    def _shift_by_avg_proposer_priority(self) -> None:
        avg = self._compute_avg_proposer_priority()
        for v in self.validators:
            v.proposer_priority = _clip64(v.proposer_priority - avg)

    def rescale_priorities(self, diff_max: int) -> None:
        if diff_max <= 0:
            return
        prios = [v.proposer_priority for v in self.validators]
        diff = max(prios) - min(prios)
        if diff < 0:
            diff = -diff
        ratio = (diff + diff_max - 1) // diff_max
        if diff > diff_max:
            for v in self.validators:
                # Go integer division truncates toward zero
                p = v.proposer_priority
                v.proposer_priority = -((-p) // ratio) if p < 0 else p // ratio

    def _increment_proposer_priority(self) -> Validator:
        for v in self.validators:
            v.proposer_priority = _clip64(v.proposer_priority + v.voting_power)
        mostest = self._compute_proposer()
        mostest.proposer_priority = _clip64(
            mostest.proposer_priority - self.total_voting_power()
        )
        return mostest

    def increment_proposer_priority(self, times: int) -> None:
        """(reference: types/validator_set.go:116-138)"""
        if self.is_nil_or_empty():
            raise ValueError("empty validator set")
        if times <= 0:
            raise ValueError("cannot call IncrementProposerPriority with non-positive times")
        diff_max = PRIORITY_WINDOW_SIZE_FACTOR * self.total_voting_power()
        self.rescale_priorities(diff_max)
        self._shift_by_avg_proposer_priority()
        proposer = None
        for _ in range(times):
            proposer = self._increment_proposer_priority()
        self.proposer = proposer

    def copy_increment_proposer_priority(self, times: int) -> "ValidatorSet":
        c = self.copy()
        c.increment_proposer_priority(times)
        return c

    # -- updates ------------------------------------------------------------

    def update_with_change_set(self, changes: Sequence[Validator]) -> None:
        """Apply validator updates/removals (power 0 = removal).
        (reference: types/validator_set.go:577-652 updateWithChangeSet)"""
        if not changes:
            return
        # split and sanity-check
        seen = set()
        updates: List[Validator] = []
        deletes: List[Validator] = []
        # Copy first: priorities are assigned to update entries below and must
        # not leak into the caller's objects.
        for c in sorted((c.copy() for c in changes), key=lambda v: v.address):
            if c.address in seen:
                raise ValueError(f"duplicate entry {c.address.hex()} in changes")
            seen.add(c.address)
            if c.voting_power < 0:
                raise ValueError("voting power can't be negative")
            if c.voting_power > MAX_TOTAL_VOTING_POWER:
                raise ValueError("to prevent clipping/overflow, voting power can't be higher than max")
            if c.voting_power == 0:
                deletes.append(c)
            else:
                updates.append(c)
        # verify deletes exist
        for d in deletes:
            if d.address not in self._by_address:
                raise ValueError(f"failed to find validator {d.address.hex()} to remove")
        # compute the new total voting power (before removals, like the reference)
        new_total = self.total_voting_power()
        for u in updates:
            _, old = self.get_by_address(u.address)
            new_total += u.voting_power - (old.voting_power if old else 0)
            if new_total > MAX_TOTAL_VOTING_POWER:
                raise ValueError("total voting power of resulting valset exceeds max")
        # new validators join with priority -1.125 * newTotal
        # (reference: types/validator_set.go:474-493)
        for u in updates:
            _, old = self.get_by_address(u.address)
            if old is None:
                u.proposer_priority = -(new_total + (new_total >> 3))
            else:
                u.proposer_priority = old.proposer_priority
        # apply
        by_addr = {v.address: v for v in self.validators}
        for u in updates:
            by_addr[u.address] = u.copy()
        for d in deletes:
            by_addr.pop(d.address, None)
        if not by_addr:
            raise ValueError("applying the validator changes would result in empty set")
        self.validators = sorted(
            by_addr.values(), key=lambda v: (-v.voting_power, v.address)
        )
        self._by_address = {v.address: i for i, v in enumerate(self.validators)}
        self._total_voting_power = None
        # scale and center
        self.rescale_priorities(PRIORITY_WINDOW_SIZE_FACTOR * self.total_voting_power())
        self._shift_by_avg_proposer_priority()
        # keep proposer reference coherent
        if self.proposer is not None and self.proposer.address in self._by_address:
            self.proposer = self.validators[self._by_address[self.proposer.address]]
        elif self.validators:
            self.proposer = self._compute_proposer()

    # -- batched commit verification ---------------------------------------

    def verify_commit(self, chain_id: str, block_id, height: int, commit) -> None:
        """All signatures checked; +2/3 must be for the block.
        (reference: types/validator_set.go:662-714, serial loop replaced by one
        batched device verify)."""
        with _commit_span("verify_commit", commit, height) as root:
            self._check_commit_for(block_id, height, commit)
            pubkeys, sigs, meta, key_types, idxs = [], [], [], [], []
            with _trace.span("commit.gather") as sp:
                for idx, cs in enumerate(commit.signatures):
                    if cs.absent():
                        continue
                    val = self.validators[idx]
                    pubkeys.append(val.pub_key.bytes())
                    idxs.append(idx)
                    sigs.append(cs.signature)
                    meta.append((idx, val.voting_power, cs.for_block()))
                    key_types.append(val.pub_key.type_name())
                sp.set(rows=len(idxs))
            root.set(rows=len(idxs))
            msgs = _sign_bytes_spanned(commit, chain_id, idxs)
            mask = verify_batch(pubkeys, msgs, sigs, key_types=key_types)
            with _trace.span("commit.tally"):
                tallied = 0
                for ok, (idx, power, for_block) in zip(mask, meta):
                    if not ok:
                        raise CommitVerifyError(f"wrong signature (#{idx})")
                    if for_block:
                        tallied += power
                needed = self.total_voting_power() * 2 // 3
                if tallied <= needed:
                    raise NotEnoughVotingPowerError(tallied, needed)

    def _check_commit_for(self, block_id, height: int, commit) -> None:
        """The structural checks every VerifyCommit* entry starts with."""
        if self.size() != len(commit.signatures):
            raise CommitVerifyError(
                f"invalid commit -- wrong set size: {self.size()} vs {len(commit.signatures)}"
            )
        if height != commit.height:
            raise CommitVerifyError(f"invalid commit -- wrong height: {height} vs {commit.height}")
        if block_id != commit.block_id:
            raise CommitVerifyError(
                f"invalid commit -- wrong block ID: want {block_id}, got {commit.block_id}"
            )

    def for_block_rows(self, commit, pubkeys: list, sigs: list, key_types: list):
        """The rows VerifyCommitLight's rule checks of `commit`, whose
        signatures stand in this set's order (the caller has held the two
        lengths equal): key, signature and key type of every for-block
        signature appended to the caller's lists, which may already hold the
        rows of other commits. Returns (idxs, powers) of the appended rows.
        No span: the caller opens one a call, or one a run of commits
        (blocksync/reactor.py, light/verifier.py), never one a commit."""
        idxs, powers = [], []
        validators = self.validators
        for idx, cs in enumerate(commit.signatures):
            if not cs.for_block():
                continue
            val = validators[idx]
            pubkeys.append(val.pub_key.bytes())
            idxs.append(idx)
            sigs.append(cs.signature)
            key_types.append(val.pub_key.type_name())
            powers.append(val.voting_power)
        return idxs, powers

    def begin_verify_commit_light(self, chain_id: str, block_id, height: int, commit):
        """Submit-phase of verify_commit_light: structural checks + device
        submit; returns a finish() callable that syncs, tallies, and raises
        on failure. Lets callers overlap several independent commit
        verifications' device round trips (light/verifier.py pipelines the
        trusting+light pair this way).

        Spans: the submit phase is the call's `commit.verify` root (verdict
        `submitted`); finish() opens `commit.finish` under it by explicit
        parent (the verdict is finish()'s to give), since other calls'
        submits may lie between the two."""
        from tendermint_tpu.crypto.batch import verify_batch_submit

        with _commit_span("verify_commit_light", commit, height, "submitted") as root:
            self._check_commit_for(block_id, height, commit)
            pubkeys, sigs, key_types = [], [], []
            with _trace.span("commit.gather") as sp:
                idxs, powers = self.for_block_rows(commit, pubkeys, sigs, key_types)
                sp.set(rows=len(idxs))
            root.set(rows=len(idxs))
            msgs = _sign_bytes_spanned(commit, chain_id, idxs)
            handle = verify_batch_submit(pubkeys, msgs, sigs, key_types=key_types)
        needed = self.total_voting_power() * 2 // 3
        return self._light_finish(root, handle, powers, needed)

    @staticmethod
    def _light_finish(root, handle, powers, needed: int):
        """finish() of the two light entries: sync, tally, raise."""
        from tendermint_tpu.crypto.batch import verify_batch_finish

        entry = root.attrs["entry"] if root.recording else ""

        def finish() -> None:
            with _trace.span("commit.finish", parent=root, entry=entry) as fin:
                mask = verify_batch_finish(handle)
                with _trace.span("commit.tally"):
                    tallied = sum(p for ok, p in zip(mask, powers) if ok)
                    if tallied <= needed:
                        fin.set(verdict="NotEnoughVotingPowerError")
                        raise NotEnoughVotingPowerError(tallied, needed)
                fin.set(verdict="accepted")

        return finish

    def verify_commit_light(self, chain_id: str, block_id, height: int, commit) -> None:
        """Only for-block signatures verified, batched; valid tally must exceed
        2/3 (reference: types/validator_set.go:719-763)."""
        self.begin_verify_commit_light(chain_id, block_id, height, commit)()

    def begin_verify_commit_light_trusting(
        self, chain_id: str, commit, trust_level: Fraction
    ):
        """Submit-phase of verify_commit_light_trusting; see
        begin_verify_commit_light."""
        from tendermint_tpu.crypto.batch import verify_batch_submit

        if trust_level.denominator == 0:
            raise CommitVerifyError("trustLevel has zero Denominator")
        total_mul = self.total_voting_power() * trust_level.numerator
        needed = total_mul // trust_level.denominator
        with _commit_span(
            "verify_commit_light_trusting", commit, commit.height, "submitted"
        ) as root:
            seen: Dict[int, int] = {}
            pubkeys, sigs, powers, idxs = [], [], [], []
            key_types = []
            with _trace.span("commit.gather") as sp:
                for idx, cs in enumerate(commit.signatures):
                    if not cs.for_block():
                        continue
                    val_idx, val = self.get_by_address(cs.validator_address)
                    if val is None:
                        continue
                    if val_idx in seen:
                        raise CommitVerifyError(
                            f"double vote from {val.address.hex()} ({seen[val_idx]} and {idx})"
                        )
                    seen[val_idx] = idx
                    pubkeys.append(val.pub_key.bytes())
                    idxs.append(idx)
                    sigs.append(cs.signature)
                    powers.append(val.voting_power)
                    key_types.append(val.pub_key.type_name())
                sp.set(rows=len(idxs))
            root.set(rows=len(idxs))
            msgs = _sign_bytes_spanned(commit, chain_id, idxs)
            handle = verify_batch_submit(pubkeys, msgs, sigs, key_types=key_types)
        return self._light_finish(root, handle, powers, needed)

    def verify_commit_light_trusting(
        self, chain_id: str, commit, trust_level: Fraction
    ) -> None:
        """Trust-level verification against a possibly different validator set
        (reference: types/validator_set.go:772-830)."""
        self.begin_verify_commit_light_trusting(chain_id, commit, trust_level)()

    # -- BLS aggregate-commit verification (ISSUE 14) -----------------------

    def verify_aggregate_commit(self, chain_id: str, block_id, height: int, commit) -> None:
        """VerifyAggregateCommit: ONE pairing check + ONE bitmap-weighted
        aggregate-pubkey MSM, against the single canonical message every
        signer signed (types/block.AggregateCommit). No reference
        counterpart — the reference has no aggregate signatures at all.

        Routing: a plain Commit routes through verify_commit (the existing
        verify_batch ladder — device RLC, breaker, QoS lanes), so callers
        can pass whatever the wire delivered. The aggregate path:

        1. every bitmap validator must hold a bls12_381 key WITH a
           verified proof of possession (crypto/keys.register_pop) — the
           rogue-key defense; an unregistered key fails the commit, it is
           never silently skipped;
        2. apk = sum of signer pubkeys via the device-schedule MSM twin
           (ops/bls12_msm.g1_aggregate_bitmap; decompressed coordinates
           cached across heights like the ed25519 A cache);
        3. e(-g1, sigma) * e(apk, H(msg)) == 1 (bls_ref pairing);
        4. signer voting power must exceed 2/3 of the total.

        Raises CommitVerifyError / NotEnoughVotingPowerError like the
        other Verify* entries."""
        from tendermint_tpu.crypto import bls_ref
        from tendermint_tpu.crypto.batch import record_backend_rows
        from tendermint_tpu.crypto.keys import pop_verified
        from tendermint_tpu.libs import metrics as _metrics
        from tendermint_tpu.ops import bls12_msm
        from tendermint_tpu.types.block import AggregateCommit

        if not isinstance(commit, AggregateCommit):
            return self.verify_commit(chain_id, block_id, height, commit)
        commit.validate_basic()
        if height != commit.height:
            raise CommitVerifyError(
                f"invalid commit -- wrong height: {height} vs {commit.height}"
            )
        if block_id != commit.block_id:
            raise CommitVerifyError(
                f"invalid commit -- wrong block ID: want {block_id}, got {commit.block_id}"
            )
        idxs = commit.signer_indices()
        if idxs and idxs[-1] >= self.size():
            raise CommitVerifyError(
                f"invalid commit -- signer index {idxs[-1]} out of range ({self.size()} validators)"
            )
        coords, powers = [], []
        for i in idxs:
            val = self.validators[i]
            if val.pub_key.type_name() != "bls12_381":
                raise CommitVerifyError(
                    f"invalid commit -- validator #{i} is {val.pub_key.type_name()}, "
                    "cannot join a BLS aggregate"
                )
            if not pop_verified(val.pub_key.bytes()):
                raise CommitVerifyError(
                    f"invalid commit -- validator #{i} has no verified proof of "
                    "possession (rogue-key defense)"
                )
            coords.append(_bls_pubkey_coords(val.pub_key.bytes()))
            powers.append(val.voting_power)
        record_backend_rows("bls12_381", len(idxs))
        m = _metrics.batch_metrics()
        m.aggregate_size.set(len(idxs))
        apk = bls12_msm.g1_aggregate_bitmap(coords, [True] * len(coords))
        if apk is None:
            raise CommitVerifyError("invalid commit -- empty aggregate pubkey")
        sig = bls_ref.g2_from_bytes(commit.agg_signature)
        if sig is None:
            raise CommitVerifyError("invalid commit -- malformed aggregate signature")
        apk_jac = (
            bls_ref._G1Field(apk[0]),
            bls_ref._G1Field(apk[1]),
            bls_ref._G1Field(1),
        )
        msg = commit.sign_bytes(chain_id)
        ok = bls_ref.pairings_are_one(
            [
                (bls_ref._jac_neg(bls_ref.G1_GEN), sig),
                (apk_jac, bls_ref.hash_to_g2(msg)),
            ]
        )
        if not ok:
            raise CommitVerifyError("invalid commit -- aggregate signature mismatch")
        tallied = sum(powers)
        needed = self.total_voting_power() * 2 // 3
        if tallied <= needed:
            raise NotEnoughVotingPowerError(tallied, needed)


# Decompressed BLS pubkey coordinate cache: consensus re-verifies the same
# validator set every height, and the 48-byte -> affine decompression (one
# field sqrt + subgroup check) is the per-key host cost worth amortizing —
# the exact shape of crypto/batch.py's ed25519 A cache.
_BLS_COORD_CACHE: Dict[bytes, Tuple[int, int]] = {}


def _bls_pubkey_coords(pk_bytes: bytes) -> Tuple[int, int]:
    got = _BLS_COORD_CACHE.get(pk_bytes)
    if got is not None:
        return got
    from tendermint_tpu.crypto import bls_ref

    pt = bls_ref.g1_from_bytes(pk_bytes)
    if pt is None:
        raise CommitVerifyError("invalid bls12_381 pubkey in validator set")
    aff = bls_ref._jac_to_affine(pt)
    got = (aff[0].v, aff[1].v)
    if len(_BLS_COORD_CACHE) < 1 << 20:
        _BLS_COORD_CACHE[bytes(pk_bytes)] = got
    return got
