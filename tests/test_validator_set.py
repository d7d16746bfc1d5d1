"""ValidatorSet: proposer rotation, updates, batched commit verification."""

from fractions import Fraction

import pytest

from tendermint_tpu.crypto import gen_ed25519
from tendermint_tpu.types.basic import BlockID, BlockIDFlag, PartSetHeader, SignedMsgType
from tendermint_tpu.types.block import CommitSig
from tendermint_tpu.types.validator_set import (
    CommitVerifyError,
    NotEnoughVotingPowerError,
    Validator,
    ValidatorSet,
)
from tendermint_tpu.types.vote import Vote
from tendermint_tpu.types.vote_set import ConflictingVotesError, VoteSet

CHAIN = "test-chain"
BID = BlockID(hash=b"\xaa" * 32, part_set_header=PartSetHeader(total=2, hash=b"\xbb" * 32))


def make_vals(n, power=10):
    privs = [gen_ed25519(bytes([i + 1]) * 32) for i in range(n)]
    vals = [Validator(p.pub_key(), power) for p in privs]
    vs = ValidatorSet(vals)
    # map privs to sorted order
    by_addr = {p.pub_key().address(): p for p in privs}
    sorted_privs = [by_addr[v.address] for v in vs.validators]
    return vs, sorted_privs


def test_sorting_and_lookup():
    vs, privs = make_vals(5)
    addrs = [v.address for v in vs.validators]
    assert addrs == sorted(addrs)  # equal power -> sorted by address
    idx, val = vs.get_by_address(addrs[2])
    assert idx == 2 and val.address == addrs[2]
    assert vs.total_voting_power() == 50
    assert vs.has_address(addrs[0]) and not vs.has_address(b"\x00" * 20)


def test_proposer_rotation_equal_power():
    vs, _ = make_vals(4)
    seen = []
    for _ in range(8):
        vs.increment_proposer_priority(1)
        seen.append(vs.get_proposer().address)
    # with equal power every validator proposes once per 4 rounds
    assert set(seen[:4]) == set(v.address for v in vs.validators)
    assert seen[:4] == seen[4:8]


def test_proposer_weighted():
    a = gen_ed25519(b"\x01" * 32).pub_key()
    b = gen_ed25519(b"\x02" * 32).pub_key()
    vs = ValidatorSet([Validator(a, 3), Validator(b, 1)])
    counts = {}
    for _ in range(40):
        vs.increment_proposer_priority(1)
        addr = vs.get_proposer().address
        counts[addr] = counts.get(addr, 0) + 1
    assert counts[a.address()] == 30
    assert counts[b.address()] == 10


def test_priorities_centered():
    vs, _ = make_vals(7, power=100)
    for _ in range(50):
        vs.increment_proposer_priority(1)
    total = sum(v.proposer_priority for v in vs.validators)
    # centered around zero, bounded by 2*total power window
    assert abs(total) <= vs.total_voting_power() * 2 * len(vs.validators)


def test_copy_increment_does_not_mutate():
    vs, _ = make_vals(3)
    before = [(v.address, v.proposer_priority) for v in vs.validators]
    vs2 = vs.copy_increment_proposer_priority(3)
    after = [(v.address, v.proposer_priority) for v in vs.validators]
    assert before == after
    assert vs2 is not vs


def test_updates_add_remove():
    vs, _ = make_vals(3, power=10)
    new_priv = gen_ed25519(b"\x09" * 32)
    vs.update_with_change_set([Validator(new_priv.pub_key(), 5)])
    assert vs.size() == 4
    assert vs.total_voting_power() == 35
    # new validator got the -1.125*total penalty -> not immediately proposer
    _, nv = vs.get_by_address(new_priv.pub_key().address())
    assert nv.voting_power == 5
    # remove it
    vs.update_with_change_set([Validator(new_priv.pub_key(), 0)])
    assert vs.size() == 3 and vs.total_voting_power() == 30
    # removing an unknown validator errors
    with pytest.raises(ValueError, match="failed to find"):
        vs.update_with_change_set([Validator(new_priv.pub_key(), 0)])
    # power update
    target = vs.validators[0]
    vs.update_with_change_set([Validator(target.pub_key, 42)])
    assert vs.total_voting_power() == 42 + 20


def test_hash_changes_with_set():
    vs, _ = make_vals(3)
    h1 = vs.hash()
    vs.update_with_change_set([Validator(gen_ed25519(b"\x0a" * 32).pub_key(), 7)])
    assert vs.hash() != h1


def _signed_commit(vs, privs, height=5, round_=0, block_id=BID, nil_idx=(), absent_idx=(), bad_idx=()):
    sigs = []
    for i, (val, priv) in enumerate(zip(vs.validators, privs)):
        if i in absent_idx:
            sigs.append(CommitSig.absent_sig())
            continue
        bid = BlockID() if i in nil_idx else block_id
        flag = BlockIDFlag.NIL if i in nil_idx else BlockIDFlag.COMMIT
        v = Vote(
            type=SignedMsgType.PRECOMMIT,
            height=height,
            round=round_,
            block_id=bid,
            timestamp_ns=1000 + i,
            validator_address=val.address,
            validator_index=i,
        )
        sig = priv.sign(v.sign_bytes(CHAIN))
        if i in bad_idx:
            sig = bytes([sig[0] ^ 1]) + sig[1:]
        sigs.append(CommitSig(flag, val.address, v.timestamp_ns, sig))
    from tendermint_tpu.types.block import Commit

    return Commit(height, round_, block_id, tuple(sigs))


def test_verify_commit_ok():
    vs, privs = make_vals(6)
    commit = _signed_commit(vs, privs)
    vs.verify_commit(CHAIN, BID, 5, commit)
    vs.verify_commit_light(CHAIN, BID, 5, commit)
    vs.verify_commit_light_trusting(CHAIN, commit, Fraction(1, 3))


def test_verify_commit_with_nil_and_absent():
    vs, privs = make_vals(6)
    commit = _signed_commit(vs, privs, nil_idx=(1,))
    vs.verify_commit(CHAIN, BID, 5, commit)  # 5/6 voting for block > 2/3
    # exactly 2/3 (4 of 6) is NOT enough: threshold is strict
    commit2 = _signed_commit(vs, privs, nil_idx=(1,), absent_idx=(2,))
    with pytest.raises(NotEnoughVotingPowerError):
        vs.verify_commit(CHAIN, BID, 5, commit2)


def test_verify_commit_insufficient_power():
    vs, privs = make_vals(6)
    commit = _signed_commit(vs, privs, nil_idx=(0, 1), absent_idx=(2,))
    with pytest.raises(NotEnoughVotingPowerError):
        vs.verify_commit(CHAIN, BID, 5, commit)


def test_verify_commit_bad_signature():
    vs, privs = make_vals(4)
    commit = _signed_commit(vs, privs, bad_idx=(3,))
    with pytest.raises(CommitVerifyError, match="wrong signature"):
        vs.verify_commit(CHAIN, BID, 5, commit)


def test_verify_commit_wrong_height_blockid_size():
    vs, privs = make_vals(4)
    commit = _signed_commit(vs, privs)
    with pytest.raises(CommitVerifyError, match="height"):
        vs.verify_commit(CHAIN, BID, 6, commit)
    other = BlockID(hash=b"\xee" * 32, part_set_header=PartSetHeader(1, b"\xff" * 32))
    with pytest.raises(CommitVerifyError, match="block ID"):
        vs.verify_commit(CHAIN, other, 5, commit)
    small, _ = make_vals(3)
    with pytest.raises(CommitVerifyError, match="set size"):
        small.verify_commit(CHAIN, BID, 5, commit)


def test_verify_commit_light_trusting_different_set():
    vs, privs = make_vals(6)
    commit = _signed_commit(vs, privs)
    # trusted set = subset with extra unknown validator
    extra = Validator(gen_ed25519(b"\x0b" * 32).pub_key(), 10)
    trusted = ValidatorSet([Validator(v.pub_key, v.voting_power) for v in vs.validators[:4]] + [extra])
    trusted.verify_commit_light_trusting(CHAIN, commit, Fraction(1, 3))
    with pytest.raises(NotEnoughVotingPowerError):
        trusted.verify_commit_light_trusting(CHAIN, commit, Fraction(9, 10))


def test_vote_set_two_thirds():
    vs, privs = make_vals(4)
    vote_set = VoteSet(CHAIN, 5, 0, SignedMsgType.PRECOMMIT, vs)
    for i, (val, priv) in enumerate(zip(vs.validators, privs)):
        v = Vote(
            type=SignedMsgType.PRECOMMIT,
            height=5,
            round=0,
            block_id=BID,
            timestamp_ns=1000,
            validator_address=val.address,
            validator_index=i,
        )
        v = v.with_signature(priv.sign(v.sign_bytes(CHAIN)))
        assert vote_set.add_vote(v)
        if i < 2:
            assert not vote_set.has_two_thirds_majority()
    assert vote_set.has_two_thirds_majority()
    assert vote_set.two_thirds_majority() == BID
    commit = vote_set.make_commit()
    vs.verify_commit(CHAIN, BID, 5, commit)


def test_vote_set_rejects_invalid():
    vs, privs = make_vals(3)
    vote_set = VoteSet(CHAIN, 5, 0, SignedMsgType.PRECOMMIT, vs)
    val, priv = vs.validators[0], privs[0]
    v = Vote(
        type=SignedMsgType.PRECOMMIT,
        height=5,
        round=0,
        block_id=BID,
        timestamp_ns=0,
        validator_address=val.address,
        validator_index=0,
    )
    signed = v.with_signature(priv.sign(v.sign_bytes(CHAIN)))
    # wrong height
    import dataclasses

    from tendermint_tpu.types.vote_set import VoteSetError

    with pytest.raises(VoteSetError, match="expected"):
        vote_set.add_vote(dataclasses.replace(signed, height=6))
    # bad signature
    with pytest.raises(VoteSetError, match="invalid signature"):
        vote_set.add_vote(v.with_signature(b"\x00" * 64))
    # good vote then duplicate
    assert vote_set.add_vote(signed)
    assert not vote_set.add_vote(signed)


def test_vote_set_conflict_detection():
    vs, privs = make_vals(3)
    vote_set = VoteSet(CHAIN, 5, 0, SignedMsgType.PRECOMMIT, vs)
    val, priv = vs.validators[0], privs[0]

    def mk(bid):
        v = Vote(
            type=SignedMsgType.PRECOMMIT,
            height=5,
            round=0,
            block_id=bid,
            timestamp_ns=0,
            validator_address=val.address,
            validator_index=0,
        )
        return v.with_signature(priv.sign(v.sign_bytes(CHAIN)))

    assert vote_set.add_vote(mk(BID))
    other = BlockID(hash=b"\xcc" * 32, part_set_header=PartSetHeader(1, b"\xdd" * 32))
    with pytest.raises(ConflictingVotesError):
        vote_set.add_vote(mk(other))


def test_vote_set_deferred_batch_flush():
    vs, privs = make_vals(4)
    vote_set = VoteSet(CHAIN, 5, 0, SignedMsgType.PRECOMMIT, vs, defer_verification=True)
    for i, (val, priv) in enumerate(zip(vs.validators, privs)):
        v = Vote(
            type=SignedMsgType.PRECOMMIT,
            height=5,
            round=0,
            block_id=BID,
            timestamp_ns=0,
            validator_address=val.address,
            validator_index=i,
        )
        sig = priv.sign(v.sign_bytes(CHAIN))
        if i == 2:
            sig = bytes([sig[0] ^ 1]) + sig[1:]  # corrupt one
        vote_set.add_vote(v.with_signature(sig))
    assert not vote_set.has_two_thirds_majority()  # nothing committed yet
    committed, failed = vote_set.flush()
    assert failed == [2]
    assert len(committed) == 3  # the valid votes, published only now
    assert vote_set.has_two_thirds_majority()  # 3/4 valid > 2/3


def test_vote_set_deferred_detects_equivocation():
    vs, privs = make_vals(4)
    vote_set = VoteSet(CHAIN, 5, 0, SignedMsgType.PRECOMMIT, vs, defer_verification=True)
    val, priv = vs.validators[0], privs[0]

    def mk(bid, i=0):
        v = Vote(
            type=SignedMsgType.PRECOMMIT, height=5, round=0, block_id=bid,
            timestamp_ns=0, validator_address=vs.validators[i].address, validator_index=i,
        )
        return v.with_signature(privs[i].sign(v.sign_bytes(CHAIN)))

    other = BlockID(hash=b"\xcc" * 32, part_set_header=PartSetHeader(1, b"\xdd" * 32))
    v1, v2 = mk(BID), mk(other)
    assert vote_set.add_vote(v1)
    assert not vote_set.add_vote(v1)  # duplicate detected while pending
    assert vote_set.add_vote(v2) == "pending"  # queued; conflict surfaces at flush
    committed, failed = vote_set.flush()
    assert failed == []
    conflicts = vote_set.pop_conflicts()
    assert len(conflicts) == 1
    assert {conflicts[0].vote_a.block_id, conflicts[0].vote_b.block_id} == {BID, other}
    assert vote_set.pop_conflicts() == []


def test_vote_set_peer_maj23_tracks_conflicting_votes():
    # Mirrors reference behavior: a conflicting vote for a peer-claimed-maj23
    # block is still tallied under that block and can produce the 2/3 majority.
    vs, privs = make_vals(4)
    vote_set = VoteSet(CHAIN, 5, 0, SignedMsgType.PRECOMMIT, vs)

    def mk(i, bid):
        v = Vote(
            type=SignedMsgType.PRECOMMIT, height=5, round=0, block_id=bid,
            timestamp_ns=0, validator_address=vs.validators[i].address, validator_index=i,
        )
        return v.with_signature(privs[i].sign(v.sign_bytes(CHAIN)))

    nil = BlockID()
    vote_set.set_peer_maj23("peer1", BID)
    # validator 0 votes nil first, then equivocates with a vote for BID
    assert vote_set.add_vote(mk(0, nil))
    with pytest.raises(ConflictingVotesError):
        vote_set.add_vote(mk(0, BID))
    # the conflicting vote was tracked under BID: it counts toward the 2/3,
    # so only 2 more votes are needed (10+10+10 = 30 > 2/3*40)
    assert vote_set.add_vote(mk(1, BID))
    assert not vote_set.has_two_thirds_majority()
    assert vote_set.add_vote(mk(2, BID))
    assert vote_set.has_two_thirds_majority()
    assert vote_set.two_thirds_majority() == BID


def test_update_with_change_set_does_not_mutate_caller():
    vs, _ = make_vals(3)
    new_val = Validator(gen_ed25519(b"\x0c" * 32).pub_key(), 5)
    assert new_val.proposer_priority == 0
    vs.update_with_change_set([new_val])
    assert new_val.proposer_priority == 0  # caller's object untouched


def test_vote_set_deferred_flush_mixed_key_types():
    """Deferred flush must verify each vote under ITS key type: an sr25519
    vote checked as ed25519 always fails (marker bit forces s >= L), which
    would silently drop valid votes — a liveness break in mixed sets
    (advisor r3 medium; mirrors validator_set batched Verify*)."""
    from tendermint_tpu.crypto.sr25519 import gen_sr25519

    privs = [gen_ed25519(bytes([i + 1]) * 32) for i in range(3)] + [
        gen_sr25519(b"\x77" * 32)
    ]
    vs = ValidatorSet([Validator(p.pub_key(), 10) for p in privs])
    by_addr = {p.pub_key().address(): p for p in privs}
    sorted_privs = [by_addr[v.address] for v in vs.validators]
    vote_set = VoteSet(CHAIN, 5, 0, SignedMsgType.PRECOMMIT, vs, defer_verification=True)
    for i, (val, priv) in enumerate(zip(vs.validators, sorted_privs)):
        v = Vote(
            type=SignedMsgType.PRECOMMIT,
            height=5,
            round=0,
            block_id=BID,
            timestamp_ns=0,
            validator_address=val.address,
            validator_index=i,
        )
        vote_set.add_vote(v.with_signature(priv.sign(v.sign_bytes(CHAIN))))
    committed, failed = vote_set.flush()
    assert failed == []
    assert len(committed) == 4  # the sr25519 vote survives the deferred path


# -- ValidatorSet.hash() from leaf hashes memoised by value (ISSUE 35)


def _pub_key(kind, i):
    import hashlib

    from tendermint_tpu.crypto.keys import Bls12381PubKey, Ed25519PubKey
    from tendermint_tpu.crypto.sr25519 import Sr25519PubKey

    raw = hashlib.sha512(b"key-%d" % i).digest()
    return {"ed25519": lambda: Ed25519PubKey(raw[:32]), "sr25519": lambda: Sr25519PubKey(raw[:32]),
            "bls12_381": lambda: Bls12381PubKey(raw[:48])}[kind]()


def _set_of(kind, n, power=10, first=0):
    return ValidatorSet([Validator(_pub_key(kind, first + i), power + i % 3) for i in range(n)])


def _plain_hash(vs):
    """The formula: the root over every validator's SimpleValidator bytes."""
    from tendermint_tpu.crypto.merkle import hash_from_byte_slices

    return hash_from_byte_slices([v.simple_bytes() for v in vs.validators])


@pytest.fixture
def cold_memo():
    from tendermint_tpu.types import validator_set

    validator_set._leaf_memo.clear()
    return validator_set


@pytest.mark.parametrize("kind", ["ed25519", "sr25519", "bls12_381"])
@pytest.mark.parametrize("n", [1, 2, 3, 100, 101])
def test_hash_is_the_plain_formula_cold_and_warm(cold_memo, kind, n):
    vs = _set_of(kind, n)
    want = _plain_hash(vs)
    before = cold_memo.leaf_memo_counts()
    assert vs.hash() == want  # cold: every leaf hashed
    cold = cold_memo.leaf_memo_counts()
    assert vs.hash() == want and vs.copy().hash() == want  # warm: every leaf found
    warm = cold_memo.leaf_memo_counts()
    assert (cold[0] - before[0], cold[1] - before[1]) == (n, 0)
    assert (warm[0] - cold[0], warm[1] - cold[1]) == (2 * n, 2 * n)
    assert len(cold_memo._leaf_memo) == n


def test_one_power_or_one_key_changed_is_another_hash(cold_memo):
    vs = _set_of("ed25519", 7)
    h = vs.hash()
    target = vs.validators[3]
    # another power for one validator, in a set built anew
    other = ValidatorSet([Validator(v.pub_key, v.voting_power + (v is target)) for v in vs.validators])
    assert other.hash() == _plain_hash(other) != h
    # another key at the same power, and the same key bytes under another key type
    from tendermint_tpu.crypto.sr25519 import Sr25519PubKey

    for key in (_pub_key("ed25519", 99), Sr25519PubKey(target.pub_key.bytes())):
        swapped = ValidatorSet([Validator(key if v is target else v.pub_key, v.voting_power)
                                for v in vs.validators])
        assert swapped.hash() == _plain_hash(swapped) and swapped.hash() not in (h, other.hash())
    # a power changed in place, memo warm: the leaf's key is its value, not the object
    vs.update_with_change_set([Validator(target.pub_key, target.voting_power + 1)])
    assert vs.hash() == _plain_hash(vs) == other.hash()
    # proposer priority has no part in a leaf
    before = vs.hash()
    priorities = [v.proposer_priority for v in vs.validators]
    vs.increment_proposer_priority(3)
    assert [v.proposer_priority for v in vs.validators] != priorities
    assert vs.hash() == before == _plain_hash(vs)


def test_a_set_larger_than_the_memos_bound_is_hashed_right_twice(cold_memo, monkeypatch):
    monkeypatch.setattr(cold_memo, "_LEAF_MEMO_BOUND", 5)
    vs, small = _set_of("ed25519", 23), _set_of("ed25519", 4, first=50)
    want = _plain_hash(vs)
    for _ in range(2):
        assert vs.hash() == want and len(cold_memo._leaf_memo) <= 5
        assert small.hash() == _plain_hash(small)


def test_an_unsupported_key_type_raises_warm_or_cold(cold_memo):
    class OtherKey(type(_pub_key("ed25519", 0))):
        def type_name(self):
            return "secp256k1"

    vs = _set_of("ed25519", 3)
    vs.validators[1].pub_key = OtherKey(vs.validators[1].pub_key.bytes())
    for _ in range(2):  # nothing is kept for it, so the second ask raises as the first
        with pytest.raises(ValueError, match="unsupported key type secp256k1"):
            vs.hash()
    assert all(key[0] == "ed25519" for key in cold_memo._leaf_memo)


def test_eight_threads_hashing_overlapping_sets_agree(cold_memo, monkeypatch):
    """Sets that share most of their keys, as a chain's do, hashed from eight
    threads over a memo so small that it is dropped again and again."""
    import sys
    import threading

    monkeypatch.setattr(cold_memo, "_LEAF_MEMO_BOUND", 48)
    sets = [_set_of("ed25519", 40, first=k) for k in range(16)]
    want = [_plain_hash(vs) for vs in sets]
    before = cold_memo.leaf_memo_counts()
    got, errors = {}, []

    def work(t):
        try:
            got[t] = [[sets[(t + k) % 16].copy().hash() for k in range(16)] for _ in range(6)]
        except Exception as e:  # a worker's failure has to reach the assertion
            errors.append(e)

    threads = [threading.Thread(target=work, args=(t,)) for t in range(8)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not errors and not any(th.is_alive() for th in threads)
    for t in range(8):
        assert got[t] == [[want[(t + k) % 16] for k in range(16)]] * 6
    after = cold_memo.leaf_memo_counts()
    assert after[0] - before[0] == 8 * 6 * 16 * 40 and 0 < after[1] - before[1] < after[0] - before[0]
