"""The consensus vote path held to a plain reference (ISSUE 36): a height's
precommits handed to a HeightVoteSet with deferred verification, ONE
VoteSet.flush, make_commit, and the commit checked again by verify_commit,
which the verified-row memo answers.

The reference vote set below is written from the description of
types/vote_set.go (AddVote, MakeCommit): a dict by validator index, one
crypto/ed25519_ref.py verify a vote as it arrives, the 2/3 rule, equivocation
kept apart. It shares nothing with types/vote_set.py. The deferred path has
to give what it gives and what the program's own undeferred set
(`defer_verification=False`, one verify a vote at add_vote) gives: committed
votes, failed indices, conflicts, the 2/3 majority and make_commit()'s bytes.
"""

import dataclasses
import types

import numpy as np
import pytest

from tendermint_tpu import native
from tendermint_tpu.consensus.round_state import HeightVoteSet
from tendermint_tpu.crypto import batch, ed25519_ref, scheduler
from tendermint_tpu.crypto.keys import gen_ed25519
from tendermint_tpu.libs import trace
from tendermint_tpu.types import canonical
from tendermint_tpu.types.basic import BlockID, BlockIDFlag, PartSetHeader, SignedMsgType
from tendermint_tpu.types.validator_set import CommitVerifyError, Validator, ValidatorSet
from tendermint_tpu.types.vote import Vote
from tendermint_tpu.types.vote_set import ConflictingVotesError, VoteSet, VoteSetError

CHAIN = "vote-commit-chain"
HEIGHT = 7
BLOCK = BlockID(b"\x0b" * 32, PartSetHeader(3, b"\x0c" * 32))
OTHER = BlockID(b"\x1b" * 32, PartSetHeader(1, b"\x1c" * 32))


# -- the plain reference


class RefVoteSet:
    """One height's precommits as types/vote_set.go describes AddVote: a
    vote seen before is a duplicate whatever peer brings it; a vote's
    signature is verified, alone, before the vote is counted; a wrong one is
    named by index and counted nowhere; a validator's second vote for another
    block is kept apart as a conflict and counted nowhere; a block is
    committed once the votes for it hold over 2/3 of the power."""

    def __init__(self, pubkeys, powers):
        self.pubkeys, self.powers = pubkeys, powers
        self.votes: dict = {}     # validator index -> the vote counted
        self.seen: set = set()
        self.committed: list = []  # in arrival order
        self.failed: list = []     # validator indices, in arrival order
        self.conflicts: list = []  # (index, block of the vote counted, block of the later one)
        self.by_block: dict = {}
        self.maj23 = None

    def add(self, vote) -> None:
        key = (vote.validator_index, vote.block_id.key(), vote.signature)
        if key in self.seen:
            return
        self.seen.add(key)
        msg = canonical.vote_sign_bytes(CHAIN, vote.type, vote.height, vote.round,
                                        vote.block_id, vote.timestamp_ns)
        if not ed25519_ref.verify(self.pubkeys[vote.validator_index], msg, vote.signature):
            self.failed.append(vote.validator_index)
            return
        first = self.votes.get(vote.validator_index)
        if first is not None:
            self.conflicts.append((vote.validator_index, first.block_id, vote.block_id))
            return
        self.votes[vote.validator_index] = vote
        self.committed.append(vote)
        block = vote.block_id.key()
        self.by_block[block] = self.by_block.get(block, 0) + self.powers[vote.validator_index]
        if self.maj23 is None and self.by_block[block] * 3 > sum(self.powers) * 2:
            self.maj23 = vote.block_id

    def commit_sigs(self):
        """MakeCommit: per validator (flag, address, timestamp, signature),
        absent where there is no vote or one for another block."""
        if self.maj23 is None:
            return None
        out = []
        for idx in range(len(self.pubkeys)):
            v = self.votes.get(idx)
            if v is not None and v.block_id == self.maj23:
                out.append((BlockIDFlag.COMMIT, v.validator_address, v.timestamp_ns, v.signature))
            else:
                out.append((BlockIDFlag.ABSENT, b"", 0, b""))
        return out


# -- a step's votes from seeded keys


class Step:
    def __init__(self, n: int, seed: int, height: int = HEIGHT):
        rng = np.random.default_rng([seed, n])
        privs = [gen_ed25519(rng.integers(0, 256, 32, dtype=np.uint8).tobytes())
                 for _ in range(n)]
        self.vals = ValidatorSet([Validator(p.pub_key(), 10 + k % 3) for k, p in enumerate(privs)])
        by_addr = {p.pub_key().address(): p for p in privs}
        self.privs = [by_addr[v.address] for v in self.vals.validators]
        self.pubkeys = [v.pub_key.bytes() for v in self.vals.validators]
        self.powers = [v.voting_power for v in self.vals.validators]
        self.height, self.n, self.rng = height, n, rng

    def vote(self, idx: int, block=BLOCK, tamper: bool = False) -> Vote:
        v = Vote(SignedMsgType.PRECOMMIT, self.height, 0, block,
                 1_700_000_000_000_000_000 + 1000 * idx + self.height,
                 self.vals.validators[idx].address, idx)
        sig = self.privs[idx].sign(v.sign_bytes(CHAIN))
        if tamper:
            sig = sig[:33] + bytes([sig[33] ^ 0x20]) + sig[34:]
        return dataclasses.replace(v, signature=sig)

    def shuffled(self, votes: list) -> list:
        """(vote, peer) in an arrival order drawn from the seed, peers round robin."""
        order = self.rng.permutation(len(votes))
        return [(votes[int(j)], "p%02d" % (k % 5)) for k, j in enumerate(order)]


def scenario(name: str, n: int, seed: int):
    """(step, arrivals) of one of the step shapes the issue names."""
    step = Step(n, seed)
    everyone = [step.vote(i) for i in range(n)]
    if name == "shuffled":
        return step, step.shuffled(everyone)
    if name == "duplicates":  # every third vote comes from three peers
        arrivals = step.shuffled(everyone)
        again = [(v, "p%02d" % (7 + k % 2)) for k, (v, _) in enumerate(arrivals) if k % 3 == 0]
        return step, arrivals + again + again[::-1]
    if name == "one_tampered":
        everyone[n // 2] = step.vote(n // 2, tamper=True)
        return step, step.shuffled(everyone)
    if name == "many_tampered":  # over a third wrong: the step never reaches 2/3
        for i in range(0, n, 2):
            everyone[i] = step.vote(i, tamper=True)
        return step, step.shuffled(everyone)
    if name == "equivocator":  # validator 1 signs two blocks, before any majority stands
        return step, ([(step.vote(1), "p00"), (step.vote(1, OTHER), "p01")]
                      + step.shuffled([v for i, v in enumerate(everyone) if i != 1]))
    if name == "short_of_power":  # 40% never vote
        return step, step.shuffled(everyone[: n * 6 // 10])
    raise ValueError(name)


SCENARIOS = ["shuffled", "duplicates", "one_tampered", "many_tampered", "equivocator",
             "short_of_power"]


def run_reference(step, arrivals):
    ref = RefVoteSet(step.pubkeys, step.powers)
    for vote, _peer in arrivals:
        ref.add(vote)
    return ref


def run_deferred(step, arrivals):
    """The path the cell drives: (votes, committed, failed, conflicts)."""
    votes = HeightVoteSet(CHAIN, step.height, step.vals, defer_verification=True)
    for vote, peer in arrivals:
        assert votes.add_vote(vote, peer) in ("pending", False)
    precommits = votes.precommits(0)
    assert precommits.sum_power() == 0 and precommits.two_thirds_majority() is None  # none counted
    flushed = votes.flush_all()
    assert not votes.has_pending() and votes.flush_all() == []
    assert [(t, r) for t, r, _, _ in flushed] == [(SignedMsgType.PRECOMMIT, 0)]
    _, _, committed, failed = flushed[0]
    conflicts = [(e.vote_b.validator_index, e.vote_a.block_id, e.vote_b.block_id)
                 for e in votes.drain_conflicts()]
    return precommits, committed, failed, conflicts


def run_undeferred(step, arrivals):
    """The program's own set with one verify a vote at add_vote."""
    vs = VoteSet(CHAIN, step.height, 0, SignedMsgType.PRECOMMIT, step.vals)
    committed, failed, conflicts, seen = [], [], [], set()
    for vote, peer in arrivals:
        key = (vote.validator_index, vote.block_id.key(), vote.signature)
        if key in seen:  # the deferred set's queue drops a vote it holds already
            continue
        seen.add(key)
        try:
            if vs.add_vote(vote, peer):
                committed.append(vote)
        except ConflictingVotesError as e:
            conflicts.append((e.vote_b.validator_index, e.vote_a.block_id, e.vote_b.block_id))
        except VoteSetError as e:
            assert "invalid signature" in str(e)
            failed.append(vote.validator_index)
    return vs, committed, failed, conflicts


def commit_or_none(vote_set):
    try:
        return vote_set.make_commit()
    except VoteSetError:
        return None


@pytest.fixture(autouse=True)
def fresh_scorer():
    """The provenance scorer is the process's: a test whose votes fail would
    leave its peers quarantined for the next (their rows then ride the
    quarantine lane, in a flush of their own)."""
    from tendermint_tpu.crypto import provenance

    prev = provenance.set_default(provenance.SuspicionScorer())
    yield
    provenance.set_default(prev)


@pytest.fixture
def votes_lane():
    """A VerifyScheduler from config.py's defaults as the process default,
    as node/node.py sets it: VoteSet.flush then rides the votes lane."""
    from tendermint_tpu.config.config import SchedulerConfig

    sched = scheduler.VerifyScheduler(SchedulerConfig())
    prev = scheduler.default_scheduler()
    scheduler.set_default(sched)
    yield sched
    scheduler.set_default(prev)
    sched.close()


# -- deferred == reference == undeferred


@pytest.mark.parametrize("n", [8, 21])
@pytest.mark.parametrize("name", SCENARIOS)
def test_the_deferred_path_gives_what_the_reference_and_the_undeferred_set_give(name, n):
    step, arrivals = scenario(name, n, seed=36)
    ref = run_reference(step, arrivals)
    precommits, committed, failed, conflicts = run_deferred(step, arrivals)
    plain, p_committed, p_failed, p_conflicts = run_undeferred(step, arrivals)
    assert committed == ref.committed == p_committed
    assert failed == ref.failed == p_failed
    assert conflicts == ref.conflicts == p_conflicts
    assert precommits.two_thirds_majority() == ref.maj23 == plain.two_thirds_majority()
    commit, p_commit = commit_or_none(precommits), commit_or_none(plain)
    want = ref.commit_sigs()
    if want is None:
        assert commit is None and p_commit is None
        assert name in ("many_tampered", "short_of_power")
        return
    assert commit.encode() == p_commit.encode()
    assert [(cs.block_id_flag, cs.validator_address, cs.timestamp_ns, cs.signature)
            for cs in commit.signatures] == want
    assert (commit.height, commit.round, commit.block_id) == (step.height, 0, BLOCK)
    # and the commit made is one VerifyCommit passes: a dropped vote is absent, not wrong
    step.vals.verify_commit(CHAIN, BLOCK, step.height, commit)
    for idx in ref.failed:
        assert commit.signatures[idx].absent()


def test_the_deferred_path_at_64_validators_under_the_votes_lane(votes_lane):
    step, arrivals = scenario("one_tampered", 64, seed=37)
    ref = run_reference(step, arrivals)
    before = votes_lane.stats()["lanes"]["votes"]["flushes"]
    precommits, committed, failed, conflicts = run_deferred(step, arrivals)
    assert votes_lane.stats()["lanes"]["votes"]["flushes"] == before + 1  # ONE flush, on the lane
    assert (committed, failed, conflicts) == (ref.committed, [32], [])
    assert precommits.two_thirds_majority() == BLOCK
    commit = precommits.make_commit()
    assert [cs.signature for cs in commit.signatures] == [s[3] for s in ref.commit_sigs()]


def test_a_failed_vote_may_come_again_valid_and_is_then_counted():
    """Dropped by index refuses nothing: the validator's real vote, arriving
    at a later tick, is verified and counted like any other."""
    step, arrivals = scenario("one_tampered", 8, seed=38)
    votes = HeightVoteSet(CHAIN, step.height, step.vals, defer_verification=True)
    for vote, peer in arrivals:
        votes.add_vote(vote, peer)
    (_, _, committed, failed), = votes.flush_all()
    assert failed == [4] and len(committed) == 7
    assert votes.add_vote(step.vote(4), "p03") == "pending"
    (_, _, committed, failed), = votes.flush_all()
    assert failed == [] and [v.validator_index for v in committed] == [4]
    assert votes.precommits(0).has_all()


# -- the memo: the commit of flushed votes is answered from memory


def count_verifies(monkeypatch) -> list:
    """Every batch of rows that reaches a verifier (host or device) under verify_batch."""
    seen = []
    inner = batch._verify_batch_routed

    def routed(pubkeys, *a, **kw):
        seen.append(len(pubkeys))
        return inner(pubkeys, *a, **kw)

    monkeypatch.setattr(batch, "_verify_batch_routed", routed)
    return seen


def last_flush() -> dict:
    return trace.verify_stats()["last_flush"]


@pytest.mark.parametrize("lane", [False, True])
def test_the_commit_of_flushed_votes_is_answered_from_the_memo(lane, monkeypatch, request):
    if lane:
        request.getfixturevalue("votes_lane")
    batch.configure_verified_memo(4096)
    verifies = count_verifies(monkeypatch)
    step, arrivals = scenario("one_tampered", 16, seed=39)
    precommits, committed, failed, _ = run_deferred(step, arrivals)
    assert verifies == [16] and failed == [8]
    votes_record = last_flush()
    assert votes_record["path"] != "memo" and votes_record["n"] == 16
    assert (votes_record["memo_rows"], votes_record["memo_hits"],
            votes_record["memo_inserted"]) == (16, 0, 15)  # the failed vote is not in the memo
    assert votes_record["memo_ms"] > 0
    commit = precommits.make_commit()
    step.vals.verify_commit(CHAIN, BLOCK, step.height, commit)
    assert verifies == [16]  # no device or host verify for the commit
    record = last_flush()
    assert (record["backend"], record["path"]) == ("memo", "memo")
    assert (record["n"], record["memo_hits"], record["memo_rows"],
            record["memo_inserted"]) == (15, 15, 15, 0)
    assert record["memo_ms"] == record["total_ms"] > 0
    # the failed vote's row is not in the memo
    bad = arrivals[[v.validator_index for v, _ in arrivals].index(8)][0]
    digest = batch._MEMO.digest_rows([step.pubkeys[8]], [bad.sign_bytes(CHAIN)], [bad.signature])
    assert digest[0] not in batch._MEMO and len(batch._MEMO) == 15


@pytest.mark.parametrize("what", ["signature", "timestamp"])
def test_one_changed_byte_in_a_memoised_row_misses_and_is_refused_by_index(what, monkeypatch):
    batch.configure_verified_memo(4096)
    step, arrivals = scenario("shuffled", 12, seed=40)
    precommits, _, _, _ = run_deferred(step, arrivals)
    commit = precommits.make_commit()
    verifies = count_verifies(monkeypatch)
    sigs = list(commit.signatures)
    cs = sigs[5]
    if what == "signature":
        changed = cs.signature[:40] + bytes([cs.signature[40] ^ 1]) + cs.signature[41:]
        sigs[5] = dataclasses.replace(cs, signature=changed)
    else:
        sigs[5] = dataclasses.replace(cs, timestamp_ns=cs.timestamp_ns + 1)
    forged = dataclasses.replace(commit, signatures=tuple(sigs))
    with pytest.raises(CommitVerifyError, match=r"wrong signature \(#5\)"):
        step.vals.verify_commit(CHAIN, BLOCK, step.height, forged)
    assert verifies == [1]  # the memo answered 11 rows; the changed one was verified, alone
    assert len(batch._MEMO) == 12  # and did not enter
    step.vals.verify_commit(CHAIN, BLOCK, step.height, commit)  # the true commit: still from memory
    assert verifies == [1] and last_flush()["memo_hits"] == 12


def test_with_a_memo_of_six_and_a_half_steps_no_vote_flush_of_a_second_lap_hits():
    """The cell's ring at a test's size: 8 heights of n votes under a memo of
    6.5 n rows (65,536 to 10,000): by LRU a height's rows are gone before its
    turn comes again, so every lap's votes are fresh to the memo and every
    commit is answered from it."""
    n = 8
    batch.configure_verified_memo(int(6.5 * n))
    heights = [Step(n, seed=41, height=5 + k) for k in range(8)]
    arrivals = [s.shuffled([s.vote(i) for i in range(n)]) for s in heights]
    for lap in range(2):
        for step, arrived in zip(heights, arrivals):
            precommits, committed, failed, _ = run_deferred(step, arrived)
            record = last_flush()
            assert (record["path"] != "memo" and record["memo_hits"] == 0
                    and record["memo_inserted"] == n), (lap, step.height, record)
            step.vals.verify_commit(CHAIN, BLOCK, step.height, precommits.make_commit())
            assert last_flush()["path"] == "memo" and last_flush()["memo_hits"] == n
    assert batch.verified_memo_stats()["evictions"] == 16 * n - int(6.5 * n)


# -- spans and counters


def by_name(events: list) -> dict:
    out: dict = {}
    for e in events:
        out.setdefault(e["name"], []).append(e)
    return out


def test_one_span_tree_a_flush_and_one_root_a_make_commit(votes_lane):
    batch.configure_verified_memo(4096)
    step, arrivals = scenario("equivocator", 16, seed=42)
    votes = HeightVoteSet(CHAIN, step.height, step.vals, defer_verification=True)
    trace.tracer.clear()
    for vote, peer in arrivals:
        votes.add_vote(vote, peer)
    assert trace.tracer.dump() == []  # add_vote opens no span: one a vote would roll the ring
    votes.flush_all()
    events = trace.tracer.dump()
    got = by_name(events)
    (root,) = got["votes.flush"]
    assert root["parent"] is None and root["root"] == root["span"]
    assert root["attrs"] == {"height": step.height, "round": 0, "type": "precommit", "rows": 17,
                             "committed": 16, "failed": 0}
    assert {e["root"] for e in events} == {root["span"]}  # ONE tree
    assert events[0]["name"] == "votes.pending"           # the first child written
    children = [e["name"] for e in events if e["parent"] == root["span"]]
    assert children == ["votes.pending", "votes.gather", "votes.sign_bytes", "lane.flush",
                        "votes.count"]
    # the queue's wait: from the first vote queued to the flush's start
    (pending,) = got["votes.pending"]
    assert pending["attrs"] == {"rows": 17}
    assert pending["t0_ns"] + pending["dur_ms"] * 1e6 <= root["t0_ns"] + 1e3
    assert pending["dur_ms"] > 0 and pending["t0_ns"] < got["votes.gather"][0]["t0_ns"]
    (lane,) = got["lane.flush"]
    assert lane["attrs"] == {"lanes": "votes", "rows": 17, "tickets": 1, "flushes": 1}
    (vb,) = got["verify_batch"]
    assert vb["parent"] == lane["span"] and vb["attrs"]["n"] == 17
    assert got["votes.count"][0]["attrs"] == {"conflicts": 1}
    look_up, insert = got["verify_batch.memo"]
    assert look_up["attrs"] == {"rows": 17, "hits": 0}
    # the memo's digests under its look-up pass, the scorer once under the flush
    (digest,) = got["memo.digest"]
    assert digest["parent"] == look_up["span"]
    assert digest["attrs"] == {"rows": 17, "native": native.available()}
    assert look_up["t0_ns"] <= digest["t0_ns"]
    assert digest["t0_ns"] + digest["dur_ms"] * 1e6 <= look_up["t0_ns"] + look_up["dur_ms"] * 1e6
    (score,) = got["provenance.score"]
    assert score["parent"] == vb["span"] and score["attrs"] == {"rows": 17}
    assert insert["attrs"] == {"rows": 17, "insert": True, "inserted": 17}
    (record,) = got["batch_verify.flush"]
    attrs = record["attrs"]
    assert (attrs["memo_rows"], attrs["memo_hits"], attrs["memo_inserted"]) == (17, 0, 17)
    assert attrs["memo_ms"] == pytest.approx(look_up["dur_ms"] + insert["dur_ms"], abs=0.01)

    trace.tracer.clear()
    commit = votes.precommits(0).make_commit()
    (made,) = trace.tracer.dump()
    assert made["name"] == "votes.make_commit" and made["parent"] is None
    assert made["attrs"] == {"height": step.height, "rows": 16}

    trace.tracer.clear()
    step.vals.verify_commit(CHAIN, BLOCK, step.height, commit)
    got = by_name(trace.tracer.dump())
    (record,) = got["batch_verify.flush"]  # a call answered whole from memory says so
    assert record["attrs"]["path"] == "memo" and record["attrs"]["memo_hits"] == 16
    assert record["root"] == got["commit.verify"][0]["span"]
    assert got["verify_batch.memo"][0]["attrs"] == {"rows": 16, "hits": 16}
    assert "verify_batch" not in got and "flush.record" not in got
    # the commit's own pass digests its rows again; its rows carry no source
    assert got["memo.digest"][0]["parent"] == got["verify_batch.memo"][0]["span"]
    assert "provenance.score" not in got


@pytest.mark.parametrize("loaded", [True, False])
def test_the_memo_s_digest_spans_say_which_path_hashed_the_rows(loaded, monkeypatch, votes_lane):
    """`memo.digest` says `native` True where the library hashed the rows and
    False where the library is unavailable; either way the votes' digests are
    the ones the commit's look-up finds, every row of it."""
    if loaded and not native.available():
        pytest.skip("native batchhost unavailable (no compiler?)")
    if not loaded:
        monkeypatch.setattr(native, "available", lambda: False)
    batch.configure_verified_memo(4096)
    step, arrivals = scenario("shuffled", 12, seed=47)
    trace.tracer.clear()
    precommits, committed, failed, _ = run_deferred(step, arrivals)
    assert len(committed) == 12 and failed == []
    step.vals.verify_commit(CHAIN, BLOCK, step.height, precommits.make_commit())
    record = last_flush()
    assert (record["path"], record["n"], record["memo_hits"]) == ("memo", 12, 12)
    digests = by_name(trace.tracer.dump())["memo.digest"]
    assert [d["attrs"] for d in digests] == [{"rows": 12, "native": loaded}] * 2


def test_without_a_lane_the_flush_s_verify_batch_hangs_under_the_root():
    step, arrivals = scenario("shuffled", 8, seed=43)
    votes = HeightVoteSet(CHAIN, step.height, step.vals, defer_verification=True)
    for vote, peer in arrivals:
        votes.add_vote(vote, peer)
    trace.tracer.clear()
    votes.flush_all()
    events = trace.tracer.dump()
    root = events[-1]
    assert root["name"] == "votes.flush"
    assert [e["name"] for e in events if e["parent"] == root["span"]] == [
        "votes.pending", "votes.gather", "votes.sign_bytes", "verify_batch", "votes.count"]
    assert "verify_batch.memo" not in by_name(events)  # the memo is off: no pass, no counters
    assert "memo_rows" not in last_flush()


def test_with_the_recorder_off_the_path_constructs_no_span(monkeypatch, votes_lane):
    batch.configure_verified_memo(4096)
    step, arrivals = scenario("one_tampered", 8, seed=44)
    made = []
    init = trace.Span.__init__

    def counting(self, *a, **kw):
        made.append(a[1])
        init(self, *a, **kw)

    monkeypatch.setattr(trace.Span, "__init__", counting)
    monkeypatch.setattr(trace.Since, "__init__", counting)
    monkeypatch.setattr(trace.tracer, "enabled", False)
    trace.tracer.clear()
    precommits, committed, failed, _ = run_deferred(step, arrivals)
    step.vals.verify_commit(CHAIN, BLOCK, step.height, precommits.make_commit())
    assert made == [] and trace.tracer.dump() == []
    assert failed == [4] and len(committed) == 7
    record = last_flush()  # the records are kept all the same, with the memo's time
    assert record["path"] == "memo" and record["memo_hits"] == 7 and record["memo_ms"] > 0


def test_the_queue_s_wait_starts_at_the_first_deferred_vote():
    """`votes.pending` runs from the vote that finds the queue empty to the
    flush's start, once a flush; votes queued after a flush wait anew, and a
    flush with nothing queued writes nothing."""
    step, arrivals = scenario("shuffled", 8, seed=46)
    vs = VoteSet(CHAIN, step.height, 0, SignedMsgType.PRECOMMIT, step.vals,
                 defer_verification=True)
    trace.tracer.clear()
    waits = []
    for part in (arrivals[:3], arrivals[3:]):
        before = trace._perf_ns()
        vs.add_vote(*part[0])
        after = trace._perf_ns()
        for vote, peer in part[1:]:
            vs.add_vote(vote, peer)
        vs.flush()
        assert vs.flush() == ([], [])
        events = trace.tracer.dump()
        (pending,) = [e for e in events if e["name"] == "votes.pending"]
        (root,) = [e for e in events if e["name"] == "votes.flush"]
        assert before <= pending["t0_ns"] <= after and pending["parent"] == root["span"]
        assert pending["attrs"] == {"rows": len(part)}
        waits.append(pending["dur_ms"])
        trace.tracer.clear()
    assert all(w > 0 for w in waits)


# -- consensus: ONE consensus.vote_flush span a tick


def test_the_tick_flushes_the_height_s_votes_and_the_late_precommits_under_one_span():
    """cs_state._flush_deferred_votes: the height's vote sets and the last
    commit's late precommits are flushed under ONE consensus.vote_flush span,
    and everything else (publishing, conflicts, progress) comes after it."""
    from tendermint_tpu.consensus.cs_state import ConsensusState

    step = Step(8, seed=45)
    votes = HeightVoteSet(CHAIN, step.height, step.vals, defer_verification=True)
    for i in range(6):
        votes.add_vote(step.vote(i), "p00")
    before = Step(8, seed=45, height=step.height - 1)
    last = VoteSet(CHAIN, before.height, 0, SignedMsgType.PRECOMMIT, before.vals,
                   defer_verification=True)
    last.add_vote(before.vote(2))
    last.add_vote(before.vote(3, tamper=True))

    said = []
    cs = ConsensusState.__new__(ConsensusState)
    cs.rs = types.SimpleNamespace(votes=votes, last_commit=last, height=step.height)
    cs.config = types.SimpleNamespace(skip_timeout_commit=False)
    cs._publish_votes = lambda vs: said.append(("publish", len(vs), len(trace.tracer.dump())))
    cs._handle_vote_conflict = lambda e: said.append(("conflict", e))
    cs._check_progress_after_vote = lambda t, r: said.append(("progress", t, r))
    trace.tracer.clear()
    cs._flush_deferred_votes()
    events = trace.tracer.dump()
    got = by_name(events)
    (tick,) = got["consensus.vote_flush"]
    assert tick["attrs"] == {"height": step.height, "committed": 7, "failed": 1}
    assert [e["parent"] for e in got["votes.flush"]] == [tick["span"]] * 2
    assert {e["root"] for e in events} == {tick["span"]}
    # published only after the span closed, the height's votes first
    assert said == [("publish", 6, len(events)), ("progress", SignedMsgType.PRECOMMIT, 0),
                    ("publish", 1, len(events))]
    trace.tracer.clear()
    cs._flush_deferred_votes()  # nothing pending: no span
    assert trace.tracer.dump() == []
