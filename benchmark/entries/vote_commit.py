"""Entry driver `vote_commit`: one operation is one height's precommit step
at a node in consensus, through the program's own objects and nothing beside
them, as consensus/cs_state.py's `_add_vote` and `_flush_deferred_votes` hold
them:

    votes = HeightVoteSet(chain_id, height, vals, defer_verification=True)
    votes.add_vote(vote, peer_id)        for each of the item's signed precommits
    votes.flush_all()                    ONE VoteSet.flush, on the scheduler's votes lane
    commit = votes.precommits(0).make_commit()
    vals.verify_commit(chain_id, block_id, height, commit)

The last line is the check the next block's LastCommit gets, and with the
verified-row memo on ([crypto] verified_memo_rows, the mix's) it is answered
from memory: the rows are the ones the flush just verified. ConsensusState
itself (proposal, WAL, application) stays out: it verifies no signature of
its own. The scheduler is a VerifyScheduler built from config.py's defaults
and set as the process default, as node/node.py does, so VoteSet.flush finds
it and the rows ride the votes lane.

Set-up builds the program's Vote objects from each item's bytes, in an
arrival order shuffled from the item's own drawn block hash (so from the
seed), each with the gossiping peer it came from, one of the configuration's
`peers` round robin over that order. The HeightVoteSet and everything after
it are built inside the timed call.

What is the same question for every entry (the combined check asked
directly, where a device flush has to run, the control `unsent_third`) is
taken from entries/verify_commit.py, not copied."""

from __future__ import annotations

import atexit
import contextlib
import gc
import os
import time

import numpy as np

import spec
from reference import FLAG_ABSENT

_vc = spec.load_module(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                    "verify_commit.py"))
native_ready = _vc.native_ready
process_faults = _vc.process_faults
passes_clean = _vc.passes_clean
rejects = _vc.rejects

_inner = [None]      # what a verify_batch call runs: the program's, as build() found it
_annotate = [None]   # while tracing: annotate("bench:flush") around the votes' flush
_records: list = []  # the flush record of each verify_batch call since the last call() began
_call: dict = {}     # what the last call() saw of itself
_memo_rows = [0]     # the mix's verified_memo_rows (a rehearsal's: build() scales them)
_warmup = [0, 0]     # the mix's warmup_calls, and the call()s made so far


def configure(traffic: dict) -> None:
    """What the mix states about the process: [crypto] verified_memo_rows."""
    _memo_rows[0] = int(traffic["verified_memo_rows"])
    _warmup[0] = int(traffic["warmup_calls"])
    _vc.configure(traffic)


def _verify_batch(pubkeys, msgs, sigs, *a, **kw):
    """In verify_batch's place from build() on, for both callers of a call
    (the votes lane and ValidatorSet.verify_commit): the call itself, and
    beside it the flush record it left, which the next call overwrites: as
    entries/verify_commit.py reads it, and what that reading leaves out of
    the memo's counters."""
    from tendermint_tpu.libs import trace

    first = _annotate[0] is not None and not _records
    with _annotate[0]("bench:flush") if first else contextlib.nullcontext():
        got = _inner[0](pubkeys, msgs, sigs, *a, **kw)
    last = trace.verify_stats()["last_flush"]
    _records.append(dict(_vc.flush_reading(), memo_hits=last.get("memo_hits"),
                         memo_ms=last.get("memo_ms")))
    return got


class _Step:
    """One item as the node meets it: the block id it would commit, and the
    votes in the order they arrive, each with the peer that brought it."""

    def __init__(self, block_id, height, arrivals):
        self.block_id, self.height, self.arrivals = block_id, height, arrivals


class State:
    def __init__(self, config, vals, items):
        from tendermint_tpu.config.config import SchedulerConfig
        from tendermint_tpu.crypto import batch, scheduler
        from tendermint_tpu.crypto.keys import Ed25519PubKey
        from tendermint_tpu.types import validator_set
        from tendermint_tpu.types.basic import BlockID, PartSetHeader, SignedMsgType
        from tendermint_tpu.types.validator_set import Validator, ValidatorSet
        from tendermint_tpu.types.vote import Vote

        self.chain_id = config["chain_id"]
        self.vals = ValidatorSet(
            [Validator(Ed25519PubKey(pk), p) for pk, p in zip(vals.pubkeys, vals.powers)]
        )
        if [v.pub_key.bytes() for v in self.vals.validators] != list(vals.pubkeys):
            raise SystemExit("vote_commit: the program orders the validator set "
                             "otherwise than power, then address")
        if self.vals.total_voting_power() != vals.total_power:
            raise SystemExit("vote_commit: the program's total power is not the stated one")
        # a rehearsal of N validators keeps the cell's ratio of memo to step (65,536 rows to
        # 10,000 votes), so that there too a height's rows are gone before its turn comes again
        rows = _memo_rows[0] * len(vals.pubkeys) // int(config["validators"])
        if rows != _memo_rows[0]:
            _memo_rows[0] = rows
            batch.configure_verified_memo(rows)
        addrs = [v.address for v in self.vals.validators]
        peers = ["p%02d" % k for k in range(int(config["peers"]))]
        self.steps = []
        for c in items:
            block_id = BlockID(c.block_hash, PartSetHeader(c.parts_total, c.parts_hash))
            votes = [Vote(SignedMsgType.PRECOMMIT, c.height, c.round, block_id, c.timestamps[i],
                          addrs[i], i, c.sigs[i])
                     for i, f in enumerate(c.flags) if f != FLAG_ABSENT]
            order = np.random.default_rng(int.from_bytes(c.block_hash[:8], "little")).permutation(
                len(votes))
            self.steps.append(_Step(block_id, c.height, [
                (votes[int(j)], peers[k % len(peers)]) for k, j in enumerate(order)]))
        # the lanes as a node has them: config.py's defaults, the process default
        self.scheduler = scheduler.VerifyScheduler(SchedulerConfig())
        scheduler.set_default(self.scheduler)
        atexit.register(self.scheduler.close)  # so that the process ends
        if _inner[0] is None:
            _inner[0] = batch.verify_batch
            batch.verify_batch = validator_set.verify_batch = _verify_batch
        # What set-up built stays out of the collector's sight. The ring holds eight heights of
        # Vote objects and the probes' two, some 96,000 at full size, where a node holds one
        # height's; left in the collector's generations they make every full collection that a
        # step's own allocations trigger (one in four calls) a pause of 85 ms in the place of 25
        # (sandbox, the verifier stubbed), which would be the cell's tail and not the program's.
        gc.collect()
        gc.freeze()


def build(config, vals, items) -> State:
    return State(config, vals, items)


def _refused(state: State, step: _Step, precommits, failed: list) -> str:
    """The words of the rule `vote_step` for a step that made no commit, from
    what the program's vote set holds; and, for the votes the flush failed,
    what no rule can see: whether one of them stands in the memo."""
    from tendermint_tpu.crypto import batch

    bits = precommits.bit_array_by_block_id(step.block_id) or []
    valid = sum(v.voting_power for v, b in zip(state.vals.validators, bits) if b)
    total = state.vals.total_voting_power()
    words = (f"no commit: valid power for the block {valid} of {total}, over {total * 2 // 3} "
             f"needed; wrong signatures: {_ranges(failed) or 'none'}")
    gone = set(failed)
    bad = [v for v, _ in step.arrivals if v.validator_index in gone]
    if bad and batch._MEMO.capacity:
        keys = [state.vals.validators[v.validator_index].pub_key.bytes() for v in bad]
        digests = batch._MEMO.digest_rows(keys, [v.sign_bytes(state.chain_id) for v in bad],
                                          [v.signature for v in bad], ["ed25519"] * len(bad))
        kept = sum(d in batch._MEMO for d in digests)
        if kept:
            words += f"; {kept} of the failed votes stand in the verified-row memo"
    return words


def _ranges(indices) -> str:
    """Sorted validator indices as the rule writes them: `#a-b, #c`."""
    out, lo, hi = [], None, None
    for i in sorted(indices):
        if hi is not None and i == hi + 1:
            hi = i
            continue
        if hi is not None:
            out.append(f"#{lo}" if lo == hi else f"#{lo}-{hi}")
        lo = hi = i
    if hi is not None:
        out.append(f"#{lo}" if lo == hi else f"#{lo}-{hi}")
    return ", ".join(out)


def call(state: State, i: int) -> str:
    """The timed call. Returns the verdict in the words of `vote_step`, or
    VerifyCommit's words where the commit it made is refused."""
    from tendermint_tpu.consensus.round_state import HeightVoteSet
    from tendermint_tpu.crypto import batch
    from tendermint_tpu.types.validator_set import CommitVerifyError, NotEnoughVotingPowerError

    del _records[:]
    _warmup[1] += 1
    if _warmup[1] == _warmup[0] + 1:
        # the window's first call: run.py's warm-up calls took the ring's first items, whose
        # rows would still stand in the memo, which no height's votes do: the window starts
        # with a memo that has seen nothing, as a node that has just started
        batch.configure_verified_memo(_memo_rows[0])
    step = state.steps[i]
    sched = state.scheduler
    seq = sched.flush_seq
    votes = HeightVoteSet(state.chain_id, step.height, state.vals, defer_verification=True)
    t0 = time.perf_counter()
    add_vote = votes.add_vote
    for vote, peer in step.arrivals:
        add_vote(vote, peer)
    add_ms = (time.perf_counter() - t0) * 1e3
    flushed = votes.flush_all()
    failed = sorted(idx for _, _, _, bad in flushed for idx in bad)
    pending = votes.has_pending()
    _call.update(add_ms=add_ms, vote_flushes=len(flushed), failed=len(failed),
                 pending_left=pending, lane_flushes=sched.flush_seq - seq,
                 lane_closed=sched.closed)
    precommits = votes.precommits(0)
    if pending or precommits.two_thirds_majority() is None:
        return _refused(state, step, precommits, failed)
    commit = precommits.make_commit()
    try:
        state.vals.verify_commit(state.chain_id, step.block_id, step.height, commit)
    except NotEnoughVotingPowerError:
        return "commit refused: not enough voting power"
    except CommitVerifyError as e:
        return f"commit refused: {e}"
    return "accepted"


def flush_reading() -> dict:
    """What the last call's two verify_batch calls say of themselves. Every
    key run.py and the accepted readers know is the VOTES' flush's (the one
    that verified: the first record not answered from the memo), but
    `total_ms`, which is the sum over the call's records, and `compile_ms`,
    which is their sum too. Beside them: `flushes` (verify_batch calls the
    call made), `device_flushes` (those not answered from the memo),
    `lane_flushes` (flushes of the scheduler during the call), `vote_flushes`
    (VoteSet.flush calls), `pending_left`, `failed`, `add_ms` (the driver's
    clock around its add_vote loop), `memo_ms` (the memo's passes of all
    records), and of the commit's record `commit_path`, `commit_rows`,
    `commit_memo_hits`."""
    records = list(_records)
    verified = [r for r in records if r["path"] != "memo"]
    r = dict(verified[0] if verified else records[0] if records else _vc.flush_reading())
    if records:
        r["total_ms"] = sum(x["total_ms"] or 0.0 for x in records)
        r["compile_ms"] = sum(x["compile_ms"] or 0.0 for x in records)
    commit = records[-1] if len(records) > 1 else {}
    r.update(flushes=len(records), device_flushes=len(verified),
             memo_ms=(sum(x["memo_ms"] or 0.0 for x in records)
                      if any(x["memo_ms"] is not None for x in records) else None),
             commit_path=commit.get("path"), commit_rows=commit.get("rows"),
             commit_memo_hits=commit.get("memo_hits"),
             **{k: _call.get(k) for k in ("add_ms", "vote_flushes", "failed", "pending_left",
                                          "lane_flushes", "lane_closed")})
    return r


def flush_fault(r: dict, expect: dict, rows: int) -> str | None:
    """None where the call made its two verify_batch calls where the
    configuration says: the votes' ONE flush under the votes lane, fresh to
    the memo, with all `rows`; the commit's answered whole from the memo."""
    if r["lane_closed"] or r["vote_flushes"] != 1 or r["lane_flushes"] != 1:
        return (f"{r['vote_flushes']} VoteSet.flush, {r['lane_flushes']} flushes of the "
                "scheduler's lanes: not ONE on the votes lane")
    if r["pending_left"]:
        return "votes left pending after the flush"
    if r["failed"]:
        return f"{r['failed']} votes failed"
    if r["flushes"] != 2 or r["device_flushes"] != 1:
        return (f"{r['flushes']} verify_batch calls, {r['device_flushes']} of them verified: "
                "not the votes' flush and the commit's answer")
    if r["memo_hits"]:
        return f"the votes' flush hit {r['memo_hits']} rows of the memo"
    fault = _vc.flush_fault(r, expect, rows)
    if fault is not None:
        return fault
    want = expect.get("commit_path", "memo")
    if r["commit_path"] != want:
        return f"the commit was answered on path {r['commit_path']!r}, not {want!r}"
    if r["commit_rows"] != rows or r["commit_memo_hits"] != rows:
        return (f"the commit's {r['commit_rows']} rows hit {r['commit_memo_hits']} of the memo, "
                f"not all {rows}")
    return None


def mask(pubkeys, msgs, sigs) -> list:
    """crypto/batch's row mask through its public call, under a memo of the
    mix's size that has seen nothing: with the window's memo the rows of its
    last heights would be answered from memory and no row mask computed."""
    from tendermint_tpu.crypto import batch

    batch.configure_verified_memo(_memo_rows[0])
    return _vc.mask(pubkeys, msgs, sigs)


@contextlib.contextmanager
def flush_spans(annotate):
    """While tracing: the votes' flush (a call's first verify_batch) under a
    span of the benchmark's own; the commit's answer from the memo is then
    the host's time after the flush."""
    _annotate[0] = annotate
    try:
        yield
    finally:
        _annotate[0] = None


def install_verifier(fn) -> None:
    """Puts `fn(pubkeys, msgs, sigs) -> bool mask` in the place of the
    program's executors UNDER verify_batch: vote set, lane, memo and flush
    record stay the program's (tests, --control)."""
    from tendermint_tpu.crypto import batch

    def routed(pubkeys, msgs, sigs, backend, key_types):
        return np.asarray(fn(pubkeys, msgs, sigs), dtype=bool), "cpu", "cpu"

    batch._verify_batch_routed = routed
    _vc._seams_off[0] = True  # rejects() asks the stand-in, not the program's check


def memo_answers_all() -> None:
    """Control: a memo that hits everything it is asked about. No vote is
    verified: the window's flushes are answered from memory, and the votes
    of the `invalid_power` probe, wrong signatures and all, are counted."""
    from tendermint_tpu.crypto import batch

    def lookup(self, digests):
        return np.ones(len(digests), dtype=bool)

    batch.VerifiedRowMemo.lookup = lookup
    batch.VerifiedRowMemo.__len__ = lambda self: 1  # an empty memo is never asked


def counted_unverified() -> None:
    """Control: votes counted as they arrive, before any flush: "no vote is
    counted before it is verified" broken in the vote set itself."""
    from tendermint_tpu.types.vote_set import VoteSet

    def add_vote(self, vote, peer_id=""):
        _addr, val = self.val_set.get_by_index(vote.validator_index)
        if self._get_vote(vote.validator_index, vote.block_id.key()) is not None:
            return False
        added, _conflicting = self._add_verified(vote.validator_index, vote, val.voting_power)
        return added

    VoteSet.add_vote = add_vote


# `unsent_third`: every combined check of crypto/batch gets a copy of row 0 in
# the place of each row past the first two thirds: the last third of a step's
# votes, in arrival order, is counted unseen.
PROGRAM_CONTROLS = dict(_vc.PROGRAM_CONTROLS, memo_answers_all=memo_answers_all,
                        counted_unverified=counted_unverified)
