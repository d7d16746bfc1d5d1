"""Entry driver `blocksync_run`: one operation is one call of
BlocksyncReactor._verify_run_batched(run) on a run of `commits_per_call`
(first, parts, second, enc) triples, the call the block-sync reactor's verify
stage waits on while a node catches up. The reactor's scheduler is a
VerifyScheduler built from config.py's defaults, as node/node.py builds it,
so the run's rows go through scheduler.verify_rows("catchup", ...) and are
flushed on the scheduler's dispatch thread.

`second.last_commit` is the program's own Commit built from the item's
bytes. `first` and `parts` are stand-ins that carry the item's drawn block
hash, height and part-set header (data.py draws block ids and builds no
blocks), which is all _verify_run_batched reads of them.

What is the same question for every entry (the row mask through the public
call, the timed programs' combined check asked directly, where a flush has
to run) is taken from entries/verify_commit.py, not copied."""

from __future__ import annotations

import atexit
import contextlib
import os
import time
from types import SimpleNamespace

import numpy as np

import spec
from reference import FLAG_ABSENT

_vc = spec.load_module(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                    "verify_commit.py"))
native_ready = _vc.native_ready
process_faults = _vc.process_faults
mask = _vc.mask
passes_clean = _vc.passes_clean
rejects = _vc.rejects

_inner = [None]     # what a flush calls: batch.verify_batch, or a stand-in
_annotate = [None]  # while tracing: annotate("bench:flush") around a flush
_flushes: list = []  # per verify_batch call since the last call() began
_lane: dict = {}    # the scheduler and its counters at the last call's start


def configure(traffic: dict) -> None:
    """What the mix states about the process (the memo), after asking the lane
    the one thing the cell cannot do without (`_lane_verifies_twice`): a
    program whose lane verifies a run twice ends here, with no result, before
    any data is made. The one before PR 29 does: its run of this cell would
    take ten minutes to read not correct (`program_warnings`,
    `flush_off_path`), on every seed."""
    if _lane_verifies_twice():
        raise SystemExit("blocksync_run: this program's scheduler lane gives up on a catch-up "
                         "ticket whose flush is in flight and verifies its rows a second time "
                         "inline, behind the same device: it cannot run hub-175.catchup soundly")
    _vc.configure(traffic)


def _lane_verifies_twice(rows: int = 512, flush_s: float = 0.2, tries: int = 5) -> bool:
    """Asked of the program itself, on no device: a catch-up ticket of a
    device's size whose flush outlasts the lane's `wait_timeout` (here 0.05 s
    against a stand-in flush of 0.2 s; in the cell 30 s against a cold compile
    or a recovery ladder). True where the rows were verified twice: by the
    lane's flush and again on the caller's thread.

    On a loaded host the dispatch thread may not have taken the ticket when
    the 0.05 s run out: the caller then takes it back off the queue and
    verifies it inline, ONCE, which is the lane's rule for a ticket still
    queued and says nothing of one in flight. That try is asked again (the
    count of flushes tells the two apart), so that the answer does not hang
    on how fast a thread wakes. The lane's own words about the probe's
    tickets (a warning where one is verified inline) are the probe's, not
    the run's, and stay out of the run's count of warnings."""
    from tendermint_tpu.config.config import SchedulerConfig
    from tendermint_tpu.crypto import batch, scheduler
    from tendermint_tpu.crypto.scheduler import VerifyScheduler

    flushes = []

    def flush(pubkeys, *a, **kw):
        flushes.append(len(pubkeys))
        time.sleep(flush_s)
        return np.ones(len(pubkeys), dtype=bool)

    for _ in range(tries):
        del flushes[:]
        sched = VerifyScheduler(SchedulerConfig(wait_timeout=0.05, catchup_max_wait=0.0),
                                backend="jax")
        program, batch.verify_batch = batch.verify_batch, flush
        scheduler.logger.disabled = True
        try:
            sched.verify_rows("catchup", [b"k"] * rows, [b"m"] * rows, [b"s"] * rows)
        finally:
            scheduler.logger.disabled = False
            batch.verify_batch = program
            sched.close()
        if len(flushes) > 1:
            return True
        if not sched.fallbacks:  # the timeout struck with the flush in flight, and the caller waited
            break
    return False


def _verify_batch(pubkeys, msgs, sigs, *a, **kw):
    """In batch.verify_batch's place from build() on: the flush itself, and
    beside it what a call that makes several flushes has to sum."""
    t0 = time.perf_counter()
    with _annotate[0]("bench:flush") if _annotate[0] else contextlib.nullcontext():
        got = _inner[0](pubkeys, msgs, sigs, *a, **kw)
    _flushes.append((len(got), int(np.count_nonzero(got)), (time.perf_counter() - t0) * 1e3))
    return got


class _Block:
    """`first` of a triple: a block's hash and height, as drawn. (`parts` and
    `second` are bare namespaces with `header` and `last_commit`.)"""

    def __init__(self, block_hash: bytes, height: int):
        self._hash = block_hash
        self.header = SimpleNamespace(height=height)

    def hash(self) -> bytes:
        return self._hash


class State:
    def __init__(self, config, vals, items):
        from tendermint_tpu.blocksync.reactor import BlocksyncReactor
        from tendermint_tpu.config.config import SchedulerConfig
        from tendermint_tpu.crypto import batch
        from tendermint_tpu.crypto.keys import Ed25519PubKey
        from tendermint_tpu.crypto.scheduler import VerifyScheduler
        from tendermint_tpu.types.basic import BlockID, BlockIDFlag, PartSetHeader
        from tendermint_tpu.types.block import Commit, CommitSig
        from tendermint_tpu.types.validator_set import Validator, ValidatorSet

        self.vals = ValidatorSet(
            [Validator(Ed25519PubKey(pk), p) for pk, p in zip(vals.pubkeys, vals.powers)]
        )
        if [v.pub_key.bytes() for v in self.vals.validators] != list(vals.pubkeys):
            raise SystemExit("blocksync_run: the program orders the validator set "
                             "otherwise than power, then address")
        if self.vals.total_voting_power() != vals.total_power:
            raise SystemExit("blocksync_run: the program's total power is not the stated one")
        addrs = [v.address for v in self.vals.validators]
        self.runs = []
        for item in items:
            run = []
            for c in item if isinstance(item, list) else [item]:
                header = PartSetHeader(c.parts_total, c.parts_hash)
                sigs = [
                    CommitSig.absent_sig() if f == FLAG_ABSENT
                    else CommitSig(BlockIDFlag.COMMIT, addrs[i], c.timestamps[i], c.sigs[i])
                    for i, f in enumerate(c.flags)
                ]
                commit = Commit(c.height, c.round, BlockID(c.block_hash, header), sigs)
                run.append((_Block(c.block_hash, c.height), SimpleNamespace(header=header),
                            SimpleNamespace(last_commit=commit), None))
            self.runs.append(run)
        # the lane as a node has it: config.py's defaults, no budget set by hand
        self.scheduler = VerifyScheduler(SchedulerConfig())
        atexit.register(self.scheduler.close)  # so that the process ends
        self.reactor = BlocksyncReactor(
            SimpleNamespace(validators=self.vals, chain_id=config["chain_id"]),
            None, None, active=False, scheduler=self.scheduler,
        )
        if _inner[0] is None:
            _inner[0] = batch.verify_batch
            batch.verify_batch = _verify_batch
        _lane.update(scheduler=self.scheduler)


def build(config, vals, items) -> State:
    return State(config, vals, items)


def call(state: State, i: int) -> str:
    """The timed call. Returns the verdict in the words of `tally_valid_power_run`."""
    del _flushes[:]
    sched = state.scheduler
    _lane.update(seq=sched.flush_seq, fallbacks=sched.fallbacks)
    bad = state.reactor._verify_run_batched(state.runs[i])
    return "accepted" if bad is None else f"refused at block #{bad}"


def flush_reading() -> dict:
    """What the last call's flushes say of themselves. One flush a call is
    that flush's record. Of several, `rows` and `rows_valid` are sums,
    `total_ms` is the last record's plus the wall around each earlier
    verify_batch (which also holds its `flush.record`, 0.1 ms), and every
    other key is the last flush's. `flushes`: verify_batch calls the call
    made; `lane_flushes`: flushes of the scheduler's dispatch thread during
    it; `lane_fallbacks`: tickets the caller verified inline."""
    r = _vc.flush_reading()
    sched = _lane["scheduler"]
    r.update(flushes=len(_flushes), lane_flushes=sched.flush_seq - _lane.get("seq", 0),
             lane_fallbacks=sched.fallbacks - _lane.get("fallbacks", 0),
             lane_closed=sched.closed)
    if len(_flushes) > 1:
        r["rows"] = sum(n for n, _, _ in _flushes)
        r["rows_valid"] = sum(v for _, v, _ in _flushes)
        r["total_ms"] = (r["total_ms"] or 0.0) + sum(ms for _, _, ms in _flushes[:-1])
    return r


def flush_fault(r: dict, expect: dict, rows: int) -> str | None:
    """None where every flush of the call ran where the configuration says,
    under the lane; else why not."""
    if r["lane_closed"] or r["lane_flushes"] < 1:
        return "the run did not go through the scheduler's lane"
    if r["lane_fallbacks"]:
        return "inline fallback of the lane"
    if r["flushes"] < 1:
        return "no verify_batch under the lane"
    return _vc.flush_fault(r, expect, rows)


@contextlib.contextmanager
def flush_spans(annotate):
    """While tracing: each verify_batch of the dispatch thread under a span of
    the benchmark's own."""
    _annotate[0] = annotate
    try:
        yield
    finally:
        _annotate[0] = None


def install_verifier(fn) -> None:
    """Puts `fn(pubkeys, msgs, sigs) -> bool mask` under the lane, in
    verify_batch's place: the controls built on the reference and the planted
    faults (tests, --control). The reactor, the scheduler and the tally stay
    the program's."""
    from tendermint_tpu.libs import trace

    def verify_batch(pubkeys, msgs, sigs, *a, **kw):
        t0 = time.perf_counter()
        got = np.asarray(fn(pubkeys, msgs, sigs), dtype=bool)
        # a flush record as the host path writes it, so that only what the
        # stand-in gets wrong comes out wrong
        trace.record_flush(backend="cpu", path="cpu", n=len(got),
                           total_s=time.perf_counter() - t0, n_valid=int(got.sum()))
        return got

    _inner[0] = verify_batch
    _vc._seams_off[0] = True  # rejects() asks the stand-in, not the program's check


# `unsent_third`: every combined check of crypto/batch gets a copy of row 0 in
# the place of each row past the first two thirds. On this driver's path that
# is the check under reactor -> lane -> verify_batch, so the timed programs run
# at the timed shapes and the run's last 21 blocks go unseen.
PROGRAM_CONTROLS = dict(_vc.PROGRAM_CONTROLS)
