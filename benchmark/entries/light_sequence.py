"""Entry driver `light_sequence`: one operation is one
light.Client.verify_light_block_at_height(h) in SEQUENTIAL mode, the call a
light proxy, a relayer or a state-syncing node waits on while it brings a
trusted header forward height by height. It builds what upstream's
BenchmarkSequence builds and nothing more: the program's LightBlocks from
each commit's `header` and `vals`, a MockProvider that serves them, and NO
scheduler installed (the standalone client: the run's rows go through a plain
FlushAccumulator).

One timed call: a fresh LightStore over a memory db holding the trusted light
block `item[0].prev`, a Client whose initialize() finds that root in the
store, the provider as primary and as the one witness (the source's), then
verify_light_block_at_height(the item's last height, now). A fresh store a
call, because on a lap over the ring a store that had seen the heights would
answer from itself and verify nothing. (Upstream's `b.N` loop has that flaw:
after its first iteration VerifyLightBlockAtHeight(1000) finds height 1000
in the client's store and returns it.)

What is the same question for every entry (the row mask through the public
call, the combined check asked directly, where a flush has to run, the
control `unsent_third`) is taken from entries/verify_commit.py, not copied:
this file loads a module of its own from it and names the streamed combined
check there, which the one-commit cells never reach."""

from __future__ import annotations

import asyncio
import contextlib
import os
import time
from types import SimpleNamespace

import numpy as np

import data
import reference
import spec

_vc = spec.load_module(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                    "verify_commit.py"))
# a flush over the planner's budget: the streamed combined check and nothing
# after it (mask, or None for "some row is bad")
_vc.COMBINED_CHECK["rlc-streamed"] = "_verify_batch_rlc_streamed"
native_ready = _vc.native_ready
process_faults = _vc.process_faults
mask = _vc.mask
passes_clean = _vc.passes_clean
rejects = _vc.rejects

_inner = [None]      # what a flush calls: batch.verify_batch, or a stand-in
_annotate = [None]   # while tracing: annotate("bench:flush") around a flush
_flushes: list = []  # per verify_batch call since the last call() began
_calls = [0]         # call()s this process has made
_first_rows = [0]    # the rows of a run's first header (fault `first_header_only`)
_root_set = [None]   # the trusted root's ValidatorSet (faults `roots_set`, `roots_powers`)


configure = _vc.configure  # what the mix states about the process (the memo)


def _verify_batch(pubkeys, msgs, sigs, *a, **kw):
    """In batch.verify_batch's place from build() on: the flush itself, and
    beside it what a call that makes several flushes has to sum."""
    t0 = time.perf_counter()
    with _annotate[0]("bench:flush") if _annotate[0] else contextlib.nullcontext():
        got = _inner[0](pubkeys, msgs, sigs, *a, **kw)
    _flushes.append((len(got), int(np.count_nonzero(got)), (time.perf_counter() - t0) * 1e3))
    return got


def validator_set(v):
    """The program's ValidatorSet of a generated set, held to its order and
    its total power."""
    from tendermint_tpu.crypto.keys import Ed25519PubKey
    from tendermint_tpu.types.validator_set import Validator, ValidatorSet

    vs = ValidatorSet([Validator(Ed25519PubKey(pk), p) for pk, p in zip(v.pubkeys, v.powers)])
    if [x.pub_key.bytes() for x in vs.validators] != list(v.pubkeys):
        raise SystemExit("light_sequence: the program orders a validator set otherwise than "
                         "power, then address")
    if vs.total_voting_power() != v.total_power:
        raise SystemExit("light_sequence: the program's total power is not the stated one")
    return vs


def light_block(c, vs):
    """The program's LightBlock of a chain's commit `c`: its header, the
    commit that signed it, and `vs`, the program's set of `c.vals`."""
    from tendermint_tpu.types.basic import BlockID, BlockIDFlag, PartSetHeader
    from tendermint_tpu.types.block import Commit, CommitSig, ConsensusVersion, Header
    from tendermint_tpu.types.light import LightBlock, SignedHeader

    h = c.header
    header = Header(
        version=ConsensusVersion(h["version_block"], h["version_app"]),
        chain_id=h["chain_id"], height=h["height"], time_ns=h["time_ns"],
        last_block_id=BlockID(h["last_block_hash"],
                              PartSetHeader(h["last_parts_total"], h["last_parts_hash"])),
        **{k: h[k] for k in reference.HEADER_FIELDS[8:]})
    sigs = [CommitSig.absent_sig() if f == reference.FLAG_ABSENT
            else CommitSig(BlockIDFlag.COMMIT, vs.validators[i].address, c.timestamps[i], c.sigs[i])
            for i, f in enumerate(c.flags)]
    commit = Commit(c.height, c.round,
                    BlockID(c.block_hash, PartSetHeader(c.parts_total, c.parts_hash)), sigs)
    return LightBlock(SignedHeader(header, commit), vs)


class State:
    def __init__(self, config, vals, items):
        from tendermint_tpu.crypto import batch

        self.chain_id = config["chain_id"]
        self.period_ns = int(config["trusting_period_s"]) * 10**9
        self.now_ns = data.BASE_TIME_NS + int(config["now_after_base_time_s"]) * 10**9
        self.loop = asyncio.new_event_loop()
        self._keep = items  # the ids below are of these objects
        sets: dict = {}     # id(ValidatorData) -> ValidatorSet
        blocks: dict = {}   # id(CommitData) -> LightBlock: items share commits and sets

        def set_of(v):
            if id(v) not in sets:
                sets[id(v)] = validator_set(v)
            return sets[id(v)]

        def block_of(c):
            if id(c) not in blocks:
                blocks[id(c)] = light_block(c, set_of(c.vals))
            return blocks[id(c)]

        self.items = []
        for item in items:
            commits = data.commits_of(item)
            root = block_of(commits[0].prev)
            served = {lb.height: lb for lb in [root] + [block_of(c) for c in commits]}
            self.items.append(SimpleNamespace(
                root=root, root_hash=commits[0].prev.block_hash, blocks=served,
                first_height=commits[0].height, last_height=commits[-1].height,
                first_rows=len(commits[0].present())))
        _root_set[0] = set_of(vals)
        if _inner[0] is None:
            _inner[0] = batch.verify_batch
            batch.verify_batch = _verify_batch


def build(config, vals, items) -> State:
    return State(config, vals, items)


def call(state: State, i: int) -> str:
    """The timed call. Returns the verdict in the words of `sequential_run`,
    from the height the client refused: the one above the store's last."""
    from tendermint_tpu.libs.kvdb import MemDB
    from tendermint_tpu.light import Client, LightStore, TrustOptions
    from tendermint_tpu.light.client import SEQUENTIAL
    from tendermint_tpu.light.provider import MockProvider
    from tendermint_tpu.light.verifier import LightError

    del _flushes[:]
    item = state.items[i]
    _first_rows[0] = item.first_rows
    store = LightStore(MemDB())
    store.save_light_block(item.root)
    provider = MockProvider(state.chain_id, item.blocks)
    client = Client(state.chain_id,
                    TrustOptions(state.period_ns, item.root.height, item.root_hash),
                    provider, [provider], store, verification_mode=SEQUENTIAL)

    async def go():
        await client.initialize(state.now_ns)
        await client.verify_light_block_at_height(item.last_height, state.now_ns)

    try:
        state.loop.run_until_complete(go())
    except LightError as e:
        k = store.heights()[-1] + 1 - item.first_height
        if "insufficient voting power" in str(e):
            verdict = f"not enough power at block #{k}"
        else:
            verdict = f"broken link at block #{k}"
    else:
        verdict = "accepted"
    _calls[0] += 1
    if _calls[0] == 1 and len(_flushes) != 1:
        # The process's first call, a warm-up call of set-up. A client that verifies a header a
        # flush (333 flushes of 100 rows, each under the device's floor and so on the host)
        # would read `flush_off_path` in every call of a run of minutes with no operation on
        # the device, which a traced run cannot reduce: the run ends here instead, with no
        # result, on what the entry has seen and not on a name of the program's.
        raise SystemExit(f"light_sequence: the first call made {len(_flushes)} flushes, not ONE "
                         "for all its rows: the program's light client does not verify a run "
                         "of headers in one flush, so light-seq-100.sequence cannot be measured "
                         "on it")
    return verdict


def flush_reading() -> dict:
    """What the last call's flushes say of themselves. One flush a call (the
    cell's) is that flush's record. Of several, `rows` and `rows_valid` are
    sums, `total_ms` is the last record's plus the wall around each earlier
    verify_batch, every other key is the last flush's. `flushes`: the
    verify_batch calls the call made."""
    r = _vc.flush_reading()
    r["flushes"] = len(_flushes)
    if len(_flushes) > 1:
        r["rows"] = sum(n for n, _, _ in _flushes)
        r["rows_valid"] = sum(v for _, v, _ in _flushes)
        r["total_ms"] = (r["total_ms"] or 0.0) + sum(ms for _, _, ms in _flushes[:-1])
    return r


def flush_fault(r: dict, expect: dict, rows: int) -> str | None:
    """None where the call made ONE flush, holding all its rows, where the
    configuration says; else why not."""
    if r["flushes"] != 1:
        return f"{r['flushes']} flushes in the call"
    return _vc.flush_fault(r, expect, rows)


@contextlib.contextmanager
def flush_spans(annotate):
    """While tracing: each verify_batch of a call under a span of the
    benchmark's own."""
    _annotate[0] = annotate
    try:
        yield
    finally:
        _annotate[0] = None


def install_verifier(fn) -> None:
    """Puts `fn(pubkeys, msgs, sigs) -> bool mask` under the client, in
    verify_batch's place: the controls built on the reference and the planted
    faults (tests, --control). Client, verifier and tally stay the program's."""
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


# -- the planted faults a chain cell can have, on the program's own path


def _gather_as(pick):
    """ValidatorSet.for_block_rows with one thing of the trusted root's in the
    place of the header's own: `pick(own set, root set, rows as gathered)`
    gives (pubkeys, powers) for the commit's for-block rows."""
    from tendermint_tpu.types.validator_set import ValidatorSet

    inner = ValidatorSet.for_block_rows

    def for_block_rows(self, commit, pubkeys, sigs, key_types):
        at = len(pubkeys)
        idxs, powers = inner(self, commit, pubkeys, sigs, key_types)
        pubkeys[at:], powers = pick(self, _root_set[0], idxs, pubkeys[at:], powers)
        return idxs, powers

    ValidatorSet.for_block_rows = for_block_rows


def roots_set() -> None:
    """Planted fault: every header's signatures are verified under the keys
    of the trusted root's set, seat by seat, as if the set never changed."""
    _gather_as(lambda own, root, idxs, keys, powers: (
        [root.validators[i].pub_key.bytes() for i in idxs], powers))


def roots_powers() -> None:
    """Planted fault: powers of the wrong height in the tally: a signer weighs
    what the trusted root's set gives its address, nothing where the root
    does not know it (the tally VerifyCommitLightTrusting makes, in
    VerifyCommitLight's place). With one key replaced a height, 34 heights
    above the root no more than 2/3 of the root's power is left."""
    def pick(own, root, idxs, keys, powers):
        known = (root.get_by_address(own.validators[i].address)[1] for i in idxs)
        return keys, [v.voting_power if v is not None else 0 for v in known]

    _gather_as(pick)


def links_unchecked() -> None:
    """Planted fault: signatures and tally alone; no header is held to what
    its predecessor committed to."""
    from tendermint_tpu.light import verifier

    verifier.check_adjacent = lambda *a, **kw: None


def _combined_checks_see(cut) -> None:
    """Every combined check of crypto/batch gets a copy of row 0 in the place
    of each row from `cut(rows)` on, so the timed programs run at the timed
    shapes and those rows go unseen (entries/verify_commit.py's
    `unsent_third`, with the cut a parameter)."""
    from tendermint_tpu.crypto import batch

    def wrap(inner):
        def combined(pubkeys, msgs, sigs, *a, **kw):
            at = cut(len(pubkeys))

            def seen(xs):
                return list(xs[:at]) + [xs[0]] * (len(xs) - at)

            return inner(seen(pubkeys), seen(msgs), seen(sigs), *a, **kw)

        return combined

    for attr in sorted(set(_vc.COMBINED_CHECK.values())):
        if hasattr(batch, attr):
            setattr(batch, attr, wrap(getattr(batch, attr)))


def first_header_only() -> None:
    """Planted fault: only a run's first header is verified, the rows of the
    others are taken as signed."""
    _combined_checks_see(lambda rows: _first_rows[0] or rows)


# `unsent_third`: the control on the program's own path, here the checks under
# client -> verify_adjacent_run -> accumulator -> verify_batch: the run's last
# 111 headers go unseen.
PROGRAM_CONTROLS = dict(_vc.PROGRAM_CONTROLS, roots_set=roots_set, roots_powers=roots_powers,
                        links_unchecked=links_unchecked, first_header_only=first_header_only)
