"""Tier-1 "no-redundant-work" guard for the live vote path.

Counter-based, NOT wall-clock — stable on shared/loaded hosts. The budgets
pin the per-vote work the hot loop is allowed to do after ISSUE 3:

- protowire encode COMPUTES (types/vote.py ENCODE_COMPUTES): at most one
  per vote across the whole ingest path (WAL frame + gossip re-sends);
- canonical sign-bytes COMPUTES (SIGN_BYTES_COMPUTES): one per vote on the
  serial-verify path, ZERO per peer vote on the deferred path (the flush
  uses the batched builder);
- fsyncs (consensus/wal.py WAL.fsync_count): group commit means one per
  queue drain + one per self-generated message, never one per peer vote.

If a future change bypasses the memo or the group-commit boundary, these
fail with a counter diff instead of a flaky timing assertion.
"""

import asyncio
import dataclasses
import time

import numpy as np
import pytest

from tendermint_tpu.crypto import gen_ed25519
from tendermint_tpu.types import vote as vote_mod
from tendermint_tpu.types.basic import BlockID, PartSetHeader, SignedMsgType
from tendermint_tpu.types.validator_set import Validator, ValidatorSet
from tendermint_tpu.types.vote import Vote
from tendermint_tpu.types.vote_set import VoteSet

BID = BlockID(b"\x01" * 32, PartSetHeader(1, b"\x02" * 32))


def make_valset(n):
    rng = np.random.default_rng(11)
    privs = [gen_ed25519(rng.integers(0, 256, 32, dtype=np.uint8).tobytes()) for _ in range(n)]
    vals = ValidatorSet([Validator(p.pub_key(), 10) for p in privs])
    by_addr = {p.pub_key().address(): p for p in privs}
    return vals, [by_addr[v.address] for v in vals.validators]


def signed_votes(vals, privs, chain_id="guard", height=1):
    out = []
    for i, (val, priv) in enumerate(zip(vals.validators, privs)):
        v = Vote(type=SignedMsgType.PRECOMMIT, height=height, round=0, block_id=BID,
                 timestamp_ns=0, validator_address=val.address, validator_index=i)
        out.append(dataclasses.replace(v, signature=priv.sign(v.sign_bytes(chain_id))))
    return out


def test_deferred_flush_does_zero_per_vote_encodes():
    """The deferred path's budget: ZERO per-vote sign-bytes/encode computes —
    sign-bytes come from the batched builder, nothing serializes the Vote."""
    n = 64
    vals, privs = make_valset(n)
    votes = signed_votes(vals, privs)
    vs = VoteSet("guard", 1, 0, SignedMsgType.PRECOMMIT, vals, defer_verification=True)
    enc0, sb0 = vote_mod.ENCODE_COMPUTES, vote_mod.SIGN_BYTES_COMPUTES
    for v in votes:
        vs.add_vote(v)
    committed, failed = vs.flush()
    assert len(committed) == n and not failed
    assert vote_mod.ENCODE_COMPUTES - enc0 == 0
    assert vote_mod.SIGN_BYTES_COMPUTES - sb0 == 0


def test_serial_add_vote_is_one_sign_bytes_per_vote():
    n = 32
    vals, privs = make_valset(n)
    votes = signed_votes(vals, privs)
    vs = VoteSet("guard", 1, 0, SignedMsgType.PRECOMMIT, vals)
    sb0 = vote_mod.SIGN_BYTES_COMPUTES
    for v in votes:
        vs.add_vote(v)
    assert vote_mod.SIGN_BYTES_COMPUTES - sb0 == n


def test_wal_fsync_budget_is_per_drain_not_per_vote(tmp_path):
    from tendermint_tpu.consensus.messages import VoteMessage
    from tendermint_tpu.consensus.wal import WAL, MsgInfo

    n = 256
    vals, privs = make_valset(8)
    votes = signed_votes(vals, privs) * (n // 8)
    wal = WAL(str(tmp_path / "wal"), group_commit=True, group_commit_max_latency=60.0)
    base = wal.fsync_count
    enc0 = vote_mod.ENCODE_COMPUTES
    for v in votes:
        wal.write(MsgInfo(VoteMessage(v), "peer"))
    wal.flush_buffered()
    # young data: ONE buffered write, ZERO fsyncs for the whole drain
    assert wal.fsync_count - base == 0
    wal._dirty_since = time.perf_counter() - 999.0  # aged past the bound
    wal.flush_buffered()
    assert wal.fsync_count - base == 1  # ONE fsync once the bound is due
    # 8 distinct Vote objects -> 8 encodes, not 256
    assert vote_mod.ENCODE_COMPUTES - enc0 == 8
    wal.close()


@pytest.mark.parametrize("defer", [True, False])
def test_live_height_budgets(tmp_path, defer):
    """End-to-end: one real ConsensusState driven through a full height by
    stub validators (the bench_live_consensus shape, shrunk). Budgets per
    ingested vote: encodes <= 1 + slack, fsyncs bounded by drains+internal
    messages, deferred sign-bytes bounded by our OWN votes only."""
    from tendermint_tpu.abci.kvstore import KVStoreApplication
    from tendermint_tpu.config.config import test_config
    from tendermint_tpu.consensus.cs_state import ConsensusState
    from tendermint_tpu.consensus.messages import (
        BlockPartMessage,
        ProposalMessage,
        VoteMessage,
    )
    from tendermint_tpu.consensus.replay import Handshaker
    from tendermint_tpu.consensus.wal import WAL
    from tendermint_tpu.evidence.pool import EvidencePool
    from tendermint_tpu.libs.kvdb import MemDB
    from tendermint_tpu.mempool.mempool import Mempool
    from tendermint_tpu.privval.file_pv import FilePV
    from tendermint_tpu.proxy.multi import AppConns, local_client_creator
    from tendermint_tpu.state.execution import BlockExecutor
    from tendermint_tpu.state.sm_state import state_from_genesis
    from tendermint_tpu.state.store import StateStore
    from tendermint_tpu.store.blockstore import BlockStore
    from tendermint_tpu.types.event_bus import EventBus
    from tendermint_tpu.types.genesis import GenesisDoc, GenesisValidator
    from tendermint_tpu.types.part_set import PartSet
    from tendermint_tpu.types.proposal import Proposal

    n_vals = 16
    chain = "guard-live"
    privs = [FilePV(gen_ed25519(bytes([60 + i]) * 32)) for i in range(n_vals)]
    gen = GenesisDoc(chain_id=chain,
                     validators=[GenesisValidator(p.get_pub_key(), 10) for p in privs])
    gen.validate_and_complete()
    state = state_from_genesis(gen)
    by_addr = {p.get_pub_key().address(): p for p in privs}
    sorted_privs = [by_addr[v.address] for v in state.validators.validators]
    proxy = AppConns(local_client_creator(KVStoreApplication()))
    block_store = BlockStore(MemDB())
    state_store = StateStore(MemDB())
    state_store.save(state)
    event_bus = EventBus()
    mempool = Mempool(proxy.mempool)
    evpool = EvidencePool(MemDB(), state_store, block_store)
    evpool.set_state(state)
    block_exec = BlockExecutor(state_store, proxy.consensus, mempool, evpool,
                               event_bus=event_bus, block_store=block_store)
    cfg = test_config().consensus
    cfg.defer_vote_verification = defer
    state = Handshaker(state_store, state, block_store, gen, event_bus).handshake(proxy)
    wal = WAL(str(tmp_path / "wal"), group_commit=cfg.wal_group_commit,
              group_commit_max_latency=cfg.wal_group_commit_max_latency)
    # a LIVE tx lifecycle tracker rides along (ISSUE 10): with tracing
    # enabled it must not move any vote-path counter budget below — the
    # tracker never touches votes, and this pins that
    from tendermint_tpu.libs.txtrace import TxTracker

    cs = ConsensusState(cfg, state, block_exec, block_store, mempool, evpool,
                        wal, event_bus=event_bus, priv_validator=sorted_privs[0],
                        tx_tracker=TxTracker())

    async def run():
        await cs.start()
        me = sorted_privs[0].get_pub_key().address()
        try:
            while cs.rs.height != 1:
                await asyncio.sleep(0.005)
            rs = cs.rs
            prop_addr = rs.validators.get_proposer().address
            prop_idx = next(i for i, v in enumerate(rs.validators.validators)
                            if v.address == prop_addr)
            if prop_addr != me:
                from tendermint_tpu.types.block import Commit as CommitT

                block = block_exec.create_proposal_block(
                    1, cs.state, CommitT(0, 0, BlockID(), ()), prop_addr, time.time_ns()
                )
                parts = PartSet.from_data(block.encode())
                bid = BlockID(block.hash(), parts.header)
                prop = Proposal(height=1, round=0, pol_round=-1, block_id=bid,
                                timestamp_ns=time.time_ns())
                prop = sorted_privs[prop_idx].sign_proposal(chain, prop)
            else:
                while cs.rs.proposal_block is None or cs.rs.proposal_block_parts is None:
                    await asyncio.sleep(0.005)
                parts = cs.rs.proposal_block_parts
                bid = BlockID(cs.rs.proposal_block.hash(), parts.header)
                prop = None

            def sign(vtype):
                out = []
                for i, p in enumerate(sorted_privs[1:], start=1):
                    v = Vote(type=vtype, height=1, round=0, block_id=bid,
                             timestamp_ns=time.time_ns(),
                             validator_address=p.get_pub_key().address(),
                             validator_index=i)
                    out.append(dataclasses.replace(
                        v, signature=p.priv_key.sign(v.sign_bytes(chain))))
                return out

            prevotes, precommits = sign(SignedMsgType.PREVOTE), sign(SignedMsgType.PRECOMMIT)

            enc0, sb0 = vote_mod.ENCODE_COMPUTES, vote_mod.SIGN_BYTES_COMPUTES
            fs0, wr0 = wal.fsync_count, wal.write_calls
            if prop is not None:
                await cs.add_peer_message(ProposalMessage(prop), "peer")
                for i in range(parts.total):
                    await cs.add_peer_message(BlockPartMessage(1, 0, parts.get_part(i)), "peer")
            for v in prevotes + precommits:
                await cs.add_peer_message(VoteMessage(v), f"peer-{v.validator_index}")
            deadline = time.monotonic() + 30
            while cs.rs.height == 1:
                assert time.monotonic() < deadline, "height 1 did not commit"
                await asyncio.sleep(0.002)
            return (
                len(prevotes) + len(precommits),
                vote_mod.ENCODE_COMPUTES - enc0,
                vote_mod.SIGN_BYTES_COMPUTES - sb0,
                wal.fsync_count - fs0,
                wal.write_calls - wr0,
            )
        finally:
            await cs.stop()

    n_votes, d_enc, d_sb, d_fsync, d_writes = asyncio.run(run())
    assert n_votes == 2 * (n_vals - 1)
    # our node signs up to 2 internal votes; each vote (peer or own) may be
    # protowire-encoded AT MOST once end-to-end
    assert d_enc <= n_votes + 4, f"encode computes {d_enc} for {n_votes} votes"
    if defer:
        # peer votes verify via the batched sign-bytes builder: per-vote
        # canonical computes must NOT scale with the vote count
        assert d_sb <= 8, f"deferred sign-bytes computes {d_sb}"
    else:
        # serial: one verify (and thus one compute) per peer vote + our own
        assert d_sb <= 2 * n_votes, f"serial sign-bytes computes {d_sb}"
    # group commit: fsyncs scale with drains + self-generated messages, not
    # with peer votes (a per-vote-fsync regression would be ~n_votes here)
    assert d_fsync <= n_votes // 2, f"{d_fsync} fsyncs for {n_votes} votes ({d_writes} writes)"


# ---------------------------------------------------------------------------
# A commit's sign bytes (ISSUE 27): one native pass a call, every call, and
# nothing remembered between calls.

from test_prep_pipeline import needs_native, prep_cfg, small_rlc  # noqa: E402,F401
from test_trace import CHAIN, _device_route, _signed_commit  # noqa: E402


@needs_native
def test_commit_sign_bytes_native_every_call_and_nothing_kept(small_rlc, prep_cfg, monkeypatch):
    """Two verify_commit calls on the SAME Commit object (host twin of the
    device path): each builds all n rows natively (a `commit.sign_bytes`
    span with builder "native", one native call, zero per-row timestamp
    encodes), and neither the commit, its CommitSigs nor the validator set
    carries anything afterwards that was not there before: the benchmark's
    ring re-presents 4 commits, and a builder that remembered them would
    answer the second lap from memory."""
    from tendermint_tpu import native
    from tendermint_tpu.libs import protowire as pw
    from tendermint_tpu.libs import trace
    from tendermint_tpu.types import canonical

    n = canonical.NATIVE_MIN_ROWS + 4
    _device_route(monkeypatch)
    vals, bid, commit = _signed_commit(n)
    held = [dict(vars(commit)), [dict(vars(cs)) for cs in commit.signatures], set(vars(vals))]
    t = trace.Tracer(ring_size=256)
    monkeypatch.setattr(trace, "tracer", t)
    ts_encodes, native_rows = [], []
    real_ts, real_native = pw.encode_timestamp, native.vote_sign_bytes
    monkeypatch.setattr(pw, "encode_timestamp", lambda *a: ts_encodes.append(a) or real_ts(*a))
    monkeypatch.setattr(
        native, "vote_sign_bytes", lambda *a: native_rows.append(len(a[3])) or real_native(*a))
    for _ in range(2):
        t.clear()
        del ts_encodes[:], native_rows[:]
        vals.verify_commit(CHAIN, bid, commit.height, commit)
        [sp] = [e for e in t.dump() if e["name"] == "commit.sign_bytes"]
        assert sp["attrs"]["builder"] == "native" and sp["attrs"]["rows"] == n
        assert sp["attrs"]["bytes"] > 64 * n
        assert native_rows == [n]
        assert ts_encodes == []
    assert dict(vars(commit)) == held[0]
    assert [dict(vars(cs)) for cs in commit.signatures] == held[1]
    assert set(vars(vals)) == held[2]
    # and the builder's module holds no container a cache could live in
    assert not [k for k, v in vars(canonical).items()
                if isinstance(v, (dict, list, set)) and not k.startswith("__")]
