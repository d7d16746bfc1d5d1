"""ISSUE 18 — cross-flush verified-row memo safety tests.

The memo (crypto/batch.VerifiedRowMemo) caches digests of rows that
verified OK so a commit assembled from deferred-verified live votes does
not re-pay device/host verification for the same rows. The safety
contract pinned here:

  - only verdict-True rows are ever inserted; a flush that raises inserts
    NOTHING (never-cache-on-failure);
  - a tampered byte anywhere in (key_type, pubkey, msg, sig) produces a
    different digest: the tampered row misses, re-verifies, and fails —
    the memo can never turn a False verdict into a True one;
  - the LRU eviction bound holds under a 10k-row flood;
  - capacity 0 disables the memo entirely (the test-suite default via
    tests/conftest.py);
  - integration: a commit built from a deferred-verified VoteSet resolves
    through the memo with ZERO re-verified rows.

The suite-wide conftest fixture swaps in a disabled memo per test; tests
here enable one explicitly through configure_verified_memo.
"""

import dataclasses

import numpy as np
import pytest

from tendermint_tpu.crypto import batch
from tendermint_tpu.crypto.keys import gen_ed25519
from tendermint_tpu.libs import trace as _trace


def _memo_on(rows=4096):
    batch.configure_verified_memo(rows)
    return batch._MEMO


def _signed(n, seed=b"\x31"):
    priv = gen_ed25519(seed * 32 if len(seed) == 1 else seed)
    pk = priv.pub_key().bytes()
    msgs = [b"memo-%05d" % i for i in range(n)]
    return [pk] * n, msgs, [priv.sign(m) for m in msgs]


def _last_flush():
    return _trace.verify_stats()["last_flush"]


# ---------------------------------------------------------------------------
# hit/miss semantics


def test_full_hit_short_circuits():
    memo = _memo_on()
    pks, msgs, sigs = _signed(60)
    assert batch.verify_batch(pks, msgs, sigs).all()
    assert len(memo) == 60
    assert memo.stats()["insertions"] == 60

    mask = batch.verify_batch(pks, msgs, sigs)
    assert mask.all() and len(mask) == 60
    st = memo.stats()
    assert st["hits"] == 60
    lf = _last_flush()
    assert lf["backend"] == "memo" and lf["path"] == "memo"
    assert lf["memo_hits"] == 60


def test_partial_hit_verifies_residue_only():
    memo = _memo_on()
    pks, msgs, sigs = _signed(60)
    assert batch.verify_batch(pks[:40], msgs[:40], sigs[:40]).all()
    hits0 = memo.stats()["hits"]

    mask = batch.verify_batch(pks, msgs, sigs)
    assert mask.all() and len(mask) == 60
    assert memo.stats()["hits"] == hits0 + 40
    # the residue flush (recorded after the memo flush) carried ONLY the
    # 20 unseen rows — and re-inserted them for next time
    assert _last_flush()["n"] == 20
    assert len(memo) == 60


def test_tampered_row_never_hits_memo():
    memo = _memo_on()
    pks, msgs, sigs = _signed(30, b"\x32")
    assert batch.verify_batch(pks, msgs, sigs).all()

    msgs = list(msgs)
    msgs[7] = msgs[7][:-1] + bytes([msgs[7][-1] ^ 1])
    mask = batch.verify_batch(pks, msgs, sigs)
    assert not mask[7]
    assert mask.sum() == 29

    # the tampered digest is not in the memo — and never got inserted
    d = memo.digest_rows([pks[7]], [msgs[7]], [sigs[7]])[0]
    assert d not in memo
    assert memo.stats()["insertions"] == 30
    # repeat: the verdict stays False (the memo cannot launder a failure)
    assert not batch.verify_batch(pks, msgs, sigs)[7]


def test_bad_rows_never_cached():
    memo = _memo_on()
    pks, msgs, sigs = _signed(20, b"\x33")
    sigs = list(sigs)
    sigs[4] = sigs[4][:32] + b"\xff" * 32  # non-canonical s: verdict False
    mask = batch.verify_batch(pks, msgs, sigs)
    assert not mask[4] and mask.sum() == 19
    assert len(memo) == 19
    d = memo.digest_rows([pks[4]], [msgs[4]], [sigs[4]])[0]
    assert d not in memo


def test_failed_flush_caches_nothing(monkeypatch):
    memo = _memo_on()
    pks, msgs, sigs = _signed(16, b"\x34")

    def boom(*a, **kw):
        raise RuntimeError("injected flush failure")

    monkeypatch.setattr(batch, "_verify_batch_routed", boom)
    with pytest.raises(RuntimeError, match="injected flush failure"):
        batch.verify_batch(pks, msgs, sigs)
    assert len(memo) == 0
    assert memo.stats()["insertions"] == 0


# ---------------------------------------------------------------------------
# bounds and disablement


def test_eviction_bound_under_10k_flood():
    memo = batch.VerifiedRowMemo(1000)
    rng = np.random.default_rng(7)
    digests = [rng.bytes(32) for _ in range(10_000)]
    ones = np.ones(1000, dtype=bool)
    for lo in range(0, 10_000, 1000):
        memo.insert(digests[lo : lo + 1000], ones)
    st = memo.stats()
    assert len(memo) == 1000
    assert st["insertions"] == 10_000
    assert st["evictions"] == 9_000
    # LRU: the newest 1000 survive, the oldest 9000 are gone
    assert memo.lookup(digests[-1000:]).all()
    assert not memo.lookup(digests[:1000]).any()


def test_capacity_zero_disables():
    memo = _memo_on(0)
    pks, msgs, sigs = _signed(12, b"\x35")
    assert batch.verify_batch(pks, msgs, sigs).all()
    assert batch.verify_batch(pks, msgs, sigs).all()  # re-verified, no memo
    st = memo.stats()
    assert st["capacity"] == 0
    assert st["hits"] == 0 and st["insertions"] == 0
    assert len(memo) == 0


def test_digest_framing_is_unambiguous():
    """pk||msg boundary shifts must produce different digests (the frame
    prevents "ab"+"c" aliasing "a"+"bc")."""
    memo = batch.VerifiedRowMemo(16)
    d1 = memo.digest_rows([b"ab"], [b"c"], [b"sig"])[0]
    d2 = memo.digest_rows([b"a"], [b"bc"], [b"sig"])[0]
    assert d1 != d2


# ---------------------------------------------------------------------------
# the native digest pass (native/batchhost.c tm_memo_digest_batch) against
# the hashlib loop, byte for byte

# raw SHA-256 padding edges, and those of an ed25519 row's frame (120 bytes
# besides the msg: 55/56 mod 64 at msg 63/64, 127/128; a whole block at 8)
_MSG_LENS = [0, 1, 8, 55, 56, 63, 64, 119, 120, 127, 128, 1024]


def _needs_native():
    from tendermint_tpu import native

    if not native.available():
        pytest.skip("native batchhost unavailable (no compiler?)")


def _rows(n, kinds, form, seed=11):
    rng = np.random.default_rng([seed, n])
    pks = [rng.bytes(32) for _ in range(n)]
    msgs = [rng.bytes(_MSG_LENS[i % len(_MSG_LENS)]) for i in range(n)]
    sigs = [rng.bytes(64) for _ in range(n)]
    key_types = None
    if kinds == "ed25519":
        key_types = ["ed25519"] * n
    elif kinds == "mixed":
        key_types = ["ed25519" if i % 3 else "sr25519" for i in range(n)]
        if n:  # digest_rows frames any string, and any key width
            key_types[0], pks[0] = "secp256k1", rng.bytes(33)
    cast = {"bytes": bytes, "bytearray": bytearray, "memoryview": memoryview}[form]
    pks, msgs, sigs = ([cast(r) for r in col] for col in (pks, msgs, sigs))
    if form == "memoryview" and n > 1:  # a view whose len() counts 2-byte items
        msgs[1] = memoryview(rng.bytes(56)).cast("H")
    return pks, msgs, sigs, key_types


@pytest.mark.parametrize("form", ["bytes", "bytearray", "memoryview"])
@pytest.mark.parametrize("kinds", [None, "ed25519", "mixed"])
@pytest.mark.parametrize("n", [0, 1, 511, 512, 10_000])
def test_native_digests_equal_the_hashlib_loop(n, kinds, form):
    """One native pass gives every row the digest the hashlib loop gives it,
    in both verify modes, for rows of any width, key type and buffer kind,
    under the threaded split (n >= 512) and the single thread."""
    from tendermint_tpu.crypto.keys import cofactorless_mode, set_verify_mode

    _needs_native()
    memo = batch.VerifiedRowMemo(16)
    pks, msgs, sigs, key_types = _rows(n, kinds, form)
    before = "cofactorless" if cofactorless_mode() else "cofactored"
    seen = set()
    try:
        for mode in ("cofactored", "cofactorless"):
            set_verify_mode(mode)
            got = memo.digest_rows(pks, msgs, sigs, key_types)
            assert got == memo._digest_rows_py(pks, msgs, sigs, key_types)
            assert all(type(d) is bytes and len(d) == 32 for d in got)
            seen.update(got)
    finally:
        set_verify_mode(before)
    assert len(seen) == 2 * n  # the mode byte parts the two modes' digests


@pytest.mark.parametrize("writer", ["python", "native"])
def test_a_memo_written_by_either_digest_path_is_read_by_the_other(writer, monkeypatch):
    """A flush whose digests the hashlib loop made fills the memo the native
    pass reads, and the other way round: the same rows hit, all of them."""
    from tendermint_tpu import native

    _needs_native()
    memo = _memo_on()
    pks, msgs, sigs = _signed(40, b"\x3a")
    with monkeypatch.context() as m:
        if writer == "python":
            m.setattr(native, "available", lambda: False)
        assert batch.verify_batch(pks, msgs, sigs).all()
    assert len(memo) == 40
    if writer == "native":
        monkeypatch.setattr(native, "available", lambda: False)
    assert batch.verify_batch(pks, msgs, sigs).all()
    lf = _last_flush()
    assert lf["path"] == "memo" and lf["memo_hits"] == 40


def test_scheduler_stats_carry_memo_block():
    _memo_on(128)
    pks, msgs, sigs = _signed(8, b"\x36")
    assert batch.verify_batch(pks, msgs, sigs).all()
    assert batch.verified_memo_stats()["insertions"] == 8


# ---------------------------------------------------------------------------
# integration: deferred-verified votes -> commit verify through the memo


def test_deferred_commit_verifies_through_memo():
    """The consensus shape the memo exists for: precommits batch-verified
    by the deferred VoteSet flush populate the memo; the commit assembled
    from those SAME votes then verifies with zero re-verified rows."""
    from tendermint_tpu.types.block import BlockID, PartSetHeader
    from tendermint_tpu.types.validator_set import Validator, ValidatorSet
    from tendermint_tpu.types.vote import Vote
    from tendermint_tpu.types.vote_set import VoteSet

    memo = _memo_on()
    rng = np.random.default_rng(42)
    privs = [
        gen_ed25519(rng.integers(0, 256, 32, dtype=np.uint8).tobytes())
        for _ in range(48)
    ]
    vals = ValidatorSet([Validator(p.pub_key(), 10) for p in privs])
    by_addr = {p.pub_key().address(): p for p in privs}
    sorted_privs = [by_addr[v.address] for v in vals.validators]
    bid = BlockID(b"\x01" * 32, PartSetHeader(1, b"\x02" * 32))

    vs = VoteSet("memo-chain", 1, 0, 2, vals, defer_verification=True)
    for i, (val, priv) in enumerate(zip(vals.validators, sorted_privs)):
        v = Vote(type=2, height=1, round=0, block_id=bid, timestamp_ns=0,
                 validator_address=val.address, validator_index=i)
        v = dataclasses.replace(v, signature=priv.sign(v.sign_bytes("memo-chain")))
        assert vs.add_vote(v) == "pending"
    committed, failed = vs.flush()
    assert len(committed) == 48 and not failed
    assert len(memo) == 48  # the deferred flush populated the memo

    commit = vs.make_commit()
    misses0 = memo.stats()["misses"]
    vals.verify_commit("memo-chain", bid, 1, commit)  # must not raise

    st = memo.stats()
    assert st["misses"] == misses0  # ZERO re-verified rows
    assert st["hits"] == 48        # the commit's full memo hit
    lf = _last_flush()
    assert lf["backend"] == "memo" and lf["memo_hits"] == 48


# ---------------------------------------------------------------------------
# memo x quarantine (ISSUE 20): the adversarial flush defense must not
# change the memo's safety contract, and the memo must not blind the
# suspicion scorer.


@pytest.fixture
def scratch_scorer():
    from tendermint_tpu.crypto import provenance as prov

    scorer = prov.SuspicionScorer(fail_quarantine=3, parole_clean=30)
    prev = prov.set_default(scorer)
    yield scorer
    prov.set_default(prev)


def test_quarantined_clean_rows_may_enter_memo(scratch_scorer):
    """A quarantined source's rows that verify CLEAN are memo-eligible:
    quarantine is a scheduling demotion (slow lane), not a verdict — the
    memo caches verdicts, and a clean verdict is a clean verdict."""
    memo = _memo_on()
    pks, msgs, sigs = _signed(20, b"\x37")
    srcs = ["peer:mallory"] * 20

    # quarantine the source with a poisoned flush first
    bad = list(sigs)
    for i in (0, 1, 2):
        bad[i] = bad[i][:32] + (1).to_bytes(32, "little")
    mask = batch.verify_batch(pks, msgs, bad, sources=srcs)
    assert mask.sum() == 17
    assert scratch_scorer.is_quarantined("peer:mallory")

    # the 17 clean rows were memoized; the 3 failed rows were NOT
    assert len(memo) == 17
    for i in (0, 1, 2):
        d = memo.digest_rows([pks[i]], [msgs[i]], [bad[i]])[0]
        assert d not in memo

    # a fully-clean flush from the still-quarantined source memoizes too
    assert batch.verify_batch(pks, msgs, sigs, sources=srcs).all()
    assert len(memo) == 20


def test_memo_hits_count_toward_parole(scratch_scorer):
    """Memo-answered rows verified clean in an earlier flush still feed
    the scorer: a quarantined source whose repeats resolve through the
    memo must be able to earn parole, not be starved of clean credit."""
    _memo_on()
    pks, msgs, sigs = _signed(16, b"\x38")
    srcs = ["peer:flaky"] * 16

    bad = list(sigs)
    for i in (0, 1, 2):
        bad[i] = bad[i][:32] + (1).to_bytes(32, "little")
    batch.verify_batch(pks, msgs, bad, sources=srcs)
    assert scratch_scorer.is_quarantined("peer:flaky")

    # first clean flush verifies for real (16 clean), the second resolves
    # entirely through the memo — BOTH must advance the clean streak
    assert batch.verify_batch(pks, msgs, sigs, sources=srcs).all()
    assert scratch_scorer.is_quarantined("peer:flaky")  # 16 < 30
    assert batch.verify_batch(pks, msgs, sigs, sources=srcs).all()
    assert _last_flush()["backend"] == "memo"
    assert not scratch_scorer.is_quarantined("peer:flaky")  # 32 >= 30: parole
    assert scratch_scorer.stats()["paroles"] == 1


def test_memo_never_launders_a_poisoned_row_across_sources(scratch_scorer):
    """A poisoned row replayed by a DIFFERENT source still fails: the
    memo keys on row bytes, failed rows are never inserted, so a replay
    re-verifies, fails again, and indicts the replaying source too."""
    memo = _memo_on()
    pks, msgs, sigs = _signed(12, b"\x39")
    bad = list(sigs)
    bad[5] = bad[5][:32] + (1).to_bytes(32, "little")

    mask = batch.verify_batch(pks, msgs, bad, sources=["peer:a"] * 12)
    assert not mask[5] and len(memo) == 11

    # peer:b replays JUST the poisoned row, over and over: every replay
    # misses the memo, re-verifies, fails — and accumulates suspicion
    # (clean-row decay never sees a clean row to forgive with)
    for _ in range(3):
        mask = batch.verify_batch(
            [pks[5]], [msgs[5]], [bad[5]], sources=["peer:b"]
        )
        assert not mask[0]
    assert scratch_scorer.is_quarantined("peer:b")
