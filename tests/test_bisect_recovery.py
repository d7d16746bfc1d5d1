"""Bisection recovery (ISSUE 20) — log-cost exact-mask recovery after a
failed combined check.

When the RLC combined check fails, the old recovery was one monolithic
per-signature flush over ALL n rows; the bisection ladder
(crypto/batch.py _bisect_recover / _bisect_recover_host) instead splits
the failed range at the largest power of two below its size, re-checks
halves with combined sub-checks over the SAME warm pow2 lane buckets,
and runs the per-sig kernel only at small leaves. These tests pin the
CONTRACT with the device kernels replaced by ed25519_ref host twins
(tests/test_flush_planner.py pattern — tier-1 pays no XLA compile):

- one bad row over C = ceil(n/leaf) chunks recovers in at most
  2*ceil(log2 C)+1 device flushes, counted TWO ways: the recovery
  ledger (LAST_FLUSH_DETAIL / trace counters) and an independent
  kernel-submission witness wrapped around the host twins;
- the recovered mask is byte-identical across every arm: single-chip
  bisect, streamed planner recovery, sharded-streamed recovery (fake
  mesh), host-RLC bisect, and the naive TMTPU_BISECT=0 fallback;
- the host arm (_bisect_recover_host) keeps the same log-cost bound;
- a dense flood trips the adaptive bail (batch._BISECT_MAX_BAD) and the
  mask stays exact;
- TMTPU_BISECT=0 restores the straight-to-per-sig arm (one recovery
  flush, identical mask).
"""

import math
import os

import numpy as np
import pytest

os.environ.setdefault("TMTPU_CRYPTO_BACKEND", "cpu")

from tendermint_tpu.crypto import batch
from tendermint_tpu.libs import trace as _trace
from tests.test_flush_planner import (
    _fake_mesh_env,
    _install_host_twins,
    _signed_rows,
)


@pytest.fixture
def bisect_env(monkeypatch):
    """Small-geometry bisection: RLC floor 8, leaf 8, planner out of the
    way, verified-row memo off (a memo hit would skip the flush whose
    count this file pins)."""
    monkeypatch.setattr(batch, "RLC_MIN", 8)
    monkeypatch.setattr(batch, "_BISECT_LEAF", 8)
    prev = batch.planner_budget()
    batch.configure_planner(max_flush_lanes=1 << 16)
    batch.configure_verified_memo(0)
    yield
    batch.configure_planner(max_flush_lanes=prev)
    batch.configure_verified_memo(batch._memo_env_rows())


class _FlushWitness:
    """Independent device-flush counter: wraps the host-twin kernel entry
    points AFTER _install_host_twins, so the recovery ledger is checked
    against actual kernel submissions, not its own bookkeeping."""

    def __init__(self, monkeypatch):
        from tendermint_tpu.ops import ed25519_jax, msm_jax

        _install_host_twins(monkeypatch)
        self.combined = 0
        self.persig = 0
        real_full = msm_jax.rlc_check_submit
        real_persig = ed25519_jax.verify_prepared

        def counting_full(*a, **k):
            self.combined += 1
            return real_full(*a, **k)

        def counting_persig(*a, **k):
            self.persig += 1
            return real_persig(*a, **k)

        monkeypatch.setattr(msm_jax, "rlc_check_submit", counting_full)
        monkeypatch.setattr(ed25519_jax, "verify_prepared", counting_persig)


def _flip(sigs, i):
    """Valid encodings, wrong signature: only the curve check fails —
    precheck passes, so the row survives to the combined check (the
    poisoning shape; docs/ROBUSTNESS.md)."""
    sigs[i] = sigs[i][:32] + (1).to_bytes(32, "little")


# ---------------------------------------------------------------------------
# The flush bound.


@pytest.mark.parametrize("bad_row", [0, 27, 63], ids=["head", "mid", "tail"])
def test_one_bad_row_flush_bound_and_exact_mask(
    bisect_env, monkeypatch, bad_row
):
    """One poisoned row in 64 (8 chunks of leaf=8) recovers in at most
    2*ceil(log2 8)+1 = 7 device flushes — pinned by the recovery ledger,
    the trace counters AND the independent submission witness — and the
    mask is byte-identical to the CPU reference."""
    witness = _FlushWitness(monkeypatch)
    pks, msgs, sigs = _signed_rows(64)
    sigs = list(sigs)
    _flip(sigs, bad_row)

    counters0 = _trace.verify_stats()["counters"]["recovery_flushes"]
    mask = batch.verify_batch(pks, msgs, sigs, backend="jax")
    recovery = batch.LAST_FLUSH_DETAIL.get("recovery_flushes", 0)

    assert not mask[bad_row] and mask.sum() == 63
    cpu = batch.verify_batch_cpu(pks, msgs, sigs)
    assert mask.tobytes() == cpu.tobytes()

    chunks = math.ceil(64 / 8)
    bound = 2 * math.ceil(math.log2(chunks)) + 1
    assert 1 <= recovery <= bound
    assert batch.LAST_JAX_PATH[0] == "rlc-bisect"
    # the witness: total kernel submissions = 1 initial (failed) combined
    # check + the recovery flushes the ledger claims
    assert witness.combined - 1 + witness.persig == recovery
    # and the cumulative trace counter grew by exactly that many
    assert (
        _trace.verify_stats()["counters"]["recovery_flushes"] - counters0
        == recovery
    )
    assert _trace.verify_stats()["last_flush"]["recovery_flushes"] == recovery


def test_two_bad_rows_cost_at_most_two_descents(bisect_env, monkeypatch):
    """k bad rows cost at most k independent descents: 2 poisoned rows in
    separate halves stay within 2 * (2*ceil(log2 C)+1) flushes."""
    witness = _FlushWitness(monkeypatch)
    pks, msgs, sigs = _signed_rows(64)
    sigs = list(sigs)
    _flip(sigs, 5)
    _flip(sigs, 60)

    mask = batch.verify_batch(pks, msgs, sigs, backend="jax")
    recovery = batch.LAST_FLUSH_DETAIL.get("recovery_flushes", 0)

    assert not mask[5] and not mask[60] and mask.sum() == 62
    bound = 2 * math.ceil(math.log2(math.ceil(64 / 8))) + 1
    assert recovery <= 2 * bound
    assert witness.combined - 1 + witness.persig == recovery


def test_dense_flood_trips_adaptive_bail_mask_exact(bisect_env, monkeypatch):
    """A dense flood (half the rows poisoned) trips _BISECT_MAX_BAD:
    remaining ranges skip their combined checks and go straight per-sig,
    so bisection never costs more than the naive arm by a growing factor
    — and the mask stays exact."""
    monkeypatch.setattr(batch, "_BISECT_MAX_BAD", 2)
    _FlushWitness(monkeypatch)
    pks, msgs, sigs = _signed_rows(64)
    sigs = list(sigs)
    bad = set(range(0, 64, 2))
    for i in bad:
        _flip(sigs, i)

    mask = batch.verify_batch(pks, msgs, sigs, backend="jax")
    assert all(bool(mask[i]) != (i in bad) for i in range(64))
    cpu = batch.verify_batch_cpu(pks, msgs, sigs)
    assert mask.tobytes() == cpu.tobytes()


def test_bisect_disabled_restores_naive_arm(bisect_env, monkeypatch):
    """TMTPU_BISECT=0: straight-to-per-sig recovery — ONE whole-batch
    recovery flush, identical mask (the bench baseline arm)."""
    monkeypatch.setenv("TMTPU_BISECT", "0")
    witness = _FlushWitness(monkeypatch)
    pks, msgs, sigs = _signed_rows(64)
    sigs = list(sigs)
    _flip(sigs, 13)

    mask = batch.verify_batch(pks, msgs, sigs, backend="jax")

    assert not mask[13] and mask.sum() == 63
    assert batch.LAST_FLUSH_DETAIL.get("recovery_flushes") == 1
    assert witness.persig == 1  # one monolithic per-sig flush, all 64 rows
    assert batch.LAST_JAX_PATH[0] == "persig"


# ---------------------------------------------------------------------------
# After a pipelined flush the ladder rides the planner's one chunk bucket.


@pytest.mark.parametrize(
    "bad_rows", [(41,), (0, 99), (30, 31, 32, 33, 34, 35)],
    ids=["one", "head-and-tail", "six-in-a-row"],
)
def test_ladder_after_a_pipelined_flush_rides_the_chunk_bucket(
    bisect_env, monkeypatch, bad_rows
):
    """A flush over the stream floor compiled ONE shape, the chunk bucket
    (rlc_partial): every combined check of its recovery ladder runs as one
    chunk on that bucket, none on a whole-flush program (rlc_check_submit,
    a program per pow2 sub-range size: what a catch-up run of 10,624 rows
    paid minutes for, PR 29). The mask is the CPU reference's, the flush
    count the ledger's."""
    from tendermint_tpu.ops import msm_jax

    witness = _FlushWitness(monkeypatch)
    partial_lanes = []
    real_partial = msm_jax.rlc_partial_submit

    def counting_partial(pts, *a, **k):
        partial_lanes.append(pts.shape[0])
        return real_partial(pts, *a, **k)

    monkeypatch.setattr(msm_jax, "rlc_partial_submit", counting_partial)
    prev_cfg = dict(batch._PREP_CFG)
    batch.configure_planner(max_flush_lanes=256)  # 127 rows a chunk
    batch._PREP_CFG.update(stream_floor=16)
    try:
        pks, msgs, sigs = _signed_rows(100)
        sigs = list(sigs)
        for i in bad_rows:
            _flip(sigs, i)
        mask = batch.verify_batch(pks, msgs, sigs, backend="jax")
        recovery = batch.LAST_FLUSH_DETAIL.get("recovery_flushes", 0)
    finally:
        batch._PREP_CFG.clear()
        batch._PREP_CFG.update(prev_cfg)

    assert mask.tobytes() == batch.verify_batch_cpu(pks, msgs, sigs).tobytes()
    assert [i for i in range(100) if not mask[i]] == list(bad_rows)
    assert batch.LAST_JAX_PATH[0] == "rlc-bisect"
    assert witness.combined == 0  # no whole-flush program at any sub-range size
    assert set(partial_lanes) == {256}  # one shape, the failed flush's own
    # the failed flush's one chunk, then one chunk a combined check
    assert len(partial_lanes) - 1 + witness.persig == recovery
    assert witness.persig >= 1


# ---------------------------------------------------------------------------
# Host arm (_bisect_recover_host): same ladder on the striped host RLC.


def test_host_bisect_flush_bound_and_exact_mask(bisect_env, monkeypatch):
    """The CPU fallback's bisection keeps the log-cost shape: one bad row
    in 64 (host leaf 2, host-RLC floor lowered to 8 so the ladder actually
    splits) recovers with at most 2*ceil(log2 C)+1 host-RLC sub-checks."""
    monkeypatch.setattr(batch, "_HOST_RLC_MIN", 8)
    combined = [0]
    serial_rows = [0]
    real_rlc = batch._verify_batch_cpu_rlc
    real_serial = batch._verify_serial_host

    def counting_rlc(pks, msgs, sigs):
        combined[0] += 1
        return real_rlc(pks, msgs, sigs)

    def counting_serial(pks, msgs, sigs):
        serial_rows[0] += len(pks)
        return real_serial(pks, msgs, sigs)

    monkeypatch.setattr(batch, "_verify_batch_cpu_rlc", counting_rlc)
    monkeypatch.setattr(batch, "_verify_serial_host", counting_serial)

    pks, msgs, sigs = _signed_rows(64)
    sigs = list(sigs)
    _flip(sigs, 41)
    detail0 = batch.LAST_FLUSH_DETAIL.get("recovery_flushes", 0)

    mask = batch.verify_batch_cpu(pks, msgs, sigs)

    assert not mask[41] and mask.sum() == 63
    # host leaf = max(8 // 4, 1) = 2, but the _HOST_RLC_MIN guard stops
    # splitting at ranges under 16 rows: C = ceil(64 / 8) = 8 chunks
    bound = 2 * math.ceil(math.log2(8)) + 1
    recovery = batch.LAST_FLUSH_DETAIL.get("recovery_flushes", 0) - detail0
    assert 1 <= recovery <= bound
    # the serial loop ran on a small leaf, never the whole batch
    assert serial_rows[0] < 64
    # combined sub-checks: 1 initial (failed) + the ladder's re-checks
    assert combined[0] - 1 + (1 if serial_rows[0] else 0) <= bound + 1


def test_host_naive_arm_counts_its_recovery_flush(bisect_env, monkeypatch):
    """TMTPU_BISECT=0 on the host arm: the whole-batch serial pass is
    counted as one recovery flush (the ledger covers both arms)."""
    monkeypatch.setattr(batch, "_HOST_RLC_MIN", 8)
    monkeypatch.setenv("TMTPU_BISECT", "0")
    pks, msgs, sigs = _signed_rows(64)
    sigs = list(sigs)
    _flip(sigs, 7)
    detail0 = batch.LAST_FLUSH_DETAIL.get("recovery_flushes", 0)

    mask = batch.verify_batch_cpu(pks, msgs, sigs)

    assert not mask[7] and mask.sum() == 63
    assert batch.LAST_FLUSH_DETAIL.get("recovery_flushes", 0) - detail0 == 1


# ---------------------------------------------------------------------------
# Byte-identity across every recovery arm.


def test_mask_byte_identical_across_all_arms(bisect_env, monkeypatch):
    """The same poisoned 93-row set recovers the IDENTICAL mask through
    single-chip bisect, streamed planner recovery, sharded-streamed
    recovery, host-RLC bisect, and the naive fallback."""
    _FlushWitness(monkeypatch)
    pks, msgs, sigs = _signed_rows(93)
    sigs = list(sigs)
    for i in (0, 31, 62, 92):  # chunk boundaries of the 31-row planner
        _flip(sigs, i)

    reference = batch.verify_batch_cpu(pks, msgs, sigs)
    assert reference.sum() == 89

    arms = {}
    arms["bisect"] = batch.verify_batch(pks, msgs, sigs, backend="jax")
    assert batch.LAST_JAX_PATH[0] == "rlc-bisect"

    batch.configure_planner(max_flush_lanes=64)  # 31 rows per chunk
    arms["streamed"] = batch.verify_batch(pks, msgs, sigs, backend="jax")
    assert batch.LAST_JAX_PATH[0] == "rlc-streamed-recovery"

    env = _fake_mesh_env(4)
    monkeypatch.setattr(batch, "_sharded_env", lambda: env)
    arms["sharded-streamed"] = batch._verify_batch_streamed(pks, msgs, sigs)
    monkeypatch.setattr(batch, "_sharded_env", lambda: None)
    batch.configure_planner(max_flush_lanes=1 << 16)

    monkeypatch.setenv("TMTPU_BISECT", "0")
    arms["naive-persig"] = batch.verify_batch(pks, msgs, sigs, backend="jax")
    arms["naive-host"] = batch.verify_batch_cpu(pks, msgs, sigs)

    for name, mask in arms.items():
        assert mask.tobytes() == reference.tobytes(), name
