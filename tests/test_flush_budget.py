"""Per-flush device-traffic budget guard for the RLC submit path.

Counter-based, test_hotpath_guard-style: PERF.md's roofline says the MSM is
HBM/H2D-bound, so the invariants that keep it fast are "how many bytes go
down the wire per flush" and "how many device dispatches a flush costs" —
not wall clock. These budgets fail tier-1 with a byte/count diff if a
regression reintroduces per-flush A-block uploads, extra dispatches, or
per-point-op layout conversions (the ~8 ms of pack/reshape plumbing the
fused pipeline removed), instead of only showing up in a lost bench round.

Kernels are stubbed (no compiles): the counters live on the submit path
(ops/msm_jax._dispatch, crypto/batch._a_block), not in the kernels.
"""

import os

import numpy as np
import pytest

os.environ.setdefault("TMTPU_CRYPTO_BACKEND", "cpu")

from tendermint_tpu.crypto import batch as B
from tendermint_tpu.crypto.keys import gen_ed25519
from tendermint_tpu.ops import msm_jax as M

N_SIGS = 63  # Na bucket 64 -> 128 lanes
NA = 64

# Cached-A steady-state flush, exact expected upload (bytes):
#   r_bytes (32, 64) u8        2048
#   perm    (32, 128) u16      8192
#   ends    (32, 256) i32     32768
#   scalars (128, 32) u8       4096
# FieldCtx/SmallCtx constants are device-resident jnp buffers (not H2D).
CACHED_FLUSH_H2D_BUDGET = 2048 + 8192 + 32768 + 4096
A_BLOCK_BYTES = 4 * 20 * NA * 4  # uploaded once, then device-cached


@pytest.fixture
def stubbed_rlc(monkeypatch):
    monkeypatch.setattr(B, "RLC_MIN", 4)
    monkeypatch.setenv("TMTPU_SHARDED", "0")
    monkeypatch.setattr(M.aot_cache, "call", lambda name, fn, *a: fn(*a))

    def cached_stub(ax, ay, az, at, r_bytes, perm, ends, fctx, C):
        return np.ones(1 + r_bytes.shape[1], dtype=bool)

    def plain_stub(pts_bytes, perm, ends, fctx, C):
        return np.ones(1 + pts_bytes.shape[1], dtype=bool)

    for name in ("_rlc_cached_jit", "_rlc_cached_jit_fused"):
        monkeypatch.setattr(M, name, cached_stub)
    for name in ("_rlc_jit", "_rlc_jit_fused"):
        monkeypatch.setattr(M, name, plain_stub)
    B._DEV_A_CACHE.clear()
    yield


def _make_batch(n=N_SIGS):
    pks, msgs, sigs = [], [], []
    for i in range(n):
        priv = gen_ed25519(bytes([0x51]) * 31 + bytes([i]))
        m = b"budget-%03d" % i
        pks.append(priv.pub_key().bytes())
        msgs.append(m)
        sigs.append(priv.sign(m))
    return pks, msgs, sigs


def _flush(pks, msgs, sigs):
    call = B._rlc_submit(pks, msgs, sigs)
    assert B._rlc_finish(call) is not None
    return call


def test_cached_flush_h2d_and_dispatch_budget(stubbed_rlc):
    pks, msgs, sigs = _make_batch()
    B._fill_a_cache(np.stack([np.frombuffer(p, dtype=np.uint8) for p in pks]))

    # flush 1: steady-state kernel, but the device-resident A block is cold
    call = _flush(pks, msgs, sigs)
    assert call.mode == "cached"
    first = dict(B.LAST_FLUSH_DETAIL)
    # flush 2: everything warm — THE per-flush budget being pinned
    call = _flush(pks, msgs, sigs)
    assert call.mode == "cached"
    second = dict(B.LAST_FLUSH_DETAIL)

    assert first["device_dispatches"] == 1
    assert second["device_dispatches"] == 1
    # warm flush: exactly the per-flush wire bytes, nothing else
    assert 0 < second["h2d_bytes"] <= CACHED_FLUSH_H2D_BUDGET, second["h2d_bytes"]
    # the A block went up ONCE (cold flush), never again
    assert first["h2d_bytes"] - second["h2d_bytes"] >= A_BLOCK_BYTES
    assert "fused" in second  # flush detail names the pipeline variant


def test_a_block_reupload_regression_would_fail(stubbed_rlc):
    """Clearing the device-resident A cache between flushes re-pays the
    A-block upload — proving the budget above actually detects the
    regression it guards against."""
    pks, msgs, sigs = _make_batch()
    B._fill_a_cache(np.stack([np.frombuffer(p, dtype=np.uint8) for p in pks]))
    _flush(pks, msgs, sigs)
    _flush(pks, msgs, sigs)
    warm = B.LAST_FLUSH_DETAIL["h2d_bytes"]
    B._DEV_A_CACHE.clear()  # the regression: device A block lost per flush
    _flush(pks, msgs, sigs)
    assert B.LAST_FLUSH_DETAIL["h2d_bytes"] >= warm + A_BLOCK_BYTES


@pytest.mark.parametrize("pallas, conversions", [("interpret", 1), ("0", 3)])
def test_fused_layout_conversion_budget(monkeypatch, pallas, conversions):
    """The fused pipeline has ONE layout outside its tree kernel, rows: the
    uptree kernel reads the row gather's rows and writes rows, so a traced
    MSM on the kernels' path makes 1 packed-layout conversion in XLA (the
    bucket extract), independent of point-op count. The CPU twin of the
    tree stage still transposes in XLA on both sides: 3. The unfused
    wrappers repack per point op; a fused-path regression back to that
    shape, or a transpose put back around the kernel, changes these."""
    import jax
    import jax.numpy as jnp

    from tendermint_tpu.ops import pallas_msm as PM

    monkeypatch.setenv("TMTPU_PALLAS", pallas)
    n, t_ = 1024, 2
    C = M.make_small_ctx()
    pts = M.Point(*(jax.ShapeDtypeStruct((20, n), jnp.int32) for _ in range(4)))
    perm = jax.ShapeDtypeStruct((t_, n), jnp.int32)
    ends = jax.ShapeDtypeStruct((t_, M.NBUCKETS), jnp.int32)
    before = PM.LAYOUT_CONVERSIONS[0]
    jax.eval_shape(lambda p, pm, e: M._msm_total_fused(C, p, pm, e), pts, perm, ends)
    assert PM.LAYOUT_CONVERSIONS[0] - before == conversions


def test_flush_detail_reaches_verify_stats(stubbed_rlc):
    """The budget counters ride the flight recorder: verify_stats
    last_flush names h2d_bytes / device_dispatches / fused for the flush
    (docs/OBSERVABILITY.md)."""
    from tendermint_tpu.libs import trace as _trace

    pks, msgs, sigs = _make_batch()
    B._fill_a_cache(np.stack([np.frombuffer(p, dtype=np.uint8) for p in pks]))
    mask = B.verify_batch(pks, msgs, sigs, backend="jax")
    assert mask.all()
    last = _trace.verify_stats()["last_flush"]
    assert last["path"] == "rlc"
    assert last["device_dispatches"] == 1
    assert 0 < last["h2d_bytes"] <= CACHED_FLUSH_H2D_BUDGET + A_BLOCK_BYTES
    assert last["fused"] is False  # auto mode on the CPU test backend
