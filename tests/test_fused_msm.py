"""Fused MSM pipeline (ops/pallas_msm.py + msm_jax._msm_total_fused).

Three correctness layers, matching how the fused path can actually fail:

1. Tier-1, integer mock: the fold schedule + bit-reversed storage map +
   fused_node_indices_device must reconstruct every bucket-boundary prefix
   sum. Points are mocked as integers (add = +, identity = 0), so this runs
   in milliseconds and catches every pairing/reversal/offset bug.
2. Tier-1, schedule equality: the Pallas kernel bodies and their fe25519
   CPU twins share fold schedules by construction; running BOTH with a
   mocked add on the same data pins them against drift (the row math
   itself is pinned to the fe ops by tests/test_pallas_fe.py).
3. Slow/kernel lane, real curve math: the fused total equals the unfused
   XLA reference bit-for-bit (same association tree at the node level,
   compressed-point equality at the output), and the full verify_batch
   mask through the fused RLC path is byte-identical to the CPU reference
   at several batch sizes — the same pattern as tests/test_rlc_fallback.py.
"""

import os

import numpy as np
import pytest

os.environ.setdefault("TMTPU_CRYPTO_BACKEND", "cpu")

from tendermint_tpu.ops import msm_jax as M
from tendermint_tpu.ops import pallas_msm as PM


@pytest.fixture(autouse=True)
def _reset_fused_state():
    yield
    M._FUSED_DISABLED[0] = None
    M._set_submit_fused(False)


# ---------------------------------------------------------------------------
# Layer 1: integer-mock schedule + index math.


def _mock_uptree_chunk(g_chunk: np.ndarray, geom) -> np.ndarray:
    """Integer twin of the uptree fold schedule + output layout."""
    out = []
    cur = g_chunk.copy()
    width = geom.ch
    while width > 128:
        width //= 2
        cur = cur[:width] + cur[width:]
        out.append(cur.copy())
    w = 64
    while w >= 1:
        cur = cur + np.roll(cur, 128 - w)
        out.append(cur.copy())
        w //= 2
    flat = np.concatenate(out)
    pad = geom.rows_out * 128 - flat.shape[0]
    return np.concatenate([flat, np.zeros(pad, dtype=flat.dtype)])


def _mock_top_tree(roots: np.ndarray) -> np.ndarray:
    """Integer twin of _tree_levels over chunk roots (+ identity lane)."""
    levels = [roots.copy()]
    cur = roots.copy()
    while cur.shape[0] > 1:
        if cur.shape[0] % 2:
            cur = np.concatenate([cur, [0]])
        cur = cur[0::2] + cur[1::2]
        levels.append(cur.copy())
    widths = M.level_widths(roots.shape[0])
    flat = np.concatenate([lv[:w] for lv, w in zip(levels, widths)])
    return np.concatenate([flat, [0]])


@pytest.mark.parametrize(
    "n,ch", [(2048, 2048), (4096, 2048), (6144, 2048), (3072, 1024), (1024, 1024)]
)
def test_fused_node_indices_reconstruct_every_prefix(n, ch):
    assert PM.chunk_for_lanes(n) == ch
    geom = PM.chunk_geometry(ch)
    t_ = M.NWIN
    ncw = n // ch
    rng = np.random.default_rng(7 + n)
    vals = rng.integers(0, 1 << 40, size=(t_, n)).astype(np.int64)
    digits = rng.integers(0, 256, size=(n, t_)).astype(np.uint8)
    perm, ends = M.sort_windows(digits)
    perm = perm.astype(np.int64)

    perm_f = perm[:, PM.brev_positions(n, ch)]
    g_vals = np.take_along_axis(vals, perm_f, axis=1)
    ctree = np.concatenate(
        [
            _mock_uptree_chunk(g_vals[t, c * ch : (c + 1) * ch], geom)
            for t in range(t_)
            for c in range(ncw)
        ]
    )
    roots = ctree.reshape(t_ * ncw, geom.rows_out * 128)[
        :, geom.row_off[geom.lc] * 128
    ].reshape(t_, ncw)
    top = np.concatenate([_mock_top_tree(roots[t]) for t in range(t_)])
    all_vals = np.concatenate([g_vals.reshape(-1), ctree, top])

    node_idx = np.asarray(M.fused_node_indices_device(ends, n, ch))
    got = all_vals[node_idx].sum(axis=-1)  # (256, T)
    sorted_vals = np.take_along_axis(vals, perm, axis=1)
    csum = np.concatenate(
        [np.zeros((t_, 1), np.int64), np.cumsum(sorted_vals, axis=1)], axis=1
    )
    want = np.stack([csum[t][ends[t]] for t in range(t_)], axis=1)  # (256, T)
    assert (got == want).all()


def test_brev_and_geometry_invariants():
    for ch in (1024, 2048):
        g = PM.chunk_geometry(ch)
        assert g.ch == 1 << g.lc
        assert g.rows_out % 8 == 0
        # row offsets strictly increasing, rows fit
        offs = list(g.row_off[1:])
        assert offs == sorted(offs)
        assert offs[-1] < g.rows_out
        i = np.arange(ch)
        # bit reversal is an involution; positions are a permutation
        assert (PM.brev_np(PM.brev_np(i, g.lc), g.lc) == i).all()
        pos = PM.brev_positions(4 * ch, ch)
        assert sorted(pos.tolist()) == list(range(4 * ch))
    # jnp brev with variable bit counts matches numpy
    import jax.numpy as jnp

    j = np.arange(64)
    for m in range(1, 12):
        assert (
            np.asarray(PM.brev_jnp(jnp.asarray(j % (1 << m)), m))
            == PM.brev_np(j % (1 << m), m)
        ).all()


def test_chunk_for_lanes_routing():
    assert PM.chunk_for_lanes(2048) == 2048
    assert PM.chunk_for_lanes(20480) == 2048
    assert PM.chunk_for_lanes(3072) == 1024
    assert PM.chunk_for_lanes(1024) == 1024
    assert PM.chunk_for_lanes(512) is None
    assert PM.chunk_for_lanes(2500) is None


# ---------------------------------------------------------------------------
# Layer 2: kernel body vs CPU twin, schedules pinned with a mocked add.


def _mock_padd_rows(p, q):
    return tuple([a + b for a, b in zip(pr, qr)] for pr, qr in zip(p, q))


def _mock_padd_fe(p, q):
    return tuple(a + b for a, b in zip(p, q))


def _uptree_kernel_interpreted(monkeypatch, x: np.ndarray, ch: int) -> np.ndarray:
    """The kernel body itself (its transposes, strided scratch accesses and
    fold schedule) under the Pallas interpreter, with the mocked add; built
    past the builder's cache, which would keep the mock."""
    import jax.numpy as jnp

    monkeypatch.setenv("TMTPU_PALLAS", "interpret")
    monkeypatch.setattr(PM, "_padd_rows", _mock_padd_rows)
    return np.asarray(PM._uptree_call.__wrapped__(x.shape[0], ch)(jnp.asarray(x)))


@pytest.mark.parametrize("ch", [1024, 2048])
def test_uptree_kernel_body_schedule_equals_twin(monkeypatch, ch):
    """Rows in, rows out: the kernel body and _uptree_jnp agree position by
    position, padding rows and roll-fold garbage included."""
    import jax.numpy as jnp

    monkeypatch.setattr(PM, "_padd_fe", _mock_padd_fe)
    g = PM.chunk_geometry(ch)
    rng = np.random.default_rng(5)
    nchunks = 2
    x = rng.integers(0, 1 << 20, size=(nchunks * ch, PM.NW)).astype(np.int32)
    twin = np.asarray(PM._uptree_jnp(jnp.asarray(x), g))
    body = _uptree_kernel_interpreted(monkeypatch, x, ch)
    assert twin.shape == body.shape == (nchunks * g.rows_out * 128, PM.NW)
    assert (twin == body).all()


@pytest.mark.parametrize("ch", [1024, 2048])
def test_uptree_rows_keep_every_word_in_place(monkeypatch, ch):
    """A table whose rows differ in every word (word w of row m holds
    m*80 + w), held to the integer model one word column at a time: under
    the mocked add the columns are independent, so a transposition that
    swapped limbs, coordinates or tiles cannot pass by symmetry."""
    import jax.numpy as jnp

    monkeypatch.setattr(PM, "_padd_fe", _mock_padd_fe)
    g = PM.chunk_geometry(ch)
    nchunks = 2
    x = np.arange(nchunks * ch * PM.NW, dtype=np.int32).reshape(nchunks * ch, PM.NW)
    want = np.concatenate(
        [
            np.stack(
                [
                    _mock_uptree_chunk(x[c * ch : (c + 1) * ch, w], g)
                    for w in range(PM.NW)
                ],
                axis=1,
            )
            for c in range(nchunks)
        ]
    )
    assert (_uptree_kernel_interpreted(monkeypatch, x, ch) == want).all()
    assert (np.asarray(PM._uptree_jnp(jnp.asarray(x), g)) == want).all()


def test_bucket_kernel_body_schedule_equals_twin(monkeypatch):
    import jax.numpy as jnp

    monkeypatch.setattr(PM, "_padd_rows", _mock_padd_rows)
    monkeypatch.setattr(PM, "_padd_fe", _mock_padd_fe)
    rng = np.random.default_rng(6)
    t_ = 32
    x = rng.integers(0, 1 << 20, size=(4, PM.NL, 256 * t_ // 128, 128)).astype(
        np.int32
    )
    twin = np.asarray(PM._bucket_jnp(jnp.asarray(x), t_))
    body = np.asarray(PM._bucket_block(jnp.asarray(x), t_, real=False))
    assert (twin == body).all()


# ---------------------------------------------------------------------------
# Routing + failure ladder (stubbed; no compiles).


def test_fused_for_lanes_flag_modes(monkeypatch):
    monkeypatch.setenv("TMTPU_FUSED_MSM", "0")
    assert not M.fused_for_lanes(2048)
    monkeypatch.setenv("TMTPU_FUSED_MSM", "1")
    assert M.fused_for_lanes(2048)
    assert not M.fused_for_lanes(999)  # no chunk tiles it
    monkeypatch.setenv("TMTPU_FUSED_MSM", "auto")
    # auto == pallas-enabled; on the CPU test backend that is False
    from tendermint_tpu.ops import pallas_fe

    assert M.fused_for_lanes(2048) == pallas_fe.enabled()
    # runtime disable wins over everything and is sticky
    monkeypatch.setenv("TMTPU_FUSED_MSM", "1")
    M.disable_fused("test")
    assert not M.fused_for_lanes(2048)
    assert M._FUSED_DISABLED[0] == "test"


def test_fused_submit_failure_disables_and_retries_unfused(monkeypatch):
    """A fused-path submit failure must (a) stick-disable the fused
    pipeline, (b) retry THIS flush unfused, and (c) produce the exact CPU
    mask — the consensus caller never sees the failure."""
    from tendermint_tpu.crypto import batch as B
    from tests.test_rlc_fallback import make_mixed_validity_batch

    monkeypatch.setattr(B, "RLC_MIN", 4)
    monkeypatch.setenv("TMTPU_SHARDED", "0")
    monkeypatch.setattr(
        M, "fused_for_lanes", lambda n: M._FUSED_DISABLED[0] is None
    )
    monkeypatch.setattr(M.aot_cache, "call", lambda name, fn, *a: fn(*a))

    calls = []

    def fused_boom(*a, **kw):
        calls.append("fused")
        raise RuntimeError("injected Mosaic lowering failure")

    def unfused_ok(ax, ay, az, at, r_bytes, perm, ends, fctx, C):
        calls.append("unfused")
        return np.ones(1 + r_bytes.shape[1], dtype=bool)

    def unfused_plain_ok(pts_bytes, perm, ends, fctx, C):
        calls.append("unfused")
        return np.ones(1 + pts_bytes.shape[1], dtype=bool)

    monkeypatch.setattr(M, "_rlc_jit_fused", fused_boom)
    monkeypatch.setattr(M, "_rlc_cached_jit_fused", fused_boom)
    monkeypatch.setattr(M, "_rlc_jit", unfused_plain_ok)
    monkeypatch.setattr(M, "_rlc_cached_jit", unfused_ok)

    pks, msgs, sigs = make_mixed_validity_batch()
    cpu = B.verify_batch_cpu(pks, msgs, sigs)
    mask = B.verify_batch(pks, msgs, sigs, backend="jax")

    assert mask.tobytes() == cpu.tobytes()
    assert "fused" in calls and "unfused" in calls
    assert M._FUSED_DISABLED[0] is not None  # sticky
    assert B.LAST_JAX_PATH[0] == "rlc"  # the RLC path survived the failure
    # next flush goes straight unfused (no new fused attempts)
    n_fused = calls.count("fused")
    B.verify_batch(pks, msgs, sigs, backend="jax")
    assert calls.count("fused") == n_fused


# ---------------------------------------------------------------------------
# Layer 3: real curve math (slow/kernel lane).


def _compress(p):
    from tendermint_tpu.crypto import ed25519_ref as ref
    from tendermint_tpu.ops import fe25519 as fe

    x = fe.to_int(np.asarray(p.x)) % ref.P
    y = fe.to_int(np.asarray(p.y)) % ref.P
    z = fe.to_int(np.asarray(p.z)) % ref.P
    t = fe.to_int(np.asarray(p.t)) % ref.P
    return ref.point_compress((x, y, z, t))


@pytest.mark.slow
@pytest.mark.kernel
def test_fused_total_matches_unfused_reference(monkeypatch):
    """The fused schedule computes the same multiscalar sum as the unfused
    per-level reference (compressed-point equality; different association
    orders give different projective representatives)."""
    import jax

    from tendermint_tpu.crypto import ed25519_ref as ref
    from tendermint_tpu.ops import fe25519 as fe

    monkeypatch.setenv("TMTPU_FUSED_MSM", "1")
    rng = np.random.default_rng(3)
    n, t_ = 1024, 2
    cols = []
    for _ in range(n):
        k = int.from_bytes(rng.bytes(8), "little") | 1
        x, y, z, t = ref.point_mul(k, ref.BASE)
        cols.append(
            [fe.from_int(x), fe.from_int(y), fe.from_int(z), fe.from_int(t)]
        )
    pts = M.Point(
        *(
            np.stack([c[i] for c in cols], axis=-1).astype(np.int32)
            for i in range(4)
        )
    )
    digits = rng.integers(0, 256, size=(n, t_)).astype(np.uint8)
    perm, ends = M.sort_windows(digits)
    C = M.make_small_ctx()

    node_idx = M.fenwick_nodes_device(ends, n)
    unf = jax.jit(M._msm_total)(C, pts, perm.astype(np.int32), node_idx)
    fus = jax.jit(M._msm_total_fused)(C, pts, perm.astype(np.int32), ends)
    unf = M.Point(*(np.asarray(c) for c in unf))
    fus = M.Point(*(np.asarray(c) for c in fus))
    assert _compress(unf) == _compress(fus)


@pytest.mark.slow
@pytest.mark.kernel
@pytest.mark.heavy  # full RLC graph at 2048/3072 lanes: multi-minute
# one-time XLA:CPU compiles (persistent-cached); on TPU the same programs
# are Pallas custom calls + gathers and compile in seconds
@pytest.mark.parametrize("n_sigs", [600, 1400])
def test_fused_rlc_mask_byte_identical_to_cpu(monkeypatch, n_sigs):
    """Full verify_batch through the fused RLC path (plain + cached-A
    kernels): mask byte-identical to the CPU reference, including rows the
    host precheck rejects (bad pubkey length, non-canonical s) — and the
    combined check itself must ACCEPT (no silent always-fallback).
    n=600 -> 2048 lanes (chunk 2048); n=1400 -> 3072 lanes (chunk 1024)."""
    from tendermint_tpu.crypto import batch as B
    from tendermint_tpu.crypto.ed25519_ref import L
    from tendermint_tpu.crypto.keys import gen_ed25519

    monkeypatch.setenv("TMTPU_FUSED_MSM", "1")
    monkeypatch.setenv("TMTPU_SHARDED", "0")
    B._A_CACHE.clear()

    pks, msgs, sigs = [], [], []
    for i in range(n_sigs):
        priv = gen_ed25519(bytes([9]) * 30 + bytes([i // 256, i % 256]))
        m = b"fused-rlc-%04d" % i
        pks.append(priv.pub_key().bytes())
        msgs.append(m)
        sigs.append(priv.sign(m))
    pks[17] = pks[17][:16]  # precheck-rejected: bad pubkey length
    sigs[41] = sigs[41][:32] + L.to_bytes(32, "little")  # non-canonical s

    lanes = 2 * B._lane_bucket(n_sigs + 1)
    assert M.fused_for_lanes(lanes), lanes

    cpu = B.verify_batch_cpu(pks, msgs, sigs)
    mask = B.verify_batch_jax(pks, msgs, sigs)  # plain kernel, fills A cache
    assert mask.tobytes() == cpu.tobytes()
    assert B.LAST_JAX_PATH[0] == "rlc"
    assert B.LAST_FLUSH_DETAIL.get("fused") is True

    mask2 = B.verify_batch_jax(pks, msgs, sigs)  # cached-A kernel
    assert mask2.tobytes() == cpu.tobytes()
    assert B.LAST_RLC_TIMINGS.get("cached") is True
    assert B.LAST_FLUSH_DETAIL.get("fused") is True
