"""Differential tests: native C host-prep kernels vs the pure-Python paths.

The native module (tendermint_tpu/native) replaces three host hot loops —
challenge hashing, RLC scalar math, per-window counting sort — with
multithreaded C. Every function is checked bit-exactly against the Python
reference on random and adversarial inputs (bad lengths, non-canonical s,
boundary scalars)."""

import hashlib

import numpy as np
import pytest

from tendermint_tpu.crypto.ed25519_ref import L

L8 = 8 * L


def _native():
    from tendermint_tpu import native

    if not native.available():
        pytest.skip("native batchhost unavailable (no compiler?)")
    return native


def test_h_batch_matches_hashlib():
    native = _native()
    rng = np.random.default_rng(7)
    n = 257
    sigs = rng.integers(0, 256, size=(n, 64), dtype=np.uint8).tobytes()
    pks = rng.integers(0, 256, size=(n, 32), dtype=np.uint8).tobytes()
    msgs = [
        bytes(rng.integers(0, 256, size=int(l), dtype=np.uint8))
        for l in rng.integers(0, 300, size=n)
    ]
    # SHA-512 block-boundary message lengths (with the 64-byte R||A prefix
    # the total crosses 1->2->3 block padding edges around 47/48 and 175/176)
    for j, ln in enumerate([0, 1, 46, 47, 48, 49, 174, 175, 176, 177]):
        msgs[j] = bytes(ln)
    moffs = np.zeros(n + 1, dtype=np.int64)
    for i, m in enumerate(msgs):
        moffs[i + 1] = moffs[i] + len(m)
    out = native.ed25519_h_batch(sigs, pks, b"".join(msgs), moffs)
    for i in range(n):
        r_b, a_b = sigs[i * 64 : i * 64 + 32], pks[i * 32 : (i + 1) * 32]
        exp = int.from_bytes(hashlib.sha512(r_b + a_b + msgs[i]).digest(), "little") % L
        assert int.from_bytes(out[i].tobytes(), "little") == exp, i


def test_sha256_matches_hashlib():
    """The memo digests' SHA-256 core alone, every length over its padding
    edges (0-300 bytes: one to six blocks) and one of 1 MB."""
    native = _native()
    rng = np.random.default_rng(9)
    out = np.empty(32, dtype=np.uint8)
    for ln in list(range(301)) + [1 << 20]:
        data = rng.bytes(ln)
        buf = np.frombuffer(data or b"\0", dtype=np.uint8)
        native._lib().tm_sha256(native._u8p(buf), ln, native._u8p(out))
        assert out.tobytes() == hashlib.sha256(data).digest(), ln


@pytest.mark.parametrize("bad", ["lengths", "no_key_type", "index_high", "index_negative", "index_short"])
def test_memo_digest_batch_refuses_rows_it_cannot_frame(bad):
    native = _native()
    pks, msgs, sigs = [b"k" * 32] * 3, [b"m"] * 3, [b"s" * 64] * 3
    key_types, idx = ["ed25519", "sr25519"], np.array([0, 1, 0], dtype=np.int32)
    if bad == "lengths":
        sigs = sigs[:2]
    elif bad == "no_key_type":
        key_types, idx = [], None
    elif bad == "index_high":
        idx[2] = 2
    elif bad == "index_negative":
        idx[0] = -1
    else:
        idx = idx[:2]
    with pytest.raises(ValueError):
        native.memo_digest_batch(0, key_types, idx, pks, msgs, sigs)


def test_memo_digests_exact_while_the_pool_is_busy():
    """A memo digest pass of more rows than the pool has threads, while a
    long ed25519_h_batch holds the pool: it takes per-call threads and still
    gives every row the hashlib loop's digest."""
    import threading

    from tendermint_tpu.crypto import batch

    native = _native()
    rng = np.random.default_rng(10)
    n = 64 * max(8, native.prep_threads())
    pks, msgs, sigs = ([rng.bytes(w) for _ in range(n)] for w in (32, 120, 64))
    memo = batch.VerifiedRowMemo(16)
    want = memo._digest_rows_py(pks, msgs, sigs)
    big = 200_000
    blobs = [rng.bytes(big * w) for w in (64, 32, 8)]
    moffs = np.arange(0, 8 * (big + 1), 8, dtype=np.int64)
    started = threading.Event()

    def hold_the_pool():
        started.set()
        native.ed25519_h_batch(*blobs, moffs)

    holder = threading.Thread(target=hold_the_pool)
    holder.start()
    assert started.wait(60)
    passes = 0
    while holder.is_alive() or passes == 0:
        assert memo.digest_rows(pks, msgs, sigs) == want
        passes += 1
    holder.join(60)
    assert not holder.is_alive()


def test_rlc_scalars_matches_bigint():
    native = _native()
    rng = np.random.default_rng(8)
    n = 300
    z = rng.integers(0, 256, size=(n, 16), dtype=np.uint8)
    z[0] = 0  # excluded row
    h = rng.integers(0, 256, size=(n, 32), dtype=np.uint8)
    h[:, 31] &= 0x1F  # < 2^253 like a reduced challenge
    s = rng.integers(0, 256, size=(n, 32), dtype=np.uint8)
    s[:, 31] &= 0x0F
    # boundary rows: max z/h/s values
    z[1] = 0xFF
    h[2] = np.frombuffer((L - 1).to_bytes(32, "little"), np.uint8)
    s[3] = np.frombuffer((L - 1).to_bytes(32, "little"), np.uint8)
    w, u = native.rlc_scalars(z, h, s)
    exp_u = 0
    for i in range(n):
        zi = int.from_bytes(z[i].tobytes(), "little")
        hi = int.from_bytes(h[i].tobytes(), "little")
        si = int.from_bytes(s[i].tobytes(), "little")
        wi = int.from_bytes(w[i].tobytes(), "little")
        if zi == 0:
            assert wi == 0
            continue
        assert wi == zi * hi % L8, i
        exp_u += zi * si
    assert u == exp_u % L


def test_sort_windows_matches_numpy():
    from tendermint_tpu.ops import msm_jax

    native = _native()
    rng = np.random.default_rng(9)
    for n in (1, 7, 512, 2048):
        digits = rng.integers(0, 256, size=(n, 32), dtype=np.uint8)
        perm_c, ends_c = native.sort_windows(digits)
        # numpy reference (bypassing the native routing inside sort_windows)
        perm_py = np.argsort(digits, axis=0, kind="stable").T
        counts = np.stack(
            [np.bincount(digits[:, w], minlength=256) for w in range(32)]
        )
        ends_py = np.cumsum(counts, axis=1).astype(np.int32)
        assert (ends_c == ends_py).all()
        assert (perm_c == perm_py).all()


def test_sort_windows_zero16_shortcut_matches_full_sort():
    """zero16_from (rows >= boundary are zero in windows 16-31 — the RLC
    z-lane layout) must produce the exact stable-sort result."""
    native = _native()
    rng = np.random.default_rng(12)
    for n, na in ((8, 4), (513, 256), (2048, 1024)):
        digits = rng.integers(0, 256, size=(n, 32), dtype=np.uint8)
        digits[na:, 16:] = 0  # the layout invariant the shortcut relies on
        # some prefix rows zero too (w-lane rows can be excluded => 0)
        digits[1, :] = 0
        perm_full, ends_full = native.sort_windows(digits)
        perm_z, ends_z = native.sort_windows(digits, zero16_from=na)
        assert (ends_z == ends_full).all(), (n, na)
        assert (perm_z == perm_full).all(), (n, na)


def test_vote_sign_bytes_matches_python_loop():
    """The C row builder against protowire, on 2,000 random rows: the blob,
    the offsets, and the output buffer's bound (the binding sizes it for n
    rows of the longest possible length, so the loop cannot overrun it)."""
    from tendermint_tpu.libs import protowire as pw

    native = _native()
    rng = np.random.default_rng(27)
    n = 2000
    ts = rng.integers(-(1 << 63), (1 << 63) - 1, size=n, dtype=np.int64, endpoint=True)
    ts[:4] = [0, -(1 << 63), (1 << 63) - 1, -1]
    parts = [b"", b"\x22\x03abc", b"\x22\x48" + bytes(range(72)), b"\x22\x00"]
    sel = rng.integers(0, len(parts), size=n).astype(np.int32)
    prefix, suffix = b"\x08\x02\x11" + bytes(8), b"\x32\x0dtest_chain_id"
    blob, offs = native.vote_sign_bytes(prefix, parts, sel, ts, suffix)
    rows = []
    for k, t in zip(sel.tolist(), ts.tolist()):
        tb = pw.encode_timestamp(*divmod(t, 10**9))
        rows.append(pw.length_delimited(
            prefix + parts[k] + b"\x2a" + pw.encode_varint(len(tb)) + tb + suffix))
    assert offs.dtype == np.int64 and offs.shape == (n + 1,)
    assert offs.tolist() == [0] + np.cumsum([len(r) for r in rows]).tolist()
    assert blob == b"".join(rows)
    # the binding reserves every row the longest body the inputs admit
    # (longest part, 17-byte timestamp: negative seconds take ten bytes,
    # nanos from 2^28 five) plus nine bytes of outer length; the random
    # rows reach that body, with a one-byte length, and none passes it
    longest = len(prefix) + 74 + 2 + 17 + len(suffix)
    assert max(len(r) for r in rows) == longest + 1
    assert len(blob) == offs[-1] <= n * (longest + 9)
    with pytest.raises(ValueError):
        native.vote_sign_bytes(prefix, parts, np.array([len(parts)], np.int32), ts[:1], suffix)
    with pytest.raises(ValueError):
        native.vote_sign_bytes(prefix, parts, sel[:3], ts[:2], suffix)
    empty = native.vote_sign_bytes(prefix, [], np.zeros(0, np.int32), np.zeros(0, np.int64), b"")
    assert empty[0] == b"" and empty[1].tolist() == [0]


def test_precheck_and_hash_fast_matches_python():
    from tendermint_tpu.crypto import batch as B

    _native()
    rng = np.random.default_rng(10)
    from tendermint_tpu.crypto.keys import gen_ed25519

    n = 64
    pubkeys, msgs, sigs = [], [], []
    for i in range(n):
        priv = gen_ed25519(bytes([i + 1]) * 32)
        m = b"msg-%03d" % i + bytes(rng.integers(0, 256, size=i, dtype=np.uint8))
        pubkeys.append(priv.pub_key().bytes())
        msgs.append(m)
        sigs.append(priv.sign(m))
    # adversarial rows: wrong lengths, non-canonical s, s == L, s == L-1
    pubkeys[3] = b"\x01" * 31
    sigs[4] = b"\x02" * 63
    sigs[5] = sigs[5][:32] + L.to_bytes(32, "little")
    sigs[6] = sigs[6][:32] + (L + 5).to_bytes(32, "little")
    sigs[7] = sigs[7][:32] + (L - 1).to_bytes(32, "little")  # canonical value
    pc_py, a_py, r_py, s_ints, hk_ints = B._precheck_and_hash(pubkeys, msgs, sigs)
    pc_c, a_c, r_c, s_c, h_c = B._precheck_and_hash_fast(pubkeys, msgs, sigs)
    assert (pc_py == pc_c).all()
    for i in range(n):
        if not pc_py[i]:
            continue
        assert (a_py[i] == a_c[i]).all()
        assert (r_py[i] == r_c[i]).all()
        assert int.from_bytes(s_c[i].tobytes(), "little") == s_ints[i]
        assert int.from_bytes(h_c[i].tobytes(), "little") == hk_ints[i]


def test_sr25519_native_matches_python():
    """Native C schnorrkel verifier (sr25519.c) vs the pure-Python reference
    on valid, tampered, wrong-key, marker-bit and s-range inputs."""
    native = _native()
    from tendermint_tpu.crypto.sr25519 import L as SR_L
    from tendermint_tpu.crypto.sr25519 import _sr25519_verify_py, gen_sr25519

    pks, msgs, sigs = [], [], []
    for i in range(12):
        priv = gen_sr25519(bytes([i + 1]) * 32)
        m = b"sr-diff-%02d" % i + b"y" * (i * 7)
        pks.append(priv.pub_key().bytes())
        msgs.append(m)
        sigs.append(priv.sign(m))
    cases = [(pks[i], msgs[i], sigs[i], True) for i in range(12)]
    cases += [
        (pks[0], msgs[0], bytes([sigs[0][0] ^ 1]) + sigs[0][1:], False),
        (pks[1], b"wrong", sigs[1], False),
        (pks[2], msgs[3], sigs[3], False),  # wrong key
        (pks[4], msgs[4], sigs[4][:63] + bytes([sigs[4][63] & 0x7F]), False),  # no marker
        (pks[5], msgs[5], sigs[5][:32] + SR_L.to_bytes(32, "little")[:31] + bytes([0x90]), False),  # s >= L
        (bytes(32), msgs[6], sigs[6], True),  # identity-ish pubkey: decode decides
    ]
    for i, (pk, m, s, expect_valid) in enumerate(cases):
        c = native.sr25519_verify(pk, m, s)
        p = _sr25519_verify_py(pk, m, s)
        assert c == p, (i, c, p)
        if expect_valid and i < 12:
            assert c


def test_sr25519_native_batch_matches_one():
    native = _native()
    from tendermint_tpu.crypto.sr25519 import gen_sr25519

    n = 16
    pks, msgs, sigs = [], [], []
    for i in range(n):
        priv = gen_sr25519(bytes([40 + i]) * 32)
        m = b"batch-%02d" % i * (i + 1)
        pks.append(priv.pub_key().bytes())
        msgs.append(m)
        sigs.append(priv.sign(m))
    sigs[5] = bytes(64)  # invalid row
    moffs = np.zeros(n + 1, dtype=np.int64)
    for i, m in enumerate(msgs):
        moffs[i + 1] = moffs[i] + len(m)
    mask = native.sr25519_verify_batch(
        b"".join(pks), b"".join(msgs), moffs, b"".join(sigs)
    )
    for i in range(n):
        assert mask[i] == native.sr25519_verify(pks[i], msgs[i], sigs[i]), i
    assert mask.sum() == n - 1 and not mask[5]


def test_verify_batch_jax_native_end_to_end():
    """The full RLC path with native host prep verifies real signatures and
    rejects a corrupted one (fallback path)."""
    jax = pytest.importorskip("jax")
    if jax.default_backend() != "cpu":
        pytest.skip("CPU-lane test")
    from tendermint_tpu.crypto import batch as B
    from tendermint_tpu.crypto.keys import gen_ed25519

    _native()
    old_min, old_jax_min = B.RLC_MIN, B._JAX_MIN_BATCH
    B.RLC_MIN = 8
    try:
        pubkeys, msgs, sigs = [], [], []
        for i in range(16):
            priv = gen_ed25519(bytes([i + 1]) * 32)
            m = b"native-e2e-%02d" % i
            pubkeys.append(priv.pub_key().bytes())
            msgs.append(m)
            sigs.append(priv.sign(m))
        mask = B.verify_batch(pubkeys, msgs, sigs, backend="jax")
        assert mask.all()
        sigs[5] = sigs[5][:32] + bytes(32)  # s = 0: fails verification
        mask = B.verify_batch(pubkeys, msgs, sigs, backend="jax")
        assert not mask[5] and mask.sum() == 15
    finally:
        B.RLC_MIN, B._JAX_MIN_BATCH = old_min, old_jax_min
