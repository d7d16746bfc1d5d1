"""RLC (random-linear-combination) batch verification — the Pippenger MSM
fast path (ops/msm_jax.py + crypto/batch.py).

Differential-tested against the host reference implementation and the
per-signature kernel. Semantics under test: the RLC path must return the
SAME mask as per-signature verification in every case — directly when the
combined check passes, via fallback when it fails
(reference semantics: types/validator_set.go:680-702, one accept/reject per
signature).

Shapes are kept to the production lane buckets (Na=64 -> 128 lanes) so the
persistent compile cache is shared with real use.
"""

import pytest

pytestmark = [pytest.mark.kernel, pytest.mark.slow]  # heavy one-time
# compiles: excluded from the tier-1 budget lane (-m 'not slow'); run
# explicitly via -m kernel

import os

import numpy as np
import pytest

os.environ.setdefault("TMTPU_CRYPTO_BACKEND", "jax")

from tendermint_tpu.crypto import batch as B
from tendermint_tpu.crypto.keys import gen_ed25519


def make_batch(n, seed=0, msg_len=40):
    pubkeys, msgs, sigs = [], [], []
    for i in range(n):
        priv = gen_ed25519(bytes([seed]) * 31 + bytes([i]))
        msg = b"msm-%03d-" % i + b"x" * (msg_len - 8)
        pubkeys.append(priv.pub_key().bytes())
        msgs.append(msg)
        sigs.append(priv.sign(msg))
    return pubkeys, msgs, sigs


@pytest.fixture(scope="module", autouse=True)
def _free_compile_memory():
    """Same guard as tests/test_sharded.py: XLA aborted (SIGABRT inside
    compilation_cache.get_executable_and_time) deserializing this module's
    large RLC executables in a process already holding ~36 earlier kernel
    tests' executables (observed r5 full-lane run; passes standalone).
    Dropping accumulated executables first keeps the process under the
    ceiling — later tests reload from the persistent cache."""
    from tests.conftest import free_compile_memory

    free_compile_memory()
    yield


@pytest.fixture
def rlc_on(monkeypatch):
    monkeypatch.setattr(B, "RLC_MIN", 1)
    # the test env exposes 8 virtual CPU devices; disable mesh routing so the
    # RLC path (single-device production shape) is what runs
    monkeypatch.setenv("TMTPU_SHARDED", "0")
    B._A_CACHE.clear()


def test_rlc_all_valid_and_cached_path(rlc_on):
    pubkeys, msgs, sigs = make_batch(40)
    # first call: uncached kernel; fills the pubkey cache
    mask = B.verify_batch_jax(pubkeys, msgs, sigs)
    assert mask.all()
    assert all(B._cache_key(bytes(pk), "ed25519") in B._A_CACHE for pk in pubkeys)
    # second call: cached-A kernel; same verdict
    mask2 = B.verify_batch_jax(pubkeys, msgs, sigs)
    assert mask2.all()
    assert B.LAST_RLC_TIMINGS.get("cached") is True


def test_rlc_bad_sig_falls_back_to_exact_mask(rlc_on):
    pubkeys, msgs, sigs = make_batch(40)
    bad = bytearray(sigs[7])
    bad[3] ^= 0xFF
    sigs[7] = bytes(bad)
    mask = B.verify_batch_jax(pubkeys, msgs, sigs)
    expected = np.ones(40, dtype=bool)
    expected[7] = False
    assert (mask == expected).all()


def test_rlc_wrong_message_falls_back(rlc_on):
    pubkeys, msgs, sigs = make_batch(40)
    msgs[0] = b"tampered" + msgs[0][8:]
    msgs[13] = b"tampered" + msgs[13][8:]
    mask = B.verify_batch_jax(pubkeys, msgs, sigs)
    expected = np.ones(40, dtype=bool)
    expected[0] = expected[13] = False
    assert (mask == expected).all()


def test_rlc_invalid_encodings_and_precheck(rlc_on):
    pubkeys, msgs, sigs = make_batch(40)
    # non-canonical s (>= L): rejected host-side, excluded from the batch eq
    from tendermint_tpu.crypto.ed25519_ref import L

    s_big = (L + 5).to_bytes(32, "little")
    sigs[3] = sigs[3][:32] + s_big
    # invalid pubkey encoding (y >= p, not on curve)
    pubkeys[11] = b"\xff" * 32
    mask = B.verify_batch_jax(pubkeys, msgs, sigs)
    expected = np.ones(40, dtype=bool)
    expected[3] = expected[11] = False
    assert (mask == expected).all()


def test_rlc_matches_cpu_backend_on_mixed_validity(rlc_on):
    pubkeys, msgs, sigs = make_batch(40, seed=2)
    # corrupt a scattering of rows in different ways
    sigs[1] = sigs[2]  # signature for the wrong message/key
    msgs[20] = msgs[21]
    rng = np.random.default_rng(3)
    junk = bytes(rng.integers(0, 256, 64, dtype=np.uint8))
    sigs[39] = junk[:32] + (int.from_bytes(junk[32:], "little") % (1 << 250)).to_bytes(32, "little")
    got = B.verify_batch_jax(pubkeys, msgs, sigs)
    want = B.verify_batch_cpu(pubkeys, msgs, sigs)
    assert (got == want).all()


def make_mixed_batch(n, n_sr, seed=0, msg_len=40):
    """Interleaved ed25519/sr25519 rows (sr rows scattered, not a suffix)."""
    from tendermint_tpu.crypto.sr25519 import gen_sr25519

    pubkeys, msgs, sigs, types = [], [], [], []
    for i in range(n):
        sd = bytes([seed]) * 30 + bytes([i // 256, i % 256])
        msg = b"mix-%03d-" % i + b"y" * (msg_len - 8)
        if i % max(n // max(n_sr, 1), 1) == 1 and sum(
            1 for t in types if t == "sr25519"
        ) < n_sr:
            priv = gen_sr25519(sd)
            types.append("sr25519")
        else:
            priv = gen_ed25519(sd)
            types.append("ed25519")
        pubkeys.append(priv.pub_key().bytes())
        msgs.append(msg)
        sigs.append(priv.sign(msg))
    return pubkeys, msgs, sigs, types


@pytest.mark.heavy
def test_rlc_mixed_all_valid_device_path(rlc_on):
    pubkeys, msgs, sigs, types = make_mixed_batch(40, 10)
    mask = B.verify_batch(pubkeys, msgs, sigs, backend="jax", key_types=types)
    assert mask.all()
    assert B.LAST_JAX_PATH[0] == "rlc-mixed"
    assert B.LAST_RLC_TIMINGS.get("mode") == "mixed"
    # sr keys landed in the typed cache
    for pk, t in zip(pubkeys, types):
        assert B._cache_key(bytes(pk), t) in B._A_CACHE


@pytest.mark.heavy
def test_rlc_mixed_bad_rows_fall_back_to_exact_mask(rlc_on):
    pubkeys, msgs, sigs, types = make_mixed_batch(40, 10, seed=3)
    sr_rows = [i for i, t in enumerate(types) if t == "sr25519"]
    ed_rows = [i for i, t in enumerate(types) if t == "ed25519"]
    bad_sr, bad_ed = sr_rows[2], ed_rows[5]
    sigs[bad_sr] = sigs[bad_sr][:33] + bytes([sigs[bad_sr][33] ^ 1]) + sigs[bad_sr][34:]
    msgs[bad_ed] = b"tampered" + msgs[bad_ed][8:]
    mask = B.verify_batch(pubkeys, msgs, sigs, backend="jax", key_types=types)
    expected = np.ones(40, dtype=bool)
    expected[bad_sr] = expected[bad_ed] = False
    assert (mask == expected).all()


def test_rlc_mixed_matches_host_verifiers(rlc_on):
    from tendermint_tpu.crypto.keys import Ed25519PubKey
    from tendermint_tpu.crypto.sr25519 import sr25519_verify

    pubkeys, msgs, sigs, types = make_mixed_batch(32, 8, seed=5)
    # corrupt: sr sig without marker bit, ed invalid pubkey, swapped messages
    sr_rows = [i for i, t in enumerate(types) if t == "sr25519"]
    i0 = sr_rows[0]
    sigs[i0] = sigs[i0][:63] + bytes([sigs[i0][63] & 0x7F])  # clear marker
    msgs[2], msgs[3] = msgs[3], msgs[2]
    got = B.verify_batch(pubkeys, msgs, sigs, backend="jax", key_types=types)
    for i in range(32):
        if types[i] == "ed25519":
            want = Ed25519PubKey(bytes(pubkeys[i])).verify(bytes(msgs[i]), bytes(sigs[i]))
        else:
            want = sr25519_verify(bytes(pubkeys[i]), bytes(msgs[i]), bytes(sigs[i]))
        assert got[i] == want, (i, types[i])


def test_rlc_accepts_pure_torsion_defect_no_fallback(rlc_on):
    """The RLC batch equation is cofactored: a signature whose only defect
    is small torsion in R passes the combined check directly (no per-sig
    fallback), agreeing with the per-sig kernel and the host wrapper —
    the single framework predicate (advisor r3 medium)."""
    from tests.sigutil import torsion_defect_sig

    pubkeys, msgs, sigs = make_batch(12)
    a_enc, msg, sig = torsion_defect_sig(seed=11, msg=b"rlc-torsion-agreement")
    pubkeys.append(a_enc)
    msgs.append(msg)
    sigs.append(sig)
    mask = B.verify_batch_jax(pubkeys, msgs, sigs)
    assert mask.all()
    assert B.LAST_JAX_PATH[0] == "rlc"  # combined check passed, no fallback
