"""Native (C) host-prep kernels, built on first use.

The RLC batch path's host side — challenge hashing, scalar math, window
sort — was ~150 ms of Python/hashlib at 10k validators (PERF.md), more
than the device kernel it feeds. batchhost.c implements the three hot
loops as multithreaded C; this module compiles it once (gcc, cached by
source hash) and binds via ctypes. Everything degrades gracefully: if no
compiler is available or the build fails, `available()` is False and
callers keep their pure-Python paths.

Set TMTPU_NATIVE=0 to force the Python paths (differential testing).
"""

from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import subprocess
import tempfile
import threading

import numpy as np

_log = logging.getLogger("tendermint_tpu.native")
_LOCK = threading.Lock()
_LIB = None
_TRIED = False

_BASE = os.path.dirname(os.path.abspath(__file__))


def _default_threads() -> int:
    """`[crypto] prep_threads` default: min(cores, 8), env-overridable
    (TMTPU_PREP_THREADS) for differential tests that pin a thread count
    regardless of the host (ISSUE 18)."""
    env = os.environ.get("TMTPU_PREP_THREADS", "")
    if env:
        try:
            return max(1, min(64, int(env)))
        except ValueError:
            pass
    return min(8, os.cpu_count() or 1)


_NTHREADS = _default_threads()


def prep_threads() -> int:
    """The thread count every native driver currently runs with."""
    return _NTHREADS


def configure_prep_threads(n: "int | None") -> int:
    """Set the prep thread count (None/0 = host default) and resize the
    persistent in-library worker pool to match. Safe before the library
    is built: the pool is (re)spun on first successful load too."""
    global _NTHREADS
    _NTHREADS = _default_threads() if not n else max(1, min(64, int(n)))
    lib = _lib()
    if lib is not None:
        lib.tm_prep_pool_configure(_NTHREADS)
    return _NTHREADS


def prep_pool_size() -> int:
    """Live size of the native worker pool (1 = serial/per-call path)."""
    lib = _lib()
    return int(lib.tm_prep_pool_size()) if lib is not None else 1


def _build() -> "ctypes.CDLL | None":
    srcs = [os.path.join(_BASE, "batchhost.c"), os.path.join(_BASE, "sr25519.c")]
    h = hashlib.sha256()
    # gen_constants.py is IN the tag: the generated headers carry curve
    # constants the verifier's correctness depends on, so an edit to the
    # generator must invalidate both the cached .so and the cached headers.
    for src in srcs + [os.path.join(_BASE, "gen_constants.py")]:
        with open(src, "rb") as f:
            h.update(f.read())
    tag = h.hexdigest()[:16]
    build_dir = os.path.join(_BASE, "_build")
    so_path = os.path.join(build_dir, f"batchhost-{tag}.so")
    if not os.path.exists(so_path):
        os.makedirs(build_dir, exist_ok=True)
        from tendermint_tpu.native.gen_constants import generate, generate_ed

        for hdr_name, gen in [
            ("sha512_constants.h", generate),
            ("ed25519_constants.h", generate_ed),
        ]:
            # regenerate whenever the .so for this tag is missing (headers
            # are cheap; existence-caching kept stale constants alive)
            hdr = os.path.join(build_dir, hdr_name)
            fd, tmp = tempfile.mkstemp(dir=build_dir, prefix=".hdr-")
            with os.fdopen(fd, "w") as f:
                f.write(gen())
            os.replace(tmp, hdr)
        fd, tmp = tempfile.mkstemp(dir=build_dir, prefix=".so-", suffix=".so")
        os.close(fd)
        cc = os.environ.get("CC", "gcc")
        cmd = [
            cc, "-O3", "-shared", "-fPIC", "-pthread",
            "-I", build_dir, *srcs, "-o", tmp,
        ]
        try:
            subprocess.run(
                cmd, check=True, capture_output=True, text=True, timeout=120
            )
        except Exception as e:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            _log.warning("native batchhost build failed (%s); using Python paths", e)
            return None
        os.replace(tmp, so_path)
    try:
        lib = ctypes.CDLL(so_path)
    except OSError as e:
        _log.warning("native batchhost load failed (%s); using Python paths", e)
        return None
    u8p = ctypes.POINTER(ctypes.c_uint8)
    i64p = ctypes.POINTER(ctypes.c_int64)
    i32p = ctypes.POINTER(ctypes.c_int32)
    lib.tm_ed25519_h_batch.argtypes = [u8p, u8p, u8p, i64p, ctypes.c_int64, u8p, ctypes.c_int]
    lib.tm_sha256.argtypes = [u8p, ctypes.c_int64, u8p]
    lib.tm_sha256.restype = None
    lib.tm_memo_digest_batch.argtypes = [
        ctypes.c_uint8, u8p, i64p, i32p, u8p, i64p, u8p, i64p, u8p, i64p,
        ctypes.c_int64, u8p, ctypes.c_int,
    ]
    lib.tm_memo_digest_batch.restype = None
    lib.tm_rlc_scalars.argtypes = [u8p, u8p, u8p, ctypes.c_int64, u8p, u8p, ctypes.c_int]
    lib.tm_sort_windows.argtypes = [u8p, ctypes.c_int64, i32p, i32p, ctypes.c_int, ctypes.c_int64]
    lib.tm_vote_sign_bytes.argtypes = [
        u8p, ctypes.c_int64, u8p, i64p, ctypes.c_int64, i32p, i64p,
        u8p, ctypes.c_int64, ctypes.c_int64, u8p, i64p,
    ]
    lib.tm_vote_sign_bytes.restype = ctypes.c_int64
    lib.tm_sr25519_verify_one.argtypes = [u8p, u8p, ctypes.c_int64, u8p]
    lib.tm_sr25519_verify_one.restype = ctypes.c_int
    lib.tm_sr25519_verify_batch.argtypes = [u8p, u8p, i64p, u8p, ctypes.c_int64, u8p, ctypes.c_int]
    lib.tm_prep_pool_configure.argtypes = [ctypes.c_int]
    lib.tm_prep_pool_configure.restype = ctypes.c_int
    lib.tm_prep_pool_size.argtypes = []
    lib.tm_prep_pool_size.restype = ctypes.c_int
    # park the worker pool at the configured width so the first flush
    # never pays pthread_create (drivers fall back to per-call threads
    # whenever the pool is busy or n == 1 thread is wanted)
    if _NTHREADS > 1:
        lib.tm_prep_pool_configure(_NTHREADS)
    return lib


def _lib() -> "ctypes.CDLL | None":
    global _LIB, _TRIED
    if _TRIED:
        return _LIB
    with _LOCK:
        if not _TRIED:
            if os.environ.get("TMTPU_NATIVE", "1") == "0":
                _LIB = None
            else:
                try:
                    _LIB = _build()
                except Exception:
                    _log.exception("native batchhost unavailable; using Python paths")
                    _LIB = None
            globals()["_TRIED"] = True
    return _LIB


def available() -> bool:
    return _lib() is not None


def _u8p(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8))


def ed25519_h_batch(
    sigs_blob: bytes, pks_blob: bytes, msgs_blob: bytes, moffs: np.ndarray
) -> np.ndarray:
    """h_i = SHA-512(R_i || A_i || M_i) mod L for n rows.

    sigs_blob: n*64 bytes (R = first 32 of each sig); pks_blob: n*32;
    msgs_blob: concatenated messages with moffs (n+1,) int64 offsets.
    Returns (n, 32) uint8 little-endian. Replaces the reference's per-row
    hashing inside its serial verify loop (types/validator_set.go:690)."""
    lib = _lib()
    assert lib is not None
    n = len(moffs) - 1
    out = np.empty((n, 32), dtype=np.uint8)
    sigs = np.frombuffer(sigs_blob, dtype=np.uint8)
    pks = np.frombuffer(pks_blob, dtype=np.uint8)
    msgs = np.frombuffer(msgs_blob, dtype=np.uint8) if msgs_blob else np.zeros(1, np.uint8)
    moffs = np.ascontiguousarray(moffs, dtype=np.int64)
    lib.tm_ed25519_h_batch(
        _u8p(sigs), _u8p(pks), _u8p(msgs),
        moffs.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        n, _u8p(out), _NTHREADS,
    )
    return out


def _column(rows, n: int):
    """n bytes-like rows joined once -> (uint8 array, (n+1,) int64 offsets)."""
    blob = b"".join(rows)
    lens = np.fromiter(map(len, rows), dtype=np.int64, count=n)
    if lens.sum() != len(blob):  # a memoryview whose len() counts items, not bytes
        lens = np.fromiter((len(bytes(r)) for r in rows), dtype=np.int64, count=n)
    if n and int(lens.max()) >> 32:
        raise OverflowError("a row part of 4 GiB or more has no 32-bit length frame")
    offs = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(lens, out=offs[1:])
    return np.frombuffer(blob or b"\0", dtype=np.uint8), offs


def memo_digest_batch(mode: int, key_types: list, kt_idx, pubkeys, msgs, sigs) -> bytes:
    """The verified-row memo's digest of n rows in one pass: row i's is
    SHA-256(mode || le32(len kt) || kt || le32(len pk) || pk || le32(len msg)
    || msg || le32(len sig) || sig) (crypto/batch.VerifiedRowMemo.digest_rows).

    key_types: the distinct key-type strings; kt_idx (n,) int32 indexes them
    row by row, or None where every row has key_types[0]. pubkeys, msgs,
    sigs: n bytes-like rows each. Returns n*32 bytes, row i's at [32i, 32i+32)."""
    lib = _lib()
    assert lib is not None
    n = len(pubkeys)
    if not len(msgs) == len(sigs) == n:
        raise ValueError("pubkeys/msgs/sigs length mismatch")
    if n and not key_types:
        raise ValueError("rows need at least one key type")
    i64p = ctypes.POINTER(ctypes.c_int64)
    kts, kt_offs = _column([t.encode() for t in key_types], len(key_types))
    idx = None
    if kt_idx is not None:
        kt_idx = np.ascontiguousarray(kt_idx, dtype=np.int32)
        if kt_idx.shape != (n,) or (n and not 0 <= kt_idx.min() <= kt_idx.max() < len(key_types)):
            raise ValueError("kt_idx must index key_types, one entry a row")
        idx = kt_idx.ctypes.data_as(ctypes.POINTER(ctypes.c_int32))
    cols = [_column(c, n) for c in (pubkeys, msgs, sigs)]
    out = np.empty(32 * n or 1, dtype=np.uint8)
    args = [a for blob, offs in cols for a in (_u8p(blob), offs.ctypes.data_as(i64p))]
    lib.tm_memo_digest_batch(
        mode, _u8p(kts), kt_offs.ctypes.data_as(i64p), idx, *args, n, _u8p(out), _NTHREADS
    )
    return out[: 32 * n].tobytes()


def vote_sign_bytes(
    prefix: bytes, parts: list, sel: np.ndarray, ts_ns: np.ndarray, suffix: bytes
):
    """n length-delimited CanonicalVote rows in one pass: row i is
    len || prefix || parts[sel[i]] || timestamp(ts_ns[i]) || suffix
    (types/canonical.vote_sign_bytes_many states the encoding).

    parts: encoded block-id parts (tag 4 + length + body, or b"" for nil);
    sel (n,) int32 indices into it; ts_ns (n,) int64. Returns (blob, offs):
    row i is blob[offs[i]:offs[i+1]], offs (n+1,) int64. The buffer holds n
    rows of the longest possible length, so the loop cannot overrun it."""
    lib = _lib()
    assert lib is not None
    n = len(ts_ns)
    sel = np.ascontiguousarray(sel, dtype=np.int32)
    ts_ns = np.ascontiguousarray(ts_ns, dtype=np.int64)
    if sel.shape != (n,) or ts_ns.shape != (n,):
        raise ValueError("sel and ts_ns must be one column of n rows each")
    part_offs = np.cumsum([0] + [len(p) for p in parts], dtype=np.int64)
    parts_arr = np.frombuffer(b"".join(parts) or b"\0", dtype=np.uint8)
    # body: prefix, part, tag 5 + length, timestamp (<= 17), suffix; the
    # outer length varint of a 63-bit size is <= 9 bytes
    longest = len(prefix) + max(map(len, parts), default=0) + 2 + 17 + len(suffix)
    out = np.empty(n * (longest + 9) or 1, dtype=np.uint8)
    offs = np.empty(n + 1, dtype=np.int64)
    pre = np.frombuffer(prefix or b"\0", dtype=np.uint8)
    suf = np.frombuffer(suffix or b"\0", dtype=np.uint8)
    i64p = ctypes.POINTER(ctypes.c_int64)
    total = lib.tm_vote_sign_bytes(
        _u8p(pre), len(prefix), _u8p(parts_arr), part_offs.ctypes.data_as(i64p),
        len(parts), sel.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        ts_ns.ctypes.data_as(i64p), _u8p(suf), len(suffix), n, _u8p(out),
        offs.ctypes.data_as(i64p),
    )
    if total < 0:
        raise ValueError("sel indexes outside parts")
    assert total <= out.size
    return out[:total].tobytes(), offs


def rlc_scalars(z16: np.ndarray, h32: np.ndarray, s32: np.ndarray):
    """w_i = z_i*h_i mod 8L; u = sum z_i*s_i mod L. Rows with z == 0 are
    excluded (w = 0, no contribution to u).

    z16 (n,16), h32 (n,32), s32 (n,32) uint8 LE -> (w (n,32) uint8, u int)."""
    lib = _lib()
    assert lib is not None
    n = z16.shape[0]
    w = np.empty((n, 32), dtype=np.uint8)
    u = np.empty(32, dtype=np.uint8)
    z16 = np.ascontiguousarray(z16, dtype=np.uint8)
    h32 = np.ascontiguousarray(h32, dtype=np.uint8)
    s32 = np.ascontiguousarray(s32, dtype=np.uint8)
    lib.tm_rlc_scalars(_u8p(z16), _u8p(h32), _u8p(s32), n, _u8p(w), _u8p(u), _NTHREADS)
    return w, int.from_bytes(u.tobytes(), "little")


def sr25519_verify(pub: bytes, msg: bytes, sig: bytes) -> bool:
    """Native schnorrkel verification (see sr25519.c; mirrors
    crypto/sr25519.sr25519_verify bit-for-bit, differentially tested)."""
    lib = _lib()
    assert lib is not None
    if len(pub) != 32 or len(sig) != 64:
        return False
    p = np.frombuffer(pub, dtype=np.uint8)
    m = np.frombuffer(msg, dtype=np.uint8) if msg else np.zeros(1, np.uint8)
    s = np.frombuffer(sig, dtype=np.uint8)
    return bool(lib.tm_sr25519_verify_one(_u8p(p), _u8p(m), len(msg), _u8p(s)))


def sr25519_verify_batch(
    pks_blob: bytes, msgs_blob: bytes, moffs: np.ndarray, sigs_blob: bytes
) -> np.ndarray:
    """Batched native schnorrkel verification -> bool mask (n,)."""
    lib = _lib()
    assert lib is not None
    n = len(moffs) - 1
    out = np.empty(n, dtype=np.uint8)
    pks = np.frombuffer(pks_blob, dtype=np.uint8)
    sigs = np.frombuffer(sigs_blob, dtype=np.uint8)
    msgs = np.frombuffer(msgs_blob, dtype=np.uint8) if msgs_blob else np.zeros(1, np.uint8)
    moffs = np.ascontiguousarray(moffs, dtype=np.int64)
    lib.tm_sr25519_verify_batch(
        _u8p(pks), _u8p(msgs),
        moffs.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        _u8p(sigs), n, _u8p(out), _NTHREADS,
    )
    return out.astype(bool)


def sort_windows(digits: np.ndarray, zero16_from: int = 0):
    """Per-window counting sort: digits (n, 32) uint8 row-major ->
    (perm (32, n) int32 stable, ends (32, 256) int32). Same contract as
    ops/msm_jax.sort_windows (which downcasts perm for the wire).
    zero16_from > 0 promises rows >= it are zero in windows 16-31 (the
    RLC z-lane is 128-bit), skipping their count pass."""
    lib = _lib()
    assert lib is not None
    n = digits.shape[0]
    digits = np.ascontiguousarray(digits, dtype=np.uint8)
    perm = np.empty((32, n), dtype=np.int32)
    ends = np.empty((32, 256), dtype=np.int32)
    i32p = ctypes.POINTER(ctypes.c_int32)
    lib.tm_sort_windows(
        _u8p(digits), n,
        perm.ctypes.data_as(i32p), ends.ctypes.data_as(i32p), _NTHREADS,
        int(zero16_from),
    )
    return perm, ends
