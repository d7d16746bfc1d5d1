"""Batch signature verification — the framework's north-star interface.

`verify_batch(pubkeys, msgs, sigs) -> bool mask` with two backends:

- "cpu": serial host loop over OpenSSL (the reference-shaped baseline — this is
  exactly what the reference does in Go, one VerifySignature per validator,
  reference: types/validator_set.go:680-702).
- "jax": the TPU path. Large batches take the random-linear-combination fast
  path (ops/msm_jax.py): ONE Pippenger multiscalar check over random 128-bit
  coefficients, ~10x less device work than per-signature ladders; if the
  combined check fails (any bad signature present), it falls back to the
  per-signature kernel (ops/ed25519_jax.py) to recover the exact mask.
  Decompressed public keys are cached across calls (consensus re-verifies
  the same validator set every height), which removes ~1/3 of the device
  work in steady state.

Verification semantics are COFACTORED (ZIP-215-style) with canonical
encodings and s < L on EVERY backend and path — cpu (OpenSSL fast path +
pure-Python cofactored referee on reject), per-sig kernel, and RLC — so the
accept/reject outcome never depends on which path or backend a node runs
(see crypto/ed25519_ref.verify_cofactored). The reference's cofactorless
loop (types/validator_set.go:680-702) agrees on all torsion-free (i.e. all
honest) inputs.

Every O(validators) verification site in the framework (VerifyCommit,
VerifyCommitLight/Trusting, vote storms, fast-sync replay, evidence) funnels
through this module.
"""

from __future__ import annotations

import contextlib
import hashlib
import os
import threading
import time
from typing import List, NamedTuple, Optional, Sequence

import numpy as np

from tendermint_tpu.crypto.circuit_breaker import VerifyCircuitBreaker
from tendermint_tpu.crypto.ed25519_ref import L
from tendermint_tpu.libs import forensics as _forensics
from tendermint_tpu.libs import trace as _trace

L8 = 8 * L  # full curve-group order; scalar modulus for torsion-exact RLC

# ---------------------------------------------------------------------------
# Device fault injection (chaos engine) + the verify-path circuit breaker.
#
# `_device_fault(site)` is called at every device entry point (RLC submit,
# RLC finish/sync, the per-signature kernel, the breaker's health probe);
# the chaos engine installs a hook there (chaos/device.DeviceFaultInjector)
# that can raise or hang to model a sick accelerator, exercising the full
# degradation ladder: RLC -> per-sig -> CPU -> breaker-OPEN (sticky CPU).
#
# The BREAKER makes persistent failure sticky: `_flush_route` gates the jax
# path on `allow_device()`, `_verify_batch_routed` records every device
# flush outcome and degrades a failed flush to the host loop instead of
# raising into the consensus receive loop. A daemon probe thread re-arms the
# device path
# (crypto/circuit_breaker.py; config: `[crypto] breaker_*`).

_DEVICE_FAULT_HOOK = None  # callable(site: str) -> None; may raise/sleep


def set_device_fault_hook(fn) -> None:
    """Install (or clear, with None) the chaos device-fault hook."""
    global _DEVICE_FAULT_HOOK
    _DEVICE_FAULT_HOOK = fn


def _device_fault(site: str) -> None:
    # Forensics heartbeat FIRST: the phase stamp must land before anything
    # that can hang (the injected hook below models exactly that), so a
    # wedged flush leaves its phase in the mmap'd ring for the watchdog /
    # bench parent to read (libs/forensics.py). One None check when
    # forensics is not configured.
    _forensics.beat(site)
    hook = _DEVICE_FAULT_HOOK
    if hook is not None:
        hook(site)


def _degrade_flush_to_cpu(pubkeys, msgs, sigs, exc: BaseException) -> np.ndarray:
    """The in-flush ladder (RLC -> per-sig) is exhausted: the device itself
    is failing. Record the failure toward the breaker's trip, then recompute
    THIS flush on the host — the consensus receive loop must never see a
    device error. Shared by the sync route and the async finish path so the
    two degrade identically."""
    BREAKER.record_failure(repr(exc))
    import logging

    logging.getLogger("tendermint_tpu.crypto.batch").exception(
        "device verification failed; degrading flush to CPU"
    )
    return verify_batch_cpu(pubkeys, msgs, sigs)


def _breaker_probe() -> None:
    """Health probe for the OPEN breaker: one tiny device round trip through
    the same fault hook real flushes pass (chaos-injected device faults keep
    the breaker open). Deliberately compile-free — a device_put + fetch
    answers 'is the device alive', which is the observed failure mode
    (round 5: even a tiny dispatch never returned)."""
    _device_fault("probe")
    import jax

    np.asarray(jax.device_put(np.arange(8, dtype=np.int32)))


BREAKER = VerifyCircuitBreaker(probe=_breaker_probe)


def configure_breaker(**kwargs) -> None:
    """Apply `[crypto]` breaker config (node/node.py)."""
    BREAKER.configure(**kwargs)


def configure_mesh_health(**kwargs) -> None:
    """Apply `[crypto] mesh_health_*` config (node/node.py): the elastic
    mesh's per-device scoring thresholds and rejoin hysteresis
    (parallel/health.py)."""
    from tendermint_tpu.parallel import health as _mh

    _mh.MESH_HEALTH.configure(**kwargs)


def record_backend_rows(backend: str, rows: int) -> None:
    """One (rows, flush) observation on the per-signature-scheme series
    (tendermint_batch_verify_backend_*): every routing site that settles
    rows of a scheme calls this exactly once for them, so BLS/sr25519
    volume never folds into the ed25519 headline.
    types/validator_set.verify_aggregate_commit records the aggregate path
    (each covered signer counts as one row)."""
    from tendermint_tpu.libs import metrics as _metrics

    m = _metrics.batch_metrics()
    m.backend_rows.labels(backend).inc(rows)
    m.backend_flushes.labels(backend).inc()

_BUCKET_SIZES = [2**i for i in range(17)]  # jit shape buckets: 1..65536


def _bucket(n: int) -> int:
    for b in _BUCKET_SIZES:
        if n <= b:
            return b
    return n


# RLC fast-path lane buckets (A-block size Na; total lanes = 2*Na). Coarse to
# bound the number of compiled kernel shapes; ~25% max padding waste.
_LANE_BUCKETS = [
    64, 256, 512, 1024, 1536, 2048, 3072, 4096, 5120, 6144, 8192,
    10240, 12288, 16384, 20480, 24576, 32768,
]


def _lane_bucket(m: int) -> int:
    for b in _LANE_BUCKETS:
        if m <= b:
            return b
    return m


# Minimum batch size for the RLC path: below this the per-signature kernel's
# latency is fine and each extra RLC shape costs a long one-time compile.
RLC_MIN = 512

# ---------------------------------------------------------------------------
# Streamed flush planner (ISSUE 13). The lane-bucket ladder above tops out at
# 32,768 lanes; anything larger used to fall into an unbounded one-off
# compile whose device temp footprint scales with the workload (a
# 100k-validator commit is ~200k lanes, ~10x the 10k commit's footprint).
# The RLC combined check is a SUM over lanes, so an arbitrarily large flush
# decomposes exactly into fixed-bucket chunks: each chunk runs the full
# Pippenger pipeline WITHOUT the identity check (ops/msm_jax.py
# rlc_partial_submit), partial points accumulate ON DEVICE via a tiny padd
# fold, and one identity check at the end delivers the combined verdict —
# workload size unbounded, device footprint constant at the chunk bucket.
#
# Chunks stream DOUBLE-BUFFERED: the native C host prep (hashing, scalars,
# window sort) of chunk k+1 runs on a prep worker thread while chunk k's
# kernels execute, and a chunk's lane-validity sync throttles submission so
# lanes in flight never exceed 2 chunks. Each chunk carries its own B lane
# with scalar (L - u_k): the basepoint has order L, so the per-chunk B terms
# sum to the single flush's one ((L - Σu_k) mod L)·B term exactly — the
# combined-check verdict, the exact-mask failure recovery, and every
# consumer's verdict slice are byte-identical to a hypothetical single
# flush. Config: `[crypto] max_flush_lanes` (node/node.py configure_planner).

def _planner_env_default() -> int:
    """TMTPU_MAX_FLUSH_LANES with the SAME normalization configure_planner
    enforces (floor 8, even) — a degenerate env value must not ship a
    planner whose chunk size is zero or negative."""
    try:
        v = int(os.environ.get("TMTPU_MAX_FLUSH_LANES", "24576"))
    except ValueError:
        v = 24576
    return max(8, v) & ~1


_PLANNER = {"max_flush_lanes": _planner_env_default()}


def configure_planner(max_flush_lanes: int | None = None) -> None:
    """Apply `[crypto]` planner config (node/node.py). Process-global, last
    node wins — the same model as the breaker and the verify mode."""
    if max_flush_lanes is not None:
        v = int(max_flush_lanes)
        if v < 8:
            # 8 is the structural floor (>= 1 row + B lane per half);
            # production budgets live at bucket scale (default 24576)
            raise ValueError(f"max_flush_lanes {v} < 8")
        _PLANNER["max_flush_lanes"] = v & ~1  # even: A block + R block


def planner_budget() -> int:
    """Device budget per flush, in MSM lanes (A + B + R + pads)."""
    return _PLANNER["max_flush_lanes"]


def planner_chunk_rows() -> int:
    """Signature rows per streamed chunk: half the lane budget is the A
    block (rows + this chunk's B lane), the other half the R block."""
    return planner_budget() // 2 - 1


def planner_engaged(n: int) -> bool:
    """Does an n-row flush stream through the planner? True exactly when a
    single flush would exceed the lane budget."""
    return n > planner_chunk_rows()


def _planner_chunks(n: int) -> list:
    """[(lo, hi), ...] row spans; every chunk pads to the SAME lane bucket
    (one warm compiled shape — prewarm covers it), ragged tail included."""
    c = planner_chunk_rows()
    return [(lo, min(lo + c, n)) for lo in range(0, n, c)]


_PREP_POOL = None  # lazy single-thread executor: the planner's prep worker
_PREP_POOL_LOCK = threading.Lock()


def _prep_pool():
    global _PREP_POOL
    if _PREP_POOL is None:
        with _PREP_POOL_LOCK:
            if _PREP_POOL is None:  # two first-streamed-flush threads racing
                from concurrent.futures import ThreadPoolExecutor

                _PREP_POOL = ThreadPoolExecutor(
                    max_workers=1, thread_name_prefix="flush-prep"
                )
    return _PREP_POOL


# ---------------------------------------------------------------------------
# Stage-overlapped host prep (ISSUE 18). Two settings, both `[crypto]` config
# (node/node.py configure_prep) with an env override each:
#
#   stream_floor  minimum rows at which an IN-budget flush rides the flush
#                 planner's one warm chunk bucket as ONE chunk
#                 (_verify_batch_pipelined): no per-size shape compiles,
#                 for the flush or for its recovery ladder. Default 2048:
#                 below it the per-size `rlc` program's own, smaller lane
#                 bucket is cheaper; keeps tiny test planner budgets out.
#                 Tests and the benchmark's CPU rehearsal move it to reach
#                 either side at a few rows.
#   host_stripe   stripe the HOST (no-device) RLC fallback so stripe k+1's
#                 prep overlaps stripe k's Pippenger MSM. "auto" (default)
#                 stripes only on multi-core hosts: on one core the overlap
#                 is pure time-slicing, and splitting the MSM costs real
#                 wall (~13% on all-distinct keys; up to ~2.4x on heavily
#                 repeated signers, where cross-stripe per-signer
#                 coefficient collapse is lost). True/False force it.
#
# `_rlc_submit` stages its host prep (challenge hashing on the prep pool
# while the dispatch thread assembles lanes and uploads the A block; only
# the MSM gather waits on the window sort) wherever the native library is
# present and the rows are all ed25519: it chooses by what it sees, and
# there is no switch.

def _host_stripe_env(default: str = "auto"):
    v = os.environ.get("TMTPU_HOST_STRIPE", default)
    if v == "0":
        return False
    if v in ("auto", ""):
        return "auto"
    return True


_PREP_CFG = {
    "stream_floor": max(
        1, int(os.environ.get("TMTPU_PREP_STREAM_FLOOR", "2048") or 2048)
    ),
    "host_stripe": _host_stripe_env(),
}


def configure_prep(
    prep_threads: int | None = None,
    stream_floor: int | None = None,
    host_stripe=None,
) -> None:
    """Apply `[crypto]` prep-pipeline config (node/node.py). Process-global,
    last node wins — the same model as configure_planner. prep_threads
    resizes the NATIVE worker pool (0/None = host default, min(cores, 8)).
    host_stripe takes True/False/"auto" (auto = stripe the host RLC
    fallback only when the host has more than one core)."""
    if prep_threads is not None:
        from tendermint_tpu import native

        native.configure_prep_threads(prep_threads or None)
    if stream_floor is not None:
        _PREP_CFG["stream_floor"] = max(1, int(stream_floor))
    if host_stripe is not None:
        _PREP_CFG["host_stripe"] = (
            "auto" if host_stripe == "auto" else bool(host_stripe)
        )


def _stream_floor() -> int:
    return _PREP_CFG["stream_floor"]


def _host_stripe_on() -> bool:
    v = _PREP_CFG["host_stripe"]
    if v == "auto":
        return (os.cpu_count() or 1) > 1
    return bool(v)


# Hot-path budget counter: rows challenge-hashed, ever (tests/
# test_prep_pipeline.py pins hashes-per-row <= once per flush). Plain int
# in a list for lock-free += from the prep pool (GIL-atomic enough for a
# test-budget counter; never read on the hot path).
HASH_ROWS_HASHED = [0]


def _overlap_seconds(spans, busy) -> float:
    """Windowed overlap accounting: Σ over prep-task spans [s, e) of their
    intersection with the UNION of device-busy intervals. Replaces the
    `prep_s - blocked` heuristic, which undercounts whenever the dispatch
    thread blocks on the prep future while kernels are still executing
    (a streamed flush's later chunks)."""
    if not spans or not busy:
        return 0.0
    merged = []
    for s, e in sorted(busy):
        if merged and s <= merged[-1][1]:
            merged[-1] = (merged[-1][0], max(merged[-1][1], e))
        else:
            merged.append((s, e))
    total = 0.0
    for s, e in spans:
        for bs, be in merged:
            lo, hi = max(s, bs), min(e, be)
            if lo < hi:
                total += hi - lo
    return total


# ---------------------------------------------------------------------------
# Cross-flush verified-row memo (ISSUE 18). A bounded LRU of digests of
# (key_type, pubkey, msg, sig) rows that verified OK: a commit assembled
# from deferred-verified live votes re-verifies the SAME rows the vote path
# already flushed, so consulting the memo first shrinks the commit flush to
# the unseen residue (typically zero rows on the self-committed path).
# Safety: only rows whose verdict was True are ever inserted (a flush that
# raises inserts nothing), the digest is length-framed over every verdict
# input INCLUDING the verify mode — a tampered byte anywhere produces a
# different digest and misses — and capacity 0 disables the memo entirely.


class VerifiedRowMemo:
    """Bounded LRU of verified-row digests. Thread-safe (scheduler lanes,
    light workers and the consensus event loop all consult it)."""

    def __init__(self, capacity: int = 65536):
        from collections import OrderedDict

        self.capacity = max(0, int(capacity))
        self._rows: "OrderedDict[bytes, None]" = OrderedDict()
        self._lock = threading.Lock()
        self.hits = 0
        self.misses = 0
        self.insertions = 0
        self.evictions = 0

    def digest_rows(self, pubkeys, msgs, sigs, key_types=None) -> list:
        """Length-framed SHA-256 per row: SHA-256(mode || le32(len kt) || kt
        || le32(len pk) || pk || le32(len msg) || msg || le32(len sig) ||
        sig). The frame prevents boundary ambiguity (pk||msg splits are not
        unique); the mode byte keeps cofactored and cofactorless
        (reference-exact) verdicts from ever aliasing each other across a
        set_verify_mode flip. One threaded native pass over the rows where
        the library loaded, else `_digest_rows_py`: the same digests."""
        from tendermint_tpu import native
        from tendermint_tpu.crypto.keys import cofactorless_mode

        if not native.available():
            return self._digest_rows_py(pubkeys, msgs, sigs, key_types)
        n = len(pubkeys)
        table = ["ed25519"] if key_types is None else list(dict.fromkeys(key_types))
        idx = None
        if len(table) > 1:
            pos = {t: k for k, t in enumerate(table)}
            idx = np.fromiter(map(pos.__getitem__, key_types), dtype=np.int32, count=n)
        blob = native.memo_digest_batch(
            1 if cofactorless_mode() else 0, table, idx, pubkeys, msgs, sigs
        )
        return [blob[i : i + 32] for i in range(0, 32 * n, 32)]

    def _digest_rows_py(self, pubkeys, msgs, sigs, key_types=None) -> list:
        """`digest_rows` a row at a time with hashlib."""
        from tendermint_tpu.crypto.keys import cofactorless_mode

        mode = b"\x01" if cofactorless_mode() else b"\x00"
        sha = hashlib.sha256
        out = []
        for i in range(len(pubkeys)):
            kt = (key_types[i] if key_types is not None else "ed25519").encode()
            pk, msg, sig = bytes(pubkeys[i]), bytes(msgs[i]), bytes(sigs[i])
            h = sha(mode)
            for part in (kt, pk, msg, sig):
                h.update(len(part).to_bytes(4, "little"))
                h.update(part)
            out.append(h.digest())
        return out

    def lookup(self, digests) -> np.ndarray:
        """Per-row hit mask; hits are LRU-refreshed and counted into the
        tendermint_batch_verify_memo_hits_total series."""
        out = np.zeros(len(digests), dtype=bool)
        if self.capacity == 0 or not digests:
            return out
        with self._lock:
            rows = self._rows
            for i, d in enumerate(digests):
                if d in rows:
                    rows.move_to_end(d)
                    out[i] = True
        nh = int(out.sum())
        self.hits += nh
        self.misses += len(digests) - nh
        if nh:
            from tendermint_tpu.libs import metrics as _metrics

            _metrics.batch_metrics().memo_hits.inc(nh)
        return out

    def insert(self, digests, mask) -> int:
        """Record verified rows: ONLY rows whose verdict is True — failed
        rows never enter, and callers skip insert entirely on exceptions
        (never-cache-on-failure). Returns the rows newly inserted."""
        if self.capacity == 0 or digests is None:
            return 0
        with self._lock:
            rows = self._rows
            before = self.insertions
            for i, d in enumerate(digests):
                if not mask[i]:
                    continue
                if d in rows:
                    rows.move_to_end(d)
                    continue
                rows[d] = None
                self.insertions += 1
                if len(rows) > self.capacity:
                    rows.popitem(last=False)
                    self.evictions += 1
            return self.insertions - before

    def __len__(self) -> int:
        with self._lock:
            return len(self._rows)

    def __contains__(self, digest: bytes) -> bool:
        with self._lock:
            return digest in self._rows

    def clear(self) -> None:
        with self._lock:
            self._rows.clear()

    def stats(self) -> dict:
        with self._lock:
            size = len(self._rows)
        return {
            "capacity": self.capacity,
            "rows": size,
            "hits": self.hits,
            "misses": self.misses,
            "insertions": self.insertions,
            "evictions": self.evictions,
        }


def _memo_env_rows() -> int:
    try:
        return int(os.environ.get("TMTPU_VERIFIED_MEMO_ROWS", "65536"))
    except ValueError:
        return 65536


_MEMO = VerifiedRowMemo(_memo_env_rows())


def configure_verified_memo(rows: int | None = None) -> None:
    """Apply `[crypto] verified_memo_rows` (node/node.py). Resizing REPLACES
    the memo — cached verdicts never outlive a capacity change."""
    global _MEMO
    if rows is not None:
        _MEMO = VerifiedRowMemo(rows)


def verified_memo_stats() -> dict:
    return _MEMO.stats()

# Below this, auto-selected "jax" routes to the host loop instead. A one-shot
# small batch is round-trip-latency-bound (the device answer costs a round
# trip + dispatch regardless of size), so there is a crossover vs the
# ~115us/sig host loop; the value is carried over from an earlier runtime,
# not re-measured on today's chip. Live consensus accumulates votes and
# flushes at validator-set size (types/vote_set.py), so real flushes land
# above this threshold.
_JAX_MIN_BATCH = 256


def backend_default() -> str:
    from tendermint_tpu.crypto.keys import cofactorless_mode

    if cofactorless_mode():
        # Reference-exact (cofactorless) interop mode: the device kernels
        # are cofactored by construction, so default-routed verification
        # stays on the host (crypto/keys.Ed25519PubKey.verify, which skips
        # the cofactored referee in this mode). Explicit backend="jax"
        # requests are still honored (and stay cofactored).
        return "cpu"
    env = os.environ.get("TMTPU_CRYPTO_BACKEND")
    if env:
        return env
    try:
        import jax  # noqa: F401

        return "jax"
    except Exception:  # pragma: no cover
        return "cpu"


# Host-side RLC (ISSUE 11): the same torsion-exact combined check the
# device runs, evaluated with a pure-host Pippenger MSM. On wheel-less
# CPU-backend hosts the serial loop pays ~milliseconds PER signature in the
# pure-Python ladder; the combined check costs ~tens of point-adds per
# signature, so large host flushes (the scheduler's admission lane, the
# breaker's cpu degrade) go an order of magnitude faster. Exactness: the
# coefficients are ≡ 0 (mod 8) (_sample_z), so every passing row's
# cofactor-torsion defect is annihilated and an all-pass batch verifies the
# combined equation EXACTLY; any failure falls back to the serial loop for
# the exact per-row mask (same contract as the device RLC ladder).
_HOST_RLC_MIN = 48

# decompressed-pubkey cache for the host path (the admission workload
# re-verifies few distinct signers; consensus re-verifies one valset)
_HOST_PT_CACHE: dict = {}
_HOST_PT_CACHE_MAX = 8192


def _host_point(pk: bytes):
    """Cached ed25519_ref decompression (None = invalid encoding)."""
    pt = _HOST_PT_CACHE.get(pk, False)
    if pt is False:
        from tendermint_tpu.crypto.ed25519_ref import point_decompress

        pt = point_decompress(pk)
        if len(_HOST_PT_CACHE) >= _HOST_PT_CACHE_MAX:
            _HOST_PT_CACHE.clear()
        _HOST_PT_CACHE[pk] = pt
    return pt


def _host_msm(pairs, window: int = 0):
    """Σ s·P over ed25519_ref extended points — windowed bucket (Pippenger)
    MSM, MSB-first with running doubles. `pairs`: [(point, scalar int)],
    zero scalars skipped. window=0 picks the width minimizing the modeled
    add count (bucket folds dominate small batches, digit adds large ones).
    Returns the extended-coordinate sum (None = empty)."""
    from tendermint_tpu.crypto.ed25519_ref import point_add, point_double

    pairs = [(p, s) for p, s in pairs if s]
    if not pairs:
        return None
    nbits = max(s.bit_length() for _, s in pairs)
    if window <= 0:
        n = len(pairs)
        window = min(
            range(3, 11),
            key=lambda w: ((nbits + w - 1) // w) * (n + (1 << (w + 1))),
        )
    nwin = (nbits + window - 1) // window
    nbuckets = (1 << window) - 1
    acc = None
    for w in range(nwin - 1, -1, -1):
        if acc is not None:
            for _ in range(window):
                acc = point_double(acc)
        shift = w * window
        buckets = [None] * (nbuckets + 1)
        for p, s in pairs:
            d = (s >> shift) & nbuckets
            if d:
                buckets[d] = p if buckets[d] is None else point_add(buckets[d], p)
        running = total = None
        for b in range(nbuckets, 0, -1):
            if buckets[b] is not None:
                running = (
                    buckets[b] if running is None
                    else point_add(running, buckets[b])
                )
            if running is not None:
                total = running if total is None else point_add(total, running)
        if total is not None:
            acc = total if acc is None else point_add(acc, total)
    return acc


def _verify_batch_cpu_rlc(pubkeys, msgs, sigs) -> Optional[np.ndarray]:
    """Host combined check: Σ w_i·A_i + ((L-u) mod L)·B + Σ z_i·R_i == O
    with w_i = z_i·h_i mod 8L, u = Σ z_i·s_i mod L — the exact device-RLC
    equation (_rlc_submit) on host points. Returns the mask when the
    combined check passes; None = caller must fall back to the serial loop
    (a row failed, or an exceptional addition produced Z == 0).

    CHUNKED at the flush planner's budget (ISSUE 13): rows past
    planner_chunk_rows() stream as fixed-size partial Pippenger MSMs summed
    with point_add — a 100k-row flush on a wheel-less host never
    materializes the whole decompressed point set at once (the
    decompressed-point cache _HOST_PT_CACHE is shared across chunks, so
    repeated signers decompress once per flush regardless of chunking).
    Per-chunk coefficient collapse + the per-chunk B term keep the
    accumulated sum exactly equal to the single-MSM equation.

    STRIPED (ISSUE 18): with host striping on (_host_stripe_on) and n at
    or above the stream floor, the flush splits into stripes and stripe k+1's prep
    (precheck, challenge hashing, scalar lifting, z sampling) runs on the
    prep pool while the dispatch thread runs stripe k's decompress +
    Pippenger MSM — the host path's equivalent of hiding prep behind
    kernels. On a single-core host the overlap is time-sliced, not
    parallel; the windowed accounting (_overlap_seconds) reports the wall
    clock during which both sides were in flight. Exactness per stripe is
    the same per-chunk B-term argument as above."""
    from tendermint_tpu.crypto.ed25519_ref import (
        BASE,
        IDENTITY,
        P,
        point_add,
        point_equal,
    )

    from tendermint_tpu import native

    n = len(pubkeys)
    use_native = native.available()
    rng = np.random.default_rng()  # OS-entropy seeded per call
    stream = n >= _stream_floor() and _host_stripe_on()
    chunk = planner_chunk_rows()
    if stream:
        # stripes small enough that the first MSM starts early, large
        # enough that per-stripe pool latency stays negligible
        chunk = min(chunk, max(1024, n // 8))
    stripes = [(lo, min(lo + chunk, n)) for lo in range(0, n, chunk)]
    pipelined = stream and len(stripes) > 1

    def _stripe_prep(lo: int, hi: int):
        """Everything before point work for rows [lo, hi): runs on the
        prep pool when pipelined (the single-worker pool serializes the
        shared rng), inline otherwise. Indices in the result are
        stripe-local."""
        t0s = time.perf_counter()
        m = hi - lo
        if use_native:
            # multithreaded C challenge hashing (the same fast helper the
            # device paths use); scalars lift to Python ints only where
            # precheck holds
            pc, _a, _r, s_rows, h_rows = _precheck_and_hash_fast(
                pubkeys[lo:hi], msgs[lo:hi], sigs[lo:hi]
            )
            t_h = time.perf_counter()
            from_bytes = int.from_bytes
            s_i = [
                from_bytes(s_rows[i].tobytes(), "little") if pc[i] else 0
                for i in range(m)
            ]
            h_i = [
                from_bytes(h_rows[i].tobytes(), "little") if pc[i] else 0
                for i in range(m)
            ]
        else:
            pc, _a, _r, s_i, h_i = _precheck_and_hash(
                pubkeys[lo:hi], msgs[lo:hi], sigs[lo:hi]
            )
            t_h = time.perf_counter()
        z = _sample_z(rng, m, pc)
        t1s = time.perf_counter()
        return pc, s_i, h_i, z, {
            "span": (t0s, t1s),
            "hash_s": t_h - t0s,
            "scalars_s": t1s - t_h,
        }

    acc = None
    prechecks: list = []
    prep_spans: list = []
    msm_spans: list = []
    stage_totals: dict = {}
    prep_total = 0.0
    if pipelined:
        fut = _prep_pool().submit(_stripe_prep, *stripes[0])
    for k, (lo, hi) in enumerate(stripes):
        if pipelined:
            pc, s_i, h_i, z, timing = fut.result()
            if k + 1 < len(stripes):
                fut = _prep_pool().submit(_stripe_prep, *stripes[k + 1])
        else:
            pc, s_i, h_i, z, timing = _stripe_prep(lo, hi)
        span = timing["span"]
        prep_total += span[1] - span[0]
        prep_spans.append(span)
        for sk in ("hash_s", "scalars_s"):
            stage_totals[sk] = stage_totals.get(sk, 0.0) + timing[sk]
        t_msm = time.perf_counter()
        m = hi - lo
        # decompress THIS stripe's points only (cache-backed, write-shared
        # across stripes and flushes); invalid encodings drop out of
        # precheck exactly as on the device paths
        r_pts = [None] * m
        a_pts = [None] * m
        for i in range(m):
            if not pc[i]:
                continue
            a = _host_point(bytes(pubkeys[lo + i]))
            r = _host_point(bytes(sigs[lo + i])[:32])
            if a is None or r is None:
                pc[i] = False
                continue
            a_pts[i] = a
            r_pts[i] = r
        # A-lane coefficients collapse per DISTINCT pubkey (mod 8L is
        # exact): the admission workload verifies many txs from few
        # signers, and one combined lane per signer cuts the MSM's digit
        # adds accordingly
        a_coef: dict = {}
        a_by_key: dict = {}
        pairs = []
        u = 0
        for i in range(m):
            if not pc[i]:
                continue
            pkb = bytes(pubkeys[lo + i])
            a_coef[pkb] = (a_coef.get(pkb, 0) + z[i] * h_i[i]) % L8
            a_by_key[pkb] = a_pts[i]
            pairs.append((r_pts[i], z[i]))
            u += z[i] * s_i[i]
        prechecks.append(pc)
        if pairs:
            pairs.extend((a_by_key[pkb], c) for pkb, c in a_coef.items())
            # the stripe's own B term: Σ_k (L - u_k) ≡ L - Σ u_k (mod L),
            # so the accumulated sum equals the single-flush equation
            pairs.append((BASE, (L - u % L) % L))
            part = _host_msm(pairs)
            if part is not None:
                acc = part if acc is None else point_add(acc, part)
        msm_spans.append((t_msm, time.perf_counter()))
    precheck = np.concatenate(prechecks)
    LAST_FLUSH_DETAIL["prep_s"] = prep_total
    if pipelined:
        LAST_FLUSH_DETAIL["prep_overlap_s"] = _overlap_seconds(
            prep_spans, msm_spans
        )
        LAST_FLUSH_DETAIL["prep_stages"] = {
            k: round(v, 6) for k, v in stage_totals.items()
        }
    if not precheck.any():
        return precheck  # nothing verifiable: every verdict already False
    if len(stripes) > 1:
        LAST_FLUSH_DETAIL["chunks"] = len(stripes)
        LAST_FLUSH_DETAIL["chunk_lanes"] = 2 * (chunk + 1)
    res = acc if acc is not None else IDENTITY
    if res[2] % P == 0:
        # exceptional unified addition on crafted torsion inputs — the
        # device kernels read this as REJECT; here the serial loop decides
        return None
    if point_equal(res, IDENTITY):
        return precheck
    return None  # some row is bad: recover the exact mask serially


def _verify_serial_host(
    pubkeys: Sequence[bytes], msgs: Sequence[bytes], sigs: Sequence[bytes]
) -> np.ndarray:
    """The always-correct serial loop: the host path's exact-mask leaf."""
    from tendermint_tpu.crypto.keys import Ed25519PubKey

    out = np.zeros(len(pubkeys), dtype=bool)
    for i, (pk, msg, sig) in enumerate(zip(pubkeys, msgs, sigs)):
        try:
            out[i] = Ed25519PubKey(bytes(pk)).verify(bytes(msg), bytes(sig))
        except ValueError:
            out[i] = False
    return out


def _bisect_recover_host(pubkeys, msgs, sigs) -> np.ndarray:
    """Host-arm twin of _bisect_recover: after the striped host-RLC
    combined check fails, isolate bad rows with host-RLC sub-checks over
    pow2 halves and run the serial loop only at small leaves — the CPU
    fallback under a poisoning flood keeps the same log-cost shape as the
    device path (docs/ROBUSTNESS.md adversarial flush defense)."""
    n = len(pubkeys)
    out = np.zeros(n, dtype=bool)
    leaf = max(_BISECT_LEAF // 4, 1)
    max_bad = _BISECT_MAX_BAD
    flushes = 0
    bad_leaves = 0

    def _combined(lo, hi):
        nonlocal flushes
        flushes += 1
        try:
            return _verify_batch_cpu_rlc(
                pubkeys[lo:hi], msgs[lo:hi], sigs[lo:hi]
            )
        except Exception:
            return None  # broken host RLC degrades to serial leaves

    def _go(lo, hi):
        nonlocal flushes, bad_leaves
        m = hi - lo
        if m <= leaf or m < 2 * _HOST_RLC_MIN or bad_leaves >= max_bad:
            flushes += 1
            bad_leaves += 1
            out[lo:hi] = _verify_serial_host(
                pubkeys[lo:hi], msgs[lo:hi], sigs[lo:hi]
            )
            return
        half = 1 << ((m - 1).bit_length() - 1)
        mid = lo + half
        first = _combined(lo, mid)
        if first is not None:
            out[lo:mid] = first
            _go(mid, hi)
            return
        _go(lo, mid)
        if hi - mid >= _HOST_RLC_MIN and bad_leaves < max_bad:
            second = _combined(mid, hi)
            if second is not None:
                out[mid:hi] = second
                return
        _go(mid, hi)

    _go(0, n)
    LAST_FLUSH_DETAIL["recovery_flushes"] = (
        LAST_FLUSH_DETAIL.get("recovery_flushes", 0) + flushes
    )
    return out


def verify_batch_cpu(
    pubkeys: Sequence[bytes], msgs: Sequence[bytes], sigs: Sequence[bytes]
) -> np.ndarray:
    from tendermint_tpu.crypto.keys import cofactorless_mode

    n = len(pubkeys)
    if n >= _HOST_RLC_MIN and not cofactorless_mode():
        # combined-check fast path (see _verify_batch_cpu_rlc); cofactorless
        # (reference-exact interop) mode stays on the serial loop — its
        # acceptance predicate is stricter than the cofactored equation the
        # combined check proves
        try:
            mask = _verify_batch_cpu_rlc(pubkeys, msgs, sigs)
        except Exception:
            import logging

            logging.getLogger("tendermint_tpu.crypto.batch").exception(
                "host RLC failed; falling back to the serial loop"
            )
            mask = None
        if mask is not None:
            LAST_FLUSH_DETAIL["host_rlc"] = True
            return mask
        if _bisect_enabled():
            return _bisect_recover_host(pubkeys, msgs, sigs)
        # naive recovery: one whole-batch serial pass replaces the failed
        # combined check — count it so the recovery ledger covers both arms
        LAST_FLUSH_DETAIL["recovery_flushes"] = (
            LAST_FLUSH_DETAIL.get("recovery_flushes", 0) + 1
        )
    return _verify_serial_host(pubkeys, msgs, sigs)


def _signed_radix16(vals: np.ndarray) -> np.ndarray:
    """uint8[N, 32] little-endian scalars (< 2^253) -> int8[64, N] signed
    radix-16 digits in [-8, 8], LSB-first. Vectorized over the batch."""
    n = vals.shape[0]
    digits = np.empty((n, 64), dtype=np.int16)
    digits[:, 0::2] = vals & 0x0F
    digits[:, 1::2] = vals >> 4
    carry = np.zeros(n, dtype=np.int16)
    for i in range(64):
        d = digits[:, i] + carry
        carry = (d > 8).astype(np.int16)
        digits[:, i] = d - 16 * carry
    # scalars < 2^253 => top digit <= 1 before carry, <= 2 after: no overflow
    assert not carry.any()
    return np.ascontiguousarray(digits.T.astype(np.int8))


def prepare_batch(
    pubkeys: Sequence[bytes], msgs: Sequence[bytes], sigs: Sequence[bytes]
):
    """Host-side preprocessing for the device kernel.

    Returns (a_bytes[32,B], r_bytes[32,B], s_digits[64,B], h_digits[64,B],
    precheck[N] bool, n) with B = padded bucket size.
    """
    n = len(pubkeys)
    b = _bucket(max(n, 1))
    LAST_FLUSH_DETAIL["jit_bucket"] = b
    LAST_FLUSH_DETAIL["padding_lanes"] = b - n
    a = np.zeros((b, 32), dtype=np.uint8)
    r = np.zeros((b, 32), dtype=np.uint8)
    s = np.zeros((b, 32), dtype=np.uint8)
    h = np.zeros((b, 32), dtype=np.uint8)
    from tendermint_tpu import native

    if n and native.available():
        precheck, a_rows, r_rows, s_rows, h_rows = _precheck_and_hash_fast(
            pubkeys, msgs, sigs
        )
        if precheck.any():
            a[:n][precheck] = a_rows[precheck]
            r[:n][precheck] = r_rows[precheck]
            s[:n][precheck] = s_rows[precheck]
            h[:n][precheck] = h_rows[precheck]
        return (
            np.ascontiguousarray(a.T),
            np.ascontiguousarray(r.T),
            _signed_radix16(s),
            _signed_radix16(h),
            precheck,
            n,
        )
    precheck = np.zeros(n, dtype=bool)
    for i in range(n):
        pk, msg, sig = bytes(pubkeys[i]), bytes(msgs[i]), bytes(sigs[i])
        if len(pk) != 32 or len(sig) != 64:
            continue
        s_int = int.from_bytes(sig[32:], "little")
        if s_int >= L:
            continue  # non-canonical s: reject without device work
        precheck[i] = True
        a[i] = np.frombuffer(pk, dtype=np.uint8)
        r[i] = np.frombuffer(sig[:32], dtype=np.uint8)
        s[i] = np.frombuffer(sig[32:], dtype=np.uint8)
        h_int = (
            int.from_bytes(hashlib.sha512(sig[:32] + pk + msg).digest(), "little") % L
        )
        h[i] = np.frombuffer(h_int.to_bytes(32, "little"), dtype=np.uint8)
    return (
        np.ascontiguousarray(a.T),
        np.ascontiguousarray(r.T),
        _signed_radix16(s),
        _signed_radix16(h),
        precheck,
        n,
    )


_L_BE = np.frombuffer(L.to_bytes(32, "big"), dtype=np.uint8)


def _s_canonical_rows(s_rows: np.ndarray) -> np.ndarray:
    """Vectorized canonical-s check: s < L per (n, 32) little-endian row
    (lexicographic compare on the byte-reversed rows)."""
    n = s_rows.shape[0]
    s_be = s_rows[:, ::-1]
    neq = s_be != _L_BE
    first = neq.argmax(axis=1)
    rows = np.arange(n)
    return neq.any(axis=1) & (s_be[rows, first] < _L_BE[first])


def _precheck_rows_fast(
    pubkeys: Sequence[bytes], msgs: Sequence[bytes], sigs: Sequence[bytes]
):
    """The precheck/blob-assembly HALF of `_precheck_and_hash_fast`: cheap,
    pure-numpy, and enough to start lane assembly — the staged submit path
    (`_rlc_submit`) runs this on the dispatch thread and hands the returned
    blobs to the prep pool for hashing while it assembles lanes and uploads
    the A block.

    Returns (precheck bool[n], a_rows, r_rows, s_rows,
    (sigs_blob, pks_blob, msgs_blob, moffs))."""
    n = len(pubkeys)
    pubkeys = [bytes(p) for p in pubkeys]
    sigs = [bytes(s) for s in sigs]
    len_ok = np.fromiter(
        (len(p) == 32 and len(s) == 64 for p, s in zip(pubkeys, sigs)),
        dtype=bool,
        count=n,
    )
    if not len_ok.all():
        zpk, zsig = bytes(32), bytes(64)
        pubkeys = [p if k else zpk for p, k in zip(pubkeys, len_ok)]
        sigs = [s if k else zsig for s, k in zip(sigs, len_ok)]
        msgs = [m if k else b"" for m, k in zip(msgs, len_ok)]
    pks_blob = b"".join(pubkeys)
    sigs_blob = b"".join(sigs)
    msgs = [bytes(m) for m in msgs]
    moffs = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(np.fromiter(map(len, msgs), dtype=np.int64, count=n), out=moffs[1:])
    sig_arr = np.frombuffer(sigs_blob, dtype=np.uint8).reshape(n, 64)
    a_rows = np.frombuffer(pks_blob, dtype=np.uint8).reshape(n, 32)
    r_rows = sig_arr[:, :32]
    s_rows = sig_arr[:, 32:]
    precheck = len_ok & _s_canonical_rows(s_rows)
    return precheck, a_rows, r_rows, s_rows, (sigs_blob, pks_blob, b"".join(msgs), moffs)


def _precheck_and_hash_fast(
    pubkeys: Sequence[bytes], msgs: Sequence[bytes], sigs: Sequence[bytes]
):
    """Native-backed `_precheck_and_hash` for pure-ed25519 batches: the
    challenge hashes h_i = SHA512(R||A||M) mod L run as multithreaded C
    (tendermint_tpu/native) instead of a serial hashlib loop, and scalars
    stay in the bytes domain (no Python bigints on the hot path).

    Returns (precheck bool[n], a_rows (n,32) u8, r_rows (n,32) u8,
    s_rows (n,32) u8, h_rows (n,32) u8). Rows failing precheck have
    h zeroed; a/r/s rows are only meaningful where precheck holds."""
    from tendermint_tpu import native

    precheck, a_rows, r_rows, s_rows, blobs = _precheck_rows_fast(
        pubkeys, msgs, sigs
    )
    h_rows = native.ed25519_h_batch(*blobs)
    HASH_ROWS_HASHED[0] += len(pubkeys)
    h_rows[~precheck] = 0
    return precheck, a_rows, r_rows, s_rows, h_rows


def _rlc_scalars_fast(precheck: np.ndarray, s_rows: np.ndarray, h_rows: np.ndarray):
    """Bytes-domain `_rlc_scalars`: same z-sampling semantics (~124-bit,
    nonzero, forced ≡ 0 mod 8; see _sample_z) with the z*h mod 8L and
    Σ z*s mod L math in native C. Returns (z16 (n,16) u8, w (n,32) u8,
    u int)."""
    from tendermint_tpu import native

    n = s_rows.shape[0]
    rng = np.random.default_rng()  # OS-entropy seeded per call
    zw = rng.integers(0, 1 << 64, size=(n, 2), dtype=np.uint64)
    a = zw[:, 0] & np.uint64((1 << 57) - 1)
    b = zw[:, 1] | np.uint64(1)
    z = np.empty((n, 2), dtype="<u8")
    z[:, 0] = b << np.uint64(3)
    z[:, 1] = (a << np.uint64(3)) | (b >> np.uint64(61))
    z16 = z.view(np.uint8).reshape(n, 16)
    z16[~precheck] = 0
    w_rows, u = native.rlc_scalars(z16, h_rows, s_rows)
    return z16, w_rows, u


def _precheck_and_hash(
    pubkeys: Sequence[bytes],
    msgs: Sequence[bytes],
    sigs: Sequence[bytes],
    key_types: Sequence[str] | None = None,
):
    """Shared host prep: length/canonical-s checks + the per-row verification
    scalar — h = SHA512(R||A||M) mod L for ed25519 rows, the merlin
    transcript challenge k for sr25519 rows (crypto/sr25519.py; reference
    crypto/sr25519/pubkey.go:34).

    Returns (precheck bool[n], a_rows (n,32) u8, r_rows (n,32) u8,
    s_ints list[int], hk_ints list[int]); rows failing precheck have zeroed
    entries."""
    n = len(pubkeys)
    precheck = np.zeros(n, dtype=bool)
    a_buf = bytearray(32 * n)
    r_buf = bytearray(32 * n)
    s_ints = [0] * n
    hk_ints = [0] * n
    sr_pending: dict = {}  # msg_len -> [(row, pk, msg, r_bytes)]
    sha512 = hashlib.sha512
    from_bytes = int.from_bytes
    for i in range(n):
        pk, msg, sig = bytes(pubkeys[i]), bytes(msgs[i]), bytes(sigs[i])
        if len(pk) != 32 or len(sig) != 64:
            continue
        if key_types is not None and key_types[i] == "sr25519":
            if not (sig[63] & 0x80):
                continue  # schnorrkel marker bit must be set
            s_int = from_bytes(sig[32:63] + bytes([sig[63] & 0x7F]), "little")
            if s_int >= L:
                continue
            # challenge k computed batched below (merlin transcripts in
            # lockstep, grouped by message length)
            sr_pending.setdefault(len(msg), []).append((i, pk, msg, sig[:32]))
        else:
            s_int = from_bytes(sig[32:], "little")
            if s_int >= L:
                continue  # non-canonical s: reject without device work
            hk_ints[i] = (
                from_bytes(sha512(sig[:32] + pk + msg).digest(), "little") % L
            )
            HASH_ROWS_HASHED[0] += 1
        precheck[i] = True
        off = 32 * i
        a_buf[off : off + 32] = pk
        r_buf[off : off + 32] = sig[:32]
        s_ints[i] = s_int
    # sr25519 challenges: merlin transcripts advanced in LOCKSTEP over each
    # same-message-length group (crypto/merlin.py BatchTranscript) — ~200x
    # faster than per-row Python transcripts (reference derivation:
    # crypto/sr25519/pubkey.go:34 via go-schnorrkel).
    for mlen, rows in sr_pending.items():
        from tendermint_tpu.crypto.merlin import BatchTranscript
        from tendermint_tpu.crypto.sr25519 import SIGNING_CTX

        m = len(rows)
        bt = BatchTranscript(b"SigningContext", m)
        bt.append_message(b"", SIGNING_CTX)
        bt.append_message(
            b"sign-bytes",
            np.frombuffer(b"".join(r[2] for r in rows), dtype=np.uint8).reshape(m, mlen),
        )
        bt.append_message(b"proto-name", b"Schnorr-sig")
        bt.append_message(
            b"sign:pk",
            np.frombuffer(b"".join(r[1] for r in rows), dtype=np.uint8).reshape(m, 32),
        )
        bt.append_message(
            b"sign:R",
            np.frombuffer(b"".join(r[3] for r in rows), dtype=np.uint8).reshape(m, 32),
        )
        wide = bt.challenge_bytes(b"sign:c", 64)
        for j, (i, _pk, _msg, _r) in enumerate(rows):
            hk_ints[i] = from_bytes(wide[j].tobytes(), "little") % L
    a_rows = np.frombuffer(bytes(a_buf), dtype=np.uint8).reshape(n, 32)
    r_rows = np.frombuffer(bytes(r_buf), dtype=np.uint8).reshape(n, 32)
    return precheck, a_rows, r_rows, s_ints, hk_ints


# ---------------------------------------------------------------------------
# Decoded-pubkey cache for the RLC path. Consensus verifies the same
# validator keys every height; decoding (a ~250-mul sqrt chain per point) is
# the single largest per-lane cost in the MSM kernel, so cache the extended
# coordinates keyed by key type + the 32-byte encoding (ed25519 compressed
# and ristretto255 encodings share the byte space but decode differently).

_A_CACHE: dict = {}  # b"e"/b"s" + pubkey bytes -> column index in _A_STORE, or None
_A_CACHE_MAX = 65536
# Contiguous coordinate store: one fancy-index gather builds the whole A
# block instead of a 10k-iteration Python loop (see _a_block).
_A_STORE = np.empty((4, 20, 1024), dtype=np.int32)
_A_STORE_LEN = 0
# The background prewarm thread (node startup) and the consensus event loop
# can fill the cache concurrently; an unlocked col=_A_STORE_LEN; write; +=1
# sequence could alias two pubkeys to one column — which would make the
# cached-A equation verify one validator's signatures against ANOTHER key's
# coordinates. Every fill holds this lock (reads are safe: columns are
# write-once and the store only grows by copy).
_A_LOCK = __import__("threading").Lock()

# Device-resident A-block cache: the assembled (4, 20, Na) coordinate block
# for a (validator set, lane bucket) pair, already uploaded. Re-uploading it
# every call costs ~3.3 MB of H2D at 10k validators per verification (its
# time on today's chip is not measured). Keyed by
# (cache generation, bucket, included rows, store columns); tiny LRU.
_DEV_A_CACHE: dict = {}
_DEV_A_MAX = 4
_A_GENERATION = 0  # bumped when _A_CACHE resets (store exhaustion)


def _cache_key(pk: bytes, key_type: str) -> bytes:
    return (b"s" if key_type == "sr25519" else b"e") + pk


def _fill_a_cache(rows: "np.ndarray", key_type: str = "ed25519") -> None:
    """Decode unique pubkey rows on device and populate the cache.
    Thread-safe (prewarm thread vs event loop; see _A_LOCK)."""
    with _A_LOCK:
        _fill_a_cache_locked(rows, key_type)


def _fill_a_cache_locked(rows: "np.ndarray", key_type: str) -> None:
    global _A_STORE, _A_STORE_LEN
    if key_type == "sr25519":
        from tendermint_tpu.ops.ristretto_jax import decode_rows as _decode
    else:
        from tendermint_tpu.ops.msm_jax import decompress_rows as _decode

    prefix = b"s" if key_type == "sr25519" else b"e"
    uniq = {bytes(r.tobytes()) for r in rows}
    missing = [k for k in uniq if prefix + k not in _A_CACHE]
    if not missing:
        return
    missing = missing[:_A_CACHE_MAX]
    if _A_STORE_LEN + len(missing) > _A_CACHE_MAX:
        # store exhausted: full reset (validator churn past 64k unique keys)
        global _A_GENERATION
        _A_CACHE.clear()
        _A_STORE_LEN = 0
        _A_GENERATION += 1  # invalidates device-resident A blocks
        _DEV_A_CACHE.clear()
    while _A_STORE.shape[2] < min(_A_CACHE_MAX, _A_STORE_LEN + len(missing)):
        _A_STORE = np.concatenate([_A_STORE, np.empty_like(_A_STORE)], axis=2)
    coords, ok = _decode(
        np.stack([np.frombuffer(k, dtype=np.uint8) for k in missing])
    )
    for j, k in enumerate(missing):
        if ok[j]:
            col = _A_STORE_LEN
            for c in range(4):
                _A_STORE[c, :, col] = coords[c][:, j]
            _A_CACHE[prefix + k] = col
            _A_STORE_LEN += 1
        else:
            _A_CACHE[prefix + k] = None


class _RlcCall:
    """An in-flight RLC batch check: device work submitted, not yet synced.

    Splitting submit from finish lets callers pipeline batches — JAX's async
    dispatch overlaps the next batch's host prep (hashing, sorting, scalar
    math) with the previous batch's device execution."""

    __slots__ = (
        "precheck", "n", "na", "mode", "dev", "a_rows", "prep_seconds",
        "ed_pos", "sr_pos", "ne", "ns", "fused",
    )

    def __init__(self, precheck, n, na, mode, dev, a_rows, prep_seconds,
                 ed_pos=None, sr_pos=None, ne=0, ns=0, fused=False):
        self.precheck = precheck
        self.n = n
        self.na = na
        self.mode = mode  # "plain" | "cached" | "mixed"
        self.dev = dev
        self.a_rows = a_rows
        self.prep_seconds = prep_seconds
        self.ed_pos = ed_pos  # mixed: row index per ed R lane
        self.sr_pos = sr_pos  # mixed: row index per sr R lane
        self.ne = ne  # mixed: ed R lane-bucket size
        self.ns = ns  # mixed: sr R lane-bucket size
        self.fused = fused  # submitted through the fused MSM pipeline


# Timing of the last completed RLC call (host-prep vs total), for bench.py.
LAST_RLC_TIMINGS: dict = {}

# Per-flush flight-recorder detail, filled by the path that actually ran
# (prepare_batch, _rlc_submit, _rlc_finish) and consumed by verify_batch /
# verify_batch_finish into libs.trace.record_flush. Best-effort shared state
# (same model as LAST_RLC_TIMINGS): concurrent flushes may interleave fields,
# which is acceptable for observability and free on the hot path.
LAST_FLUSH_DETAIL: dict = {}


def _record_submit_counters(msm_jax_mod, before: dict) -> None:
    """Flush-detail deltas of the submit-path device-traffic counters
    (thread-local in msm_jax, so concurrent submits from the prewarm
    thread and the event loop never contaminate each other's deltas)."""
    counters = msm_jax_mod.flush_counters()
    LAST_FLUSH_DETAIL["h2d_bytes"] = counters["h2d_bytes"] - before["h2d_bytes"]
    LAST_FLUSH_DETAIL["device_dispatches"] = (
        counters["dispatches"] - before["dispatches"]
    )
    LAST_FLUSH_DETAIL["fused"] = msm_jax_mod.last_submit_fused()


def _sample_z(rng, n: int, precheck) -> list:
    """Random RLC coefficients: ~124-bit, nonzero, and ≡ 0 (mod 8) so every
    lane's cofactor-torsion component is annihilated exactly (see
    ops/msm_jax.py docstring). 0 for excluded rows."""
    zw = rng.integers(0, 1 << 64, size=(n, 2), dtype=np.uint64)
    return [
        ((((int(zw[i, 0]) & ((1 << 57) - 1)) << 64) | int(zw[i, 1]) | 1) << 3)
        if precheck[i]
        else 0
        for i in range(n)
    ]


def _rlc_scalars(precheck, s_ints, hk_ints, n: int):
    """Shared RLC coefficient/scalar derivation (single-device submit AND the
    sharded path — keep them identical: the torsion-exact L8 reduction is
    consensus-relevant). Returns (zs, w_scalars, u)."""
    rng = np.random.default_rng()  # OS-entropy seeded per call
    zs = _sample_z(rng, n, precheck)
    w_scalars = [zs[i] * hk_ints[i] % L8 if precheck[i] else 0 for i in range(n)]
    u = sum(zs[i] * s_ints[i] for i in range(n) if precheck[i]) % L
    return zs, w_scalars, u


def _rlc_submit(
    pubkeys: Sequence[bytes],
    msgs: Sequence[bytes],
    sigs: Sequence[bytes],
    key_types: Sequence[str] | None = None,
) -> _RlcCall:
    """Host prep + device submit of the RLC combined check (no sync).

    Pure-ed25519 batches use the plain kernel on first sight of a validator
    set (A decoded in-kernel, cache filled at finish) and the cached-A kernel
    in steady state. Mixed ed25519+sr25519 batches always prefill the typed
    pubkey cache (both decoders) and run the mixed cached kernel with
    separate ed/sr R-lane blocks.

    The whole submit is the `rlc.submit` span; its duration is the call's
    `prep_seconds` (the flush record's `prep_ms` on this path)."""
    with _trace.timed("rlc.submit", n=len(pubkeys)) as sub:
        call = _rlc_submit_spanned(pubkeys, msgs, sigs, key_types, sub)
    call.prep_seconds = sub.seconds
    return call


def _rlc_submit_spanned(pubkeys, msgs, sigs, key_types, sub) -> _RlcCall:
    """_rlc_submit's body; `sub` is its open span, the explicit parent of
    the hashing task handed to the prep pool."""
    from tendermint_tpu.crypto.ed25519_ref import BASE, point_compress
    from tendermint_tpu.ops import msm_jax

    _device_fault("rlc_submit")
    # Per-flush device-traffic accounting (tests/test_flush_budget.py pins
    # budgets on the deltas): dispatches + H2D bytes this submit produces.
    msm_jax._set_submit_fused(False)
    counters0 = dict(msm_jax.flush_counters())
    n = len(pubkeys)
    mixed = key_types is not None and any(t == "sr25519" for t in key_types)
    from tendermint_tpu import native

    # Two prep arms, chosen by what the rows and the host are: the staged
    # native arm (all ed25519, the C library built), else pure Python
    # (a mixed set's sr25519 challenges, or a host with no compiler).
    staged = not mixed and native.available()
    prep_stages: dict = {}
    if staged:
        # Stage 1 (dispatch thread): cheap precheck + blob assembly only.
        with _trace.timed("prep.precheck", chunk=0) as st:
            precheck, a_rows, r_rows, s_rows, blobs = _precheck_rows_fast(
                pubkeys, msgs, sigs
            )
        prep_stages["precheck_s"] = st.seconds

        # Stage 2 (prep pool): challenge hashing runs OFF the dispatch
        # thread while lane assembly and the A-block upload proceed below.
        # A hashing failure latches in the future and re-raises at
        # .result() — the flush fails loudly and the dispatch thread never
        # wedges (tests/test_prep_pipeline.py).
        def _hash_task(blobs=blobs, rows=n):
            with _trace.timed("prep.hash", parent=sub, chunk=0) as st:
                h = native.ed25519_h_batch(*blobs)
            HASH_ROWS_HASHED[0] += rows
            return h, st.interval()

        hash_fut = _prep_pool().submit(_hash_task)
    else:
        precheck, a_rows, r_rows, s_ints, hk_ints = _precheck_and_hash(
            pubkeys, msgs, sigs, key_types if mixed else None
        )

    types = key_types if mixed else ["ed25519"] * n
    ckeys = [_cache_key(bytes(pubkeys[i]), types[i]) for i in range(n)]

    # Pubkey-decompress cache hit rate, sampled BEFORE any fill: steady-state
    # consensus should read ~1.0 here (same validator set every height).
    n_pre = int(precheck.sum())
    hits = sum(1 for i in range(n) if precheck[i] and ckeys[i] in _A_CACHE)
    LAST_FLUSH_DETAIL["cache_hits"] = hits
    LAST_FLUSH_DETAIL["cache_misses"] = n_pre - hits

    if mixed:
        # Prefill the typed cache so every included lane has coordinates.
        # Two passes: the second-type fill can trigger a full cache reset
        # (store exhaustion under extreme validator churn), orphaning keys
        # the first pass just cached — the retry refills them; after a reset
        # the store has capacity for the whole batch, so one retry suffices.
        for _attempt in range(2):
            for kt in ("ed25519", "sr25519"):
                rows_kt = a_rows[
                    [
                        precheck[i]
                        and types[i] == kt
                        and ckeys[i] not in _A_CACHE
                        for i in range(n)
                    ]
                ]
                if len(rows_kt):
                    _fill_a_cache(rows_kt, kt)
            if all(ckeys[i] in _A_CACHE for i in range(n) if precheck[i]):
                break

    # Exclude rows whose pubkey is a cached-invalid encoding: their verdict
    # is False regardless, and excluding them keeps the batch equation clean.
    for i in range(n):
        if precheck[i] and _A_CACHE.get(ckeys[i], True) is None:
            precheck[i] = False

    # A-lane scalars mod 8L (exact for points of any order; kills torsion
    # since z ≡ 0 mod 8 survives the reduction), B-lane scalar mod L.
    # Staged submits defer this until the A block is uploading — the hash
    # future resolves right before the scalar math needs h (w = z·h is 0
    # wherever z is 0, so zeroing h after the exclusions above is exact).
    if not staged:
        zs, w_scalars, u = _rlc_scalars(precheck, s_ints, hk_ints, n)

    b_enc = np.frombuffer(point_compress(BASE), dtype=np.uint8)
    na = _lane_bucket(n + 1)

    included = [ckeys[i] for i in range(n) if precheck[i]]
    cached = bool(included) and all(k in _A_CACHE for k in included)

    a_spans: list = []  # the A-block stage's intervals (overlap accounting)

    def _a_block():
        with _trace.timed("flush.a_block") as st:
            dev, hit = _a_block_of()
            st.set(hit=hit)
        a_spans.append(st.interval())
        return dev

    def _a_block_of():
        """(device A block, whether the device A-block cache held it)."""
        import jax as _jax

        rows = np.flatnonzero(precheck)
        # Snapshot the cache columns AND the store slice under one lock
        # hold: a concurrent store-exhaustion reset (_fill_a_cache_locked)
        # clears _A_CACHE and rewrites columns, so an unlocked read could
        # see torn coordinates (advisor r4). The slice copy is small
        # (4*20*|rows|*4 bytes) and write-once columns make reads cheap.
        with _A_LOCK:  # prewarm thread vs event loop (same model as fills)
            cols = (
                np.fromiter(
                    (_A_CACHE[ckeys[i]] for i in rows), dtype=np.int64, count=len(rows)
                )
                if len(rows)
                else np.empty(0, dtype=np.int64)
            )
            key = (_A_GENERATION, na, rows.tobytes(), cols.tobytes())
            hit = _DEV_A_CACHE.pop(key, None)
            if hit is not None:
                _DEV_A_CACHE[key] = hit  # LRU refresh
                return hit, True
            store_slice = _A_STORE[:, :, cols].copy() if len(rows) else None
        bx, by, bz, bt = msm_jax.basepoint_coords()
        block = np.empty((4, 20, na), dtype=np.int32)
        block[0] = bx[:, None]
        block[1] = by[:, None]
        block[2] = bz[:, None]
        block[3] = bt[:, None]
        if len(rows):
            block[:, :, rows] = store_slice
        dev = tuple(_jax.device_put(block[c]) for c in range(4))
        # an A-block upload is real H2D traffic this flush paid (cache
        # hits above return without it — that's the budget being guarded)
        msm_jax.flush_counters()["h2d_bytes"] += block.nbytes
        with _A_LOCK:
            while len(_DEV_A_CACHE) >= _DEV_A_MAX:
                _DEV_A_CACHE.pop(next(iter(_DEV_A_CACHE)))
            _DEV_A_CACHE[key] = dev
        return dev, False

    if mixed:
        ed_pos = [i for i in range(n) if types[i] != "sr25519"]
        sr_pos = [i for i in range(n) if types[i] == "sr25519"]
        ne = _lane_bucket(max(len(ed_pos), 1))
        ns = _lane_bucket(max(len(sr_pos), 1))
        LAST_FLUSH_DETAIL["jit_bucket"] = na
        LAST_FLUSH_DETAIL["padding_lanes"] = na + ne + ns - (2 * n + 1)
        ed_r = np.tile(b_enc, (ne, 1))
        sr_r = np.zeros((ns, 32), dtype=np.uint8)  # identity: valid ristretto
        for j, i in enumerate(ed_pos):
            if precheck[i]:
                ed_r[j] = r_rows[i]
        for j, i in enumerate(sr_pos):
            if precheck[i]:
                sr_r[j] = r_rows[i]
        scalars = [0] * (na + ne + ns)
        scalars[:n] = w_scalars
        scalars[n] = (L - u) % L
        for j, i in enumerate(ed_pos):
            scalars[na + j] = zs[i]
        for j, i in enumerate(sr_pos):
            scalars[na + ne + j] = zs[i]
        dev = msm_jax.rlc_check_cached_mixed_submit(_a_block(), ed_r, sr_r, scalars)
        _record_submit_counters(msm_jax, counters0)
        return _RlcCall(
            precheck, n, na, "mixed", dev, None, 0.0,
            ed_pos=np.asarray(ed_pos, dtype=np.int64),
            sr_pos=np.asarray(sr_pos, dtype=np.int64),
            ne=ne, ns=ns, fused=msm_jax.last_submit_fused(),
        )

    # A block: [A_0..A_{n-1}, B, pads]; excluded/pad lanes are the basepoint
    # encoding with scalar 0 (bucket 0 is never summed).
    LAST_FLUSH_DETAIL["jit_bucket"] = na
    LAST_FLUSH_DETAIL["padding_lanes"] = 2 * na - (2 * n + 1)
    pts_r = np.tile(b_enc, (na, 1))
    if precheck.any():
        pts_r[:n][precheck] = r_rows[precheck]

    a_dev = None
    if staged and cached:
        # Early A-block upload: a cache-miss H2D transfer runs while the
        # prep pool is still hashing — the overlap this stage exists to
        # create (a _DEV_A_CACHE hit returns instantly and hides nothing;
        # in that steady state the hashing stands before the dispatch).
        a_dev = _a_block()

    if staged:
        with _trace.span("flush.prep_wait", chunk=0):
            h_rows, h_span = hash_fut.result()  # re-raises a prep failure
        prep_stages["hash_s"] = h_span[1] - h_span[0]
        h_rows[~precheck] = 0
        with _trace.timed("prep.scalars", chunk=0) as st:
            z16, w_rows, u = _rlc_scalars_fast(precheck, s_rows, h_rows)
        prep_stages["scalars_s"] = st.seconds
        LAST_FLUSH_DETAIL["prep_overlap_s"] = _overlap_seconds([h_span], a_spans)
        LAST_FLUSH_DETAIL["chunks"] = 1
        LAST_FLUSH_DETAIL["chunk_lanes"] = 2 * na

        # Scalars stay in the bytes domain end to end: the (2*na, 32) digit
        # rows feed the window sort directly (no bigint list round trip).
        scalars = np.zeros((2 * na, 32), dtype=np.uint8)
        scalars[:n] = w_rows
        scalars[n] = np.frombuffer(
            ((L - u) % L).to_bytes(32, "little"), dtype=np.uint8
        )
        scalars[na : na + n, :16] = z16  # already zeroed where ~precheck
        # Window sort hoisted out of the submit helper: only the MSM gather
        # waits on it (same sort_windows the helper would run — identical
        # perm/ends), and the stage table gets an honest sort_s.
        with _trace.timed("prep.sort", chunk=0) as st:
            digits = msm_jax.scalars_to_bytes(scalars, 2 * na)
            presorted = msm_jax.sort_windows(digits, zero16_from=na)
        prep_stages["sort_s"] = st.seconds
        LAST_FLUSH_DETAIL["prep_stages"] = {
            k: round(v, 6) for k, v in prep_stages.items()
        }
    else:
        scalars = [0] * (2 * na)
        scalars[:n] = w_scalars
        scalars[n] = (L - u) % L
        scalars[na : na + n] = [zs[i] if precheck[i] else 0 for i in range(n)]
        presorted = None  # the submit helper sorts

    if cached:
        dev = msm_jax.rlc_check_cached_submit(
            a_dev if a_dev is not None else _a_block(), pts_r, scalars,
            presorted=presorted,
        )
    else:
        pts_a = np.tile(b_enc, (na, 1))
        if precheck.any():
            pts_a[:n][precheck] = a_rows[precheck]
        pts_ar = np.concatenate([pts_a, pts_r], axis=0)
        dev = msm_jax.rlc_check_submit(
            pts_ar, scalars, zero16_from=na, presorted=presorted
        )
    _record_submit_counters(msm_jax, counters0)
    return _RlcCall(
        precheck, n, na, "cached" if cached else "plain", dev,
        a_rows if not cached else None, 0.0,
        fused=msm_jax.last_submit_fused(),
    )


def _rlc_finish(call: _RlcCall) -> Optional[np.ndarray]:
    """Sync the device result (ONE packed D2H fetch); mask on success,
    None -> per-sig fallback."""
    precheck, n, na = call.precheck, call.n, call.na
    try:
        _device_fault("rlc_finish")
        with _trace.timed("flush.sync", chunk=0) as sy:
            out = np.asarray(call.dev)  # [batch_ok, lane_ok...]
    except Exception as e:
        _trace.mark_device_call(ok=False, error=repr(e))
        raise
    _trace.mark_device_call(ok=True)
    LAST_FLUSH_DETAIL["transfer_s"] = sy.seconds
    LAST_FLUSH_DETAIL["prep_s"] = call.prep_seconds
    batch_ok = bool(out[0])
    ok = out[1:]
    if call.mode == "mixed":
        ed_ok = ok[: call.ne]
        sr_ok = ok[call.ne : call.ne + call.ns]
        lanes_ok = True
        for j, i in enumerate(call.ed_pos):
            if precheck[i] and not ed_ok[j]:
                lanes_ok = False
        for j, i in enumerate(call.sr_pos):
            if precheck[i] and not sr_ok[j]:
                lanes_ok = False
        return precheck if (batch_ok and lanes_ok) else None
    if call.mode == "cached":
        lanes_ok = bool(ok[:n][precheck].all()) if precheck.any() else True
    else:
        lanes_ok = (
            bool(ok[:n][precheck].all() and ok[na : na + n][precheck].all())
            if precheck.any()
            else True
        )
        # Populate the pubkey cache for subsequent calls (steady-state
        # consensus hits the cached kernel, skipping A decompression).
        if precheck.any():
            _fill_a_cache(call.a_rows[precheck])
    if batch_ok and lanes_ok:
        return precheck
    return None


def _rlc_finish_many(calls: Sequence[_RlcCall]) -> List[Optional[np.ndarray]]:
    """Finish several in-flight RLC calls with ONE device->host fetch.

    Every sync is a device round trip, and per-call finishes serialize
    them. Same-shaped results (same lane bucket — e.g. fast sync verifying
    many blocks against one validator set) are stacked ON DEVICE and fetched
    in a single transfer; mixed shapes fall back to per-call syncs."""
    import jax.numpy as _jnp

    if len(calls) > 1:
        shapes = {tuple(c.dev.shape) for c in calls}
        if len(shapes) == 1:
            stacked = np.asarray(_jnp.stack([c.dev for c in calls]))
            for c, row in zip(calls, stacked):
                c.dev = row  # numpy now; _rlc_finish syncs for free
    return [_rlc_finish(c) for c in calls]


def _prep_stream_chunk(
    pubkeys, msgs, sigs, lo: int, hi: int, na_c: int, sort: bool = True,
    chunk: int = 0, parent=None,
):
    """Host prep of ONE planner chunk, plain-kernel lane layout:
    [A_lo..A_{hi-1}, B, pads -> na_c | R_lo..R_{hi-1}, pads -> na_c], with
    the chunk's own B-lane scalar (L - u_k) mod L (see the planner note
    above: per-chunk B terms sum exactly). Runs on the prep worker thread —
    it must touch no shared mutable state beyond the (locked) caches.

    `chunk` is the chunk's index and `parent` the flush's span on the
    dispatch thread: the worker's `prep.chunk` span nests under it.

    Returns (precheck (hi-lo,) bool, pts (2*na_c, 32) u8, scalars,
    presorted, timing) — timing = {"span": (start, end), "stages": {...}},
    the spans' own intervals, so the caller can compute windowed
    prep/device overlap (_overlap_seconds) and the per-stage breakdown."""
    from tendermint_tpu.crypto.ed25519_ref import BASE, point_compress

    from tendermint_tpu import native

    c = hi - lo
    stages: dict = {}
    with _trace.timed(
        "prep.chunk", parent=parent, chunk=chunk, rows=c, lanes=2 * na_c
    ) as whole:
        pk, mg, sg = pubkeys[lo:hi], msgs[lo:hi], sigs[lo:hi]
        if native.available():
            with _trace.timed("prep.hash", chunk=chunk) as st:
                precheck, a_rows, r_rows, s_rows, h_rows = _precheck_and_hash_fast(
                    pk, mg, sg
                )
            stages["hash_s"] = st.seconds
            with _trace.timed("prep.scalars", chunk=chunk) as st:
                z16, w_rows, u = _rlc_scalars_fast(precheck, s_rows, h_rows)
            stages["scalars_s"] = st.seconds
            scalars = np.zeros((2 * na_c, 32), dtype=np.uint8)
            scalars[:c] = w_rows
            scalars[c] = np.frombuffer(
                ((L - u) % L).to_bytes(32, "little"), dtype=np.uint8
            )
            scalars[na_c : na_c + c, :16] = z16  # zeroed where ~precheck
        else:
            with _trace.timed("prep.hash", chunk=chunk) as st:
                precheck, a_rows, r_rows, s_ints, hk_ints = _precheck_and_hash(
                    pk, mg, sg
                )
            stages["hash_s"] = st.seconds
            with _trace.timed("prep.scalars", chunk=chunk) as st:
                zs, w_scalars, u = _rlc_scalars(precheck, s_ints, hk_ints, c)
            stages["scalars_s"] = st.seconds
            scalars = [0] * (2 * na_c)
            scalars[:c] = w_scalars
            scalars[c] = (L - u) % L
            scalars[na_c : na_c + c] = [
                zs[i] if precheck[i] else 0 for i in range(c)
            ]
        b_enc = np.frombuffer(point_compress(BASE), dtype=np.uint8)
        pts = np.tile(b_enc, (2 * na_c, 1))
        if precheck.any():
            pts[:c][precheck] = a_rows[precheck]
            pts[na_c : na_c + c][precheck] = r_rows[precheck]
        # the window sort belongs to the PREP worker too (it is the largest
        # single host-prep cost at chunk scale — overlapping hashing but not
        # the sort would leave the dispatch thread sort-bound between
        # chunks); the sharded arm sorts per shard in prepare_rlc_shards
        presorted = None
        if sort:
            from tendermint_tpu.ops.msm_jax import scalars_to_bytes, sort_windows

            with _trace.timed("prep.sort", chunk=chunk) as st:
                digits = scalars_to_bytes(scalars, 2 * na_c)
                presorted = sort_windows(digits, zero16_from=na_c)
            stages["sort_s"] = st.seconds
    timing = {"span": whole.interval(), "stages": stages}
    return precheck, pts, scalars, presorted, timing


def _prep_stream_chunk_sharded(
    pubkeys, msgs, sigs, lo: int, hi: int, na_c: int, nd: int
):
    """Sharded-arm prep worker task: chunk prep + the per-shard lane split
    AND per-shard window sorts (prepare_rlc_shards) — all off the
    submitting thread, so the mesh dispatch cadence is kernel-bound."""
    from tendermint_tpu.parallel.sharded import prepare_rlc_shards

    t0 = time.perf_counter()
    precheck, pts, scalars, _, _ = _prep_stream_chunk(
        pubkeys, msgs, sigs, lo, hi, na_c, sort=False
    )
    shards = prepare_rlc_shards(pts, scalars, nd)
    return precheck, shards, time.perf_counter() - t0


def _verify_batch_rlc_streamed(
    pubkeys: Sequence[bytes],
    msgs: Sequence[bytes],
    sigs: Sequence[bytes],
    mode: str = "streamed",
) -> Optional[np.ndarray]:
    """The streamed RLC combined check (see the planner note): fixed-bucket
    chunks through rlc_partial_submit, double-buffered host prep, on-device
    partial accumulation, one identity check. Returns the mask when the
    combined check passes, None -> the caller recovers the exact per-row
    mask chunk by chunk.

    An in-budget flush (_verify_batch_pipelined, mode "pipelined") is the
    planner's one span [(0, n)]: no partial_fold, no prep hidden behind a
    kernel. Prep/device overlap is windowed accounting (_overlap_seconds):
    prep-task wall spans intersected with the union of device-busy
    intervals (each chunk's submit-return through its sync-return)."""
    from collections import deque

    from tendermint_tpu.ops import msm_jax

    _device_fault("rlc_submit")
    msm_jax._set_submit_fused(False)
    counters0 = dict(msm_jax.flush_counters())
    n = len(pubkeys)
    na_c = planner_budget() // 2
    chunks = _planner_chunks(n)
    pool = _prep_pool()
    flush_span = _trace.current()  # rlc.pipelined / rlc.streamed: the workers' parent
    prechecks: list = [None] * len(chunks)
    acc = None
    inflight: deque = deque()  # (chunk idx, unsynced lane-validity array)
    lanes_ok = [True]
    prep_total = [0.0]
    prep_spans: list = []
    dev_busy: list = []
    submit_t: list = [None] * len(chunks)
    stage_totals: dict = {}
    peak_lanes = [0]

    def _sync_oldest():
        k, dev_ok = inflight.popleft()
        _device_fault("rlc_finish")
        with _trace.timed("flush.sync", chunk=k) as sy:
            ok = np.asarray(dev_ok)  # blocks until chunk k's kernels land
        dev_busy.append((submit_t[k], sy.interval()[1]))
        pc = prechecks[k]
        c = chunks[k][1] - chunks[k][0]
        if pc.any() and not (
            ok[:c][pc].all() and ok[na_c : na_c + c][pc].all()
        ):
            lanes_ok[0] = False

    fut = pool.submit(
        _prep_stream_chunk, pubkeys, msgs, sigs, *chunks[0], na_c,
        chunk=0, parent=flush_span,
    )
    for k in range(len(chunks)):
        with _trace.span("flush.prep_wait", chunk=k):
            precheck, pts, scalars, presorted, timing = fut.result()
        span = timing["span"]
        prep_total[0] += span[1] - span[0]
        prep_spans.append(span)
        for sk, sv in timing["stages"].items():
            stage_totals[sk] = stage_totals.get(sk, 0.0) + sv
        prechecks[k] = precheck
        if k + 1 < len(chunks):
            fut = pool.submit(
                _prep_stream_chunk, pubkeys, msgs, sigs, *chunks[k + 1], na_c,
                chunk=k + 1, parent=flush_span,
            )
        part, dev_ok = msm_jax.rlc_partial_submit(
            pts, scalars, zero16_from=na_c, presorted=presorted
        )
        submit_t[k] = time.perf_counter()
        # device-resident accumulation: one tiny padd fold per chunk; the
        # chunk's big intermediates die with its kernel, only the (4, 20)
        # accumulator and the lane flags persist
        acc = part if acc is None else msm_jax.partial_fold_submit(acc, part)
        inflight.append((k, dev_ok))
        # planner-side accounting of submitted-but-unsynced chunks (an
        # independent throttle-order witness lives in
        # tests/test_flush_planner.py's outstanding-submission tracker)
        peak_lanes[0] = max(peak_lanes[0], len(inflight) * 2 * na_c)
        if len(inflight) >= 2:
            # throttle: sync the older chunk's flags before submitting the
            # next — lanes in flight are bounded at 2 chunks, never more
            _sync_oldest()
    while inflight:
        _sync_oldest()
    try:
        _device_fault("rlc_finish")
        with _trace.timed("flush.sync", what="identity") as sy:
            batch_ok = bool(np.asarray(msm_jax.partial_identity_submit(acc)))
    except Exception as e:
        _trace.mark_device_call(ok=False, error=repr(e))
        raise
    _trace.mark_device_call(ok=True)
    dev_busy.append(sy.interval())
    _record_submit_counters(msm_jax, counters0)
    LAST_FLUSH_DETAIL.update(
        jit_bucket=na_c,
        padding_lanes=len(chunks) * 2 * na_c - (2 * n + len(chunks)),
        chunks=len(chunks),
        chunk_lanes=2 * na_c,
        prep_s=prep_total[0],
        prep_overlap_s=_overlap_seconds(prep_spans, dev_busy),
        prep_stages={k: round(v, 6) for k, v in stage_totals.items()},
        peak_lanes_in_flight=peak_lanes[0],
        transfer_s=sy.seconds,
    )
    LAST_RLC_TIMINGS.update(
        prep_ms=prep_total[0] * 1e3,
        total_ms=(sy.interval()[1] - prep_spans[0][0]) * 1e3,
        cached=False,
        mode=mode,
    )
    if batch_ok and lanes_ok[0]:
        return np.concatenate(prechecks)
    return None


def _verify_batch_rlc_sharded_streamed(
    pubkeys: Sequence[bytes],
    msgs: Sequence[bytes],
    sigs: Sequence[bytes],
    env=None,
) -> Optional[np.ndarray]:
    """The planner's multi-chip arm: fixed-bucket chunks stream ACROSS the
    mesh (parallel/sharded.sharded_rlc_stream) — per-shard lane slices via
    prepare_rlc_shards with chunk-multiple padding per shard, per-shard
    device-resident partial accumulation, ONE all_gather at the end. Host
    prep double-buffers exactly like the single-device arm.

    Elastic replay (ISSUE 19): a shard/device failure mid-stream feeds the
    health model, invalidates the mesh cache, and REPLAYS the whole flush
    from chunk 0 on whatever topology _sharded_env() now offers — the
    survivor mesh re-preps every chunk (per-shard accumulators died with
    the old mesh), so the verdict mask is byte-identical to the unfaulted
    run. Descent is bounded (_MESH_REPLAY_ATTEMPTS); when the mesh is gone
    the caller takes the single-chip rung. A bad SIGNATURE is not a fault:
    the combined check returns False without raising, and the exact-mask
    recovery path handles it, so the PR 16 verified-row memo keeps its
    never-cache-on-failure semantics through any replay.

    `env` pins one topology (prewarm's survivor warm); pinned calls never
    replay. Returns the mask, or None -> next rung in the caller."""
    pinned = env is not None
    replays = 0
    for _attempt in range(_MESH_REPLAY_ATTEMPTS):
        e = env if pinned else _sharded_env()
        if e is None:
            return None
        try:
            mask = _run_sharded_stream(e, pubkeys, msgs, sigs)
        except _MeshReplay:
            if pinned:
                return None
            replays += 1
            continue
        if mask is not None and replays:
            LAST_FLUSH_DETAIL["mesh_replays"] = replays
        return mask
    return None


def _run_sharded_stream(
    env, pubkeys: Sequence[bytes], msgs: Sequence[bytes], sigs: Sequence[bytes]
) -> Optional[np.ndarray]:
    """One streamed pass over one mesh topology (see the replay contract
    above). Raises _MeshReplay on device/mesh errors; returns None only for
    a failed combined check (bad signature somewhere)."""
    from collections import deque

    nd = env[0]
    run_chunk, finish = env[3]
    n = len(pubkeys)
    na_c = planner_budget() // 2
    while (2 * na_c) % nd:
        na_c += 1  # per-shard lane slices must tile the mesh exactly
    chunks = _planner_chunks(n)
    from tendermint_tpu.parallel import telemetry as _mesh_tm

    _mesh_tm.record_pad(
        requested_lanes=2 * n + len(chunks),
        padded_lanes=len(chunks) * 2 * na_c,
    )
    pool = _prep_pool()
    prechecks: list = [None] * len(chunks)
    inflight: deque = deque()
    lanes_ok = [True]
    prep_total = [0.0]
    overlap_s = [0.0]
    peak_lanes = [0]

    def _sync_oldest():
        k, dev_ok = inflight.popleft()
        _device_fault("rlc_finish")
        ok = np.asarray(dev_ok).reshape(-1)
        pc = prechecks[k]
        c = chunks[k][1] - chunks[k][0]
        if pc.any() and not (
            ok[:c][pc].all() and ok[na_c : na_c + c][pc].all()
        ):
            lanes_ok[0] = False

    try:
        acc = None
        fut = pool.submit(
            _prep_stream_chunk_sharded, pubkeys, msgs, sigs, *chunks[0],
            na_c, nd,
        )
        for k in range(len(chunks)):
            t_wait = time.perf_counter()
            precheck, shards, prep_s = fut.result()
            blocked = time.perf_counter() - t_wait
            prep_total[0] += prep_s
            if k > 0:
                overlap_s[0] += max(0.0, prep_s - blocked)
            prechecks[k] = precheck
            if k + 1 < len(chunks):
                fut = pool.submit(
                    _prep_stream_chunk_sharded, pubkeys, msgs, sigs,
                    *chunks[k + 1], na_c, nd,
                )
            acc, dev_ok = run_chunk(*shards, acc)
            inflight.append((k, dev_ok))
            peak_lanes[0] = max(peak_lanes[0], len(inflight) * 2 * na_c)
            if len(inflight) >= 2:
                _sync_oldest()
        while inflight:
            _sync_oldest()
        batch_ok = bool(np.asarray(finish(acc)))
    except Exception as exc:
        import logging

        hm = _mesh_health()
        if not getattr(exc, "_mesh_scored", False):
            # surfaced at a host-side sync (np.asarray), outside
            # sharded.py's guard — score it here (attribution probes or
            # the exception's own shard/device stamp, parallel/health.py)
            hm.record_failure(_env_devices(env), exc)
        if not getattr(exc, "_mesh_attributed", False):
            # no single device owns this failure: strike the MESH rung of
            # the breaker (per-backend states) — the single-chip device
            # path stays armed
            BREAKER.record_backend_failure("mesh", repr(exc))
        invalidate_sharded_env()
        _publish_mesh_health()
        logging.getLogger("tendermint_tpu.crypto.batch").exception(
            "sharded streamed RLC failed; elastic replay on the surviving "
            "topology"
        )
        raise _MeshReplay from exc
    BREAKER.record_backend_success("mesh")
    LAST_FLUSH_DETAIL.update(
        jit_bucket=na_c,
        padding_lanes=len(chunks) * 2 * na_c - (2 * n + len(chunks)),
        chunks=len(chunks),
        chunk_lanes=2 * na_c,
        prep_s=prep_total[0],
        prep_overlap_s=overlap_s[0],
        peak_lanes_in_flight=peak_lanes[0],
    )
    if batch_ok and lanes_ok[0]:
        return np.concatenate(prechecks)
    return None


def _unfused_retry(e: Exception, retried: bool, was_fused: bool, failed: str) -> bool:
    """What the three combined checks do with an exception from an attempt,
    called from their `except`. A fused attempt's failure (e.g. a Mosaic
    lowering rejection on this TPU generation) must not cost the RLC path:
    stick to the unfused reference schedule and retry this flush once
    (True). Any other failure (cache churn past capacity, device error) is
    logged as `failed` and the caller answers None — it recovers exactly —
    rather than propagating into the consensus receive loop (False).

    A policy the callers' own loops ask, not a wrapper that runs them: what
    JAX's lowering of a plain jit costs depends on the Python stack it is
    first called under, and two frames added between `_verify_batch_rlc` and
    `decompress_rows` took that program's lowering from 4.4 to 23 s on the
    chip's host (PERF.md section 6, PR 31)."""
    if was_fused and not retried:
        from tendermint_tpu.ops import msm_jax

        msm_jax.disable_fused(repr(e))
        return True
    import logging

    logging.getLogger("tendermint_tpu.crypto.batch").exception(failed)
    return False


def _verify_batch_pipelined(
    pubkeys: Sequence[bytes], msgs: Sequence[bytes], sigs: Sequence[bytes]
) -> Optional[np.ndarray]:
    """In-budget flush above the stream floor: all n rows as ONE chunk on
    the planner's one warm chunk bucket (planner_budget()//2 rows), so no
    per-size shape compiles — verify_batch_jax's fast path and the recovery
    ladder's combined check of a sub-range (_bisect_recover) alike.
    Declines (None) only over planner_chunk_rows(). Returns the mask when
    the combined check passes; None -> the caller recovers through the
    per-signature ladder (never recursively through verify_batch_jax).

    The names (`rlc.pipelined`, mode "pipelined", path `rlc-pipelined`)
    date from ISSUE 18's head/tail split, which ran the fixed-shape chunk
    program twice to hide 3 ms of prep (gone, ISSUE 30). The benchmark
    reads them: a rename belongs to a `benchmark` PR."""
    from tendermint_tpu.ops import msm_jax

    n = len(pubkeys)
    if planner_engaged(n):
        return None  # more than one chunk holds: the streamed path's
    for retried in (False, True):
        try:
            with _trace.span("rlc.pipelined", n=n):
                return _verify_batch_rlc_streamed(
                    pubkeys, msgs, sigs, mode="pipelined"
                )
        except Exception as e:
            if not _unfused_retry(
                e, retried, msm_jax.last_submit_fused(),
                "pipelined RLC failed; recovering per-signature",
            ):
                return None


def _verify_batch_streamed(
    pubkeys: Sequence[bytes], msgs: Sequence[bytes], sigs: Sequence[bytes]
) -> np.ndarray:
    """Planner-engaged verification (row count above the chunk budget):
    streamed combined check first; on failure (a bad signature somewhere, an
    invalid encoding, or a device error) recover the EXACT per-row mask one
    planner chunk at a time through the normal verify_batch_jax ladder —
    each recovery chunk is at most the budget, so even the failure path
    never materializes an over-budget device shape."""
    from tendermint_tpu.ops import msm_jax

    mask = None
    sharded_tried = False
    if _sharded_env() is not None:
        sharded_tried = True
        mask = _verify_batch_rlc_sharded_streamed(pubkeys, msgs, sigs)
        if mask is not None:
            LAST_JAX_PATH[0] = "rlc-sharded-streamed"
            return mask
    # Single-chip streamed rung: either this host was never meshed, or the
    # mesh fell off the ladder MID-FLUSH (device loss exhausted the replay
    # attempts / tripped the mesh rung — _sharded_env() is None now). A
    # sharded attempt that failed with the mesh still standing was a bad
    # SIGNATURE: skip straight to exact recovery, a single-chip rerun of
    # the same combined check would just fail again.
    if not sharded_tried or _sharded_env() is None:
        for retried in (False, True):
            try:
                with _trace.span("rlc.streamed", n=len(pubkeys)):
                    mask = _verify_batch_rlc_streamed(pubkeys, msgs, sigs)
                break
            except Exception as e:
                if not _unfused_retry(
                    e, retried, msm_jax.last_submit_fused(),
                    "streamed RLC failed; recovering chunk by chunk",
                ):
                    mask = None
                    break
        if mask is not None:
            LAST_JAX_PATH[0] = "rlc-streamed"
            return mask
    # exact recovery: the combined check only short-circuits when every row
    # passes; chunk-local RLC + per-sig fallback recovers the identical mask
    # a single-flush fallback would have produced, with bounded memory
    detail = {
        k: LAST_FLUSH_DETAIL.get(k)
        for k in ("chunks", "chunk_lanes", "peak_lanes_in_flight")
    }
    parts = []
    for lo, hi in _planner_chunks(len(pubkeys)):
        parts.append(verify_batch_jax(pubkeys[lo:hi], msgs[lo:hi], sigs[lo:hi]))
    LAST_FLUSH_DETAIL["rlc_fallback"] = True
    for k, v in detail.items():
        if v is not None:
            LAST_FLUSH_DETAIL[k] = v
    LAST_JAX_PATH[0] = "rlc-streamed-recovery"
    return np.concatenate(parts)


def _verify_batch_rlc(
    pubkeys: Sequence[bytes],
    msgs: Sequence[bytes],
    sigs: Sequence[bytes],
    key_types: Sequence[str] | None = None,
) -> Optional[np.ndarray]:
    """RLC fast path. Returns the bool mask if the combined check passes,
    or None when the caller must fall back to the per-signature kernel
    (some signature failed, or an encoding was invalid)."""
    from tendermint_tpu.ops import msm_jax

    t0 = time.perf_counter()
    for retried in (False, True):
        call = None
        try:
            call = _rlc_submit(pubkeys, msgs, sigs, key_types)  # span rlc.submit
            with _trace.span("rlc.finish", mode=call.mode):
                mask = _rlc_finish(call)
            break
        except Exception as e:
            # Per-call fused flag when the submit completed; the module
            # global only for a failure inside the submit itself (a
            # concurrent thread's submit could have rewritten it since).
            was_fused = (
                call.fused if call is not None else msm_jax.last_submit_fused()
            )
            if not _unfused_retry(
                e, retried, was_fused,
                "RLC fast path failed; falling back to per-signature verification",
            ):
                return None
    LAST_RLC_TIMINGS.update(
        prep_ms=call.prep_seconds * 1e3,
        total_ms=(time.perf_counter() - t0) * 1e3,
        cached=call.mode == "cached",
        mode=call.mode,
    )
    return mask


# Which path the last verify_batch_jax call took: "rlc", "persig", "sharded"
# (observability + tests).
LAST_JAX_PATH: list = [""]

_SHARDED_RUNNER = None  # cached ((n_devices, health_generation), env)
_SHARDED_BUILD_LOCK = threading.Lock()  # non-blocking: vote lane never waits
_RUNNER_CACHE: dict = {}  # device-key tuple -> env; survives rebuilds, so
# re-selecting a previously-built topology (rejoin to full mesh, prewarmed
# survivor half-mesh) reuses its warm jit closures instead of recompiling
_LAST_MESH_ND = [0]  # previously built mesh size (rebuild telemetry)
_MESH_REPLAY_ATTEMPTS = 4  # bounded ladder descent per streamed flush


class _MeshReplay(Exception):
    """Internal: a sharded flush died on a device/mesh error; the health
    model has been fed and the mesh cache invalidated — the caller should
    replay the flush on whatever topology _sharded_env() now offers."""


def _mesh_health():
    from tendermint_tpu.parallel import health as _mh

    return _mh.MESH_HEALTH


def invalidate_sharded_env() -> None:
    """Drop the cached mesh runner (health-generation change, shard
    failure): the next _sharded_env() call re-selects the healthy topology.
    Runner closures persist in _RUNNER_CACHE, so a re-selected shape is a
    warm dispatch, not a recompile."""
    global _SHARDED_RUNNER
    _SHARDED_RUNNER = None


def mesh_ladder_state() -> str:
    """Current degrade-ladder rung: full | survivor | single | host
    (parallel/health.py; gauge tendermint_tpu_mesh_ladder_state)."""
    try:
        import jax

        n_vis = len(jax.devices())
    except Exception:
        n_vis = 0
    cur = _SHARDED_RUNNER
    mesh_nd = cur[1][0] if cur is not None else 0
    return _mesh_health().ladder_state(
        n_vis,
        mesh_nd,
        not BREAKER.allow_device(),
        not BREAKER.allow_backend("mesh"),
    )


def _publish_mesh_health() -> None:
    """Push per-device health + the ladder rung into mesh telemetry (the
    /debug/mesh + /debug/verify_stats `mesh.health` block and the
    tendermint_tpu_mesh_device_health / _ladder_state gauges)."""
    try:
        from tendermint_tpu.parallel import telemetry as _mesh_tm

        _mesh_tm.record_mesh_health(_mesh_health().snapshot(), mesh_ladder_state())
    except Exception:  # observability must never break the verify path
        pass


def _on_mesh_rejoin() -> None:
    """Health-prober callback: a dead device passed its N clean probes —
    drop the survivor runner so the next flush rebuilds toward the full
    mesh, and re-arm the mesh rung."""
    invalidate_sharded_env()
    BREAKER.close_backend("mesh")
    _publish_mesh_health()


def _build_sharded_env(devs):
    """Construct (or fetch warm from _RUNNER_CACHE) the runner tuple for an
    exact device list."""
    key = tuple(str(d) for d in devs)
    env = _RUNNER_CACHE.get(key)
    if env is None:
        from tendermint_tpu.parallel.sharded import (
            make_mesh,
            sharded_rlc_check,
            sharded_rlc_stream,
            sharded_verify,
        )

        mesh = make_mesh(list(devs), axis_names=("vals",))
        env = (
            len(devs),
            sharded_verify(mesh),
            sharded_rlc_check(mesh),
            sharded_rlc_stream(mesh),
        )
        _RUNNER_CACHE[key] = env
    return env


def _env_devices(env) -> list:
    """Reverse-map a runner env to its device strings (health attribution
    for failures that surface at a host-side sync, outside sharded.py's
    guard). Unknown envs (test fakes) map to [] — attribution then rides
    the exception's own shard/device stamp, if any."""
    for key, v in _RUNNER_CACHE.items():
        if v is env:
            return list(key)
    return []


def _sharded_env():
    """Production multi-chip path: when >1 healthy jax device is visible,
    shard across a 1D mesh (parallel/sharded.py) of the largest
    power-of-two of the HEALTHY devices (parallel/health.py) — the elastic
    rung selection: a full mesh while everything is alive, a rebuilt
    survivor mesh after a device loss, None (-> single-chip fused RLC)
    when fewer than 2 healthy devices remain or the breaker's "mesh" rung
    is open. The cache is keyed on (mesh size, health generation), and a
    rebuild happens behind a NON-BLOCKING lock: a flush arriving mid-
    rebuild (e.g. the scheduler's vote lane) routes single-chip immediately
    instead of waiting on mesh construction.

    Returns (n_devices, persig_run, rlc_run, (run_chunk, finish)) or None."""
    global _SHARDED_RUNNER
    knob = os.environ.get("TMTPU_SHARDED", "auto")
    if knob == "0":
        return None
    import jax

    devs = jax.devices()
    if knob != "1" and devs and devs[0].platform == "cpu":
        # "auto" engages only on accelerator platforms: the CPU test env
        # exposes 8 virtual devices for mesh tests, but routing every
        # verify_batch through shard_map there would just burn compiles.
        return None
    if not BREAKER.allow_backend("mesh"):
        return None
    hm = _mesh_health()
    hm.add_rejoin_listener(_on_mesh_rejoin)
    healthy = hm.healthy_devices(devs)
    if not healthy:
        return None
    nd = 1 << (len(healthy).bit_length() - 1)  # largest pow2 <= healthy
    if nd < 2:
        return None
    key = (nd, hm.generation)
    cur = _SHARDED_RUNNER
    if cur is not None and cur[0] == key:
        return cur[1]
    if not _SHARDED_BUILD_LOCK.acquire(blocking=False):
        return None  # rebuild in flight: degrade THIS flush, never wait
    try:
        cur = _SHARDED_RUNNER
        if cur is not None and cur[0] == key:
            return cur[1]
        t0 = time.perf_counter()
        env = _build_sharded_env(healthy[:nd])
        _SHARDED_RUNNER = (key, env)
        prev = _LAST_MESH_ND[0]
        _LAST_MESH_ND[0] = nd
        if prev and prev != nd:
            from tendermint_tpu.parallel import telemetry as _mesh_tm

            _mesh_tm.record_rebuild(prev, nd, time.perf_counter() - t0)
    finally:
        _SHARDED_BUILD_LOCK.release()
    _publish_mesh_health()
    return env


def _sharded_runner():
    env = _sharded_env()
    return env[1] if env is not None else None


def _verify_batch_rlc_sharded(
    pubkeys: Sequence[bytes], msgs: Sequence[bytes], sigs: Sequence[bytes]
) -> Optional[np.ndarray]:
    """Multi-chip RLC fast path: ONE combined Pippenger check with lanes
    sharded across the mesh (parallel/sharded.sharded_rlc_check) — each chip
    runs a partial MSM over its lane shard, partial points are all-gathered
    over ICI and summed. ~10x less per-chip work than the sharded per-sig
    ladder. Returns the mask, or None -> per-sig sharded fallback.

    Elastic (ISSUE 19): a device/mesh error feeds the health model and the
    flush replays on the survivor topology (host prep — hashing, scalars —
    is mesh-independent and computed once; only the nd-dependent padding
    and shard split re-derive per attempt)."""
    from tendermint_tpu.crypto.ed25519_ref import BASE, point_compress
    from tendermint_tpu.parallel.sharded import prepare_rlc_shards

    if _sharded_env() is None:
        return None
    n = len(pubkeys)
    from tendermint_tpu import native

    use_native = native.available()
    if use_native:
        precheck, a_rows, r_rows, s_rows, h_rows = _precheck_and_hash_fast(
            pubkeys, msgs, sigs
        )
        z16, w_rows, u = _rlc_scalars_fast(precheck, s_rows, h_rows)
    else:
        precheck, a_rows, r_rows, s_ints, hk_ints = _precheck_and_hash(
            pubkeys, msgs, sigs
        )
        zs, w_scalars, u = _rlc_scalars(precheck, s_ints, hk_ints, n)

    for _attempt in range(_MESH_REPLAY_ATTEMPTS):
        env = _sharded_env()
        if env is None:
            return None
        nd, _, rlc_run, _stream = env
        # NOTE: no decoded-pubkey cache on this path yet — every height
        # re-decodes A in-kernel (acceptable: this path only runs on
        # multi-chip hosts, which this environment cannot exercise beyond
        # the dryrun); a cached-A sharded variant is the natural next step.
        na = _lane_bucket(n + 1)
        while (2 * na) % nd:
            na += 1
        # Round the per-shard lane count up to a fused-chunk multiple when
        # the padding stays modest (<= 25%): each shard then runs the
        # VMEM-resident fused stage pipeline (ops/pallas_msm.py) instead of
        # the per-level schedule — e.g. 10k validators on 8 chips pad
        # 20480 -> 24576 lanes (3x1024 per shard) for the fused
        # tree/prefix/bucket kernels.
        from tendermint_tpu.ops import msm_jax as _msm

        if _msm.fused_for_lanes(nd * 1024):
            target = nd * 1024
            padded = -(-2 * na // target) * target
            if 4 * padded <= 5 * (2 * na):
                na = padded // 2
        # Mesh telemetry: the padding decision happens HERE (sharded.py
        # only ever sees padded arrays), so pad waste is recorded here.
        from tendermint_tpu.parallel import telemetry as _mesh_tm

        _mesh_tm.record_pad(requested_lanes=2 * n + 1, padded_lanes=2 * na)
        b_enc = np.frombuffer(point_compress(BASE), dtype=np.uint8)
        pts = np.tile(b_enc, (2 * na, 1))
        if precheck.any():
            pts[:n][precheck] = a_rows[precheck]
            pts[na : na + n][precheck] = r_rows[precheck]
        if use_native:
            scalars = np.zeros((2 * na, 32), dtype=np.uint8)
            scalars[:n] = w_rows
            scalars[n] = np.frombuffer(
                ((L - u) % L).to_bytes(32, "little"), dtype=np.uint8
            )
            scalars[na : na + n, :16] = z16  # zeroed where ~precheck
        else:
            scalars = [0] * (2 * na)
            scalars[:n] = w_scalars
            scalars[n] = (L - u) % L
            scalars[na : na + n] = [
                zs[i] if precheck[i] else 0 for i in range(n)
            ]

        try:
            bok, ok = rlc_run(*prepare_rlc_shards(pts, scalars, nd))
        except Exception as exc:
            import logging

            hm = _mesh_health()
            if not getattr(exc, "_mesh_scored", False):
                hm.record_failure(_env_devices(env), exc)
            if not getattr(exc, "_mesh_attributed", False):
                BREAKER.record_backend_failure("mesh", repr(exc))
            invalidate_sharded_env()
            _publish_mesh_health()
            logging.getLogger("tendermint_tpu.crypto.batch").exception(
                "sharded RLC failed; elastic replay on the surviving "
                "topology"
            )
            continue
        BREAKER.record_backend_success("mesh")
        ok = np.asarray(ok)
        lanes_ok = (
            bool(ok[:n][precheck].all() and ok[na : na + n][precheck].all())
            if precheck.any()
            else True
        )
        if bool(np.asarray(bok)) and lanes_ok:
            LAST_JAX_PATH[0] = "rlc-sharded"
            return precheck
        return None  # combined check said no: bad signature, exact recovery
    return None


def _bisect_enabled() -> bool:
    """TMTPU_BISECT=0 restores the straight-to-per-sig recovery (bench
    baseline arm; docs/ROBUSTNESS.md adversarial flush defense)."""
    return os.environ.get("TMTPU_BISECT", "1") != "0"


# Bisection stops splitting at this range size and recovers the leaf
# per-signature: below a few hundred rows the per-sig kernel's one flush
# beats two more combined checks.
_BISECT_LEAF = 256

# Adaptive bail: once this many poisoned leaves have been isolated the flood
# is dense (high poison rate), so remaining ranges skip their combined
# checks and go straight per-sig — bisection must never cost more than the
# straight fallback by a growing factor.
_BISECT_MAX_BAD = 8


def _persig_flush(pubkeys, msgs, sigs, sharded) -> np.ndarray:
    """The exact per-signature kernel flush (sharded when a mesh runner is
    up): the recovery ladder's leaf and the primary path for small/non-RLC
    batches. Verdict = device mask & host precheck — byte-identical
    regardless of how the caller partitioned the rows."""
    from tendermint_tpu.ops.ed25519_jax import verify_prepared

    a, r, s_bits, h_bits, precheck, n = prepare_batch(pubkeys, msgs, sigs)
    t_dev = time.perf_counter()
    try:
        _device_fault("persig")
        if sharded is not None:
            LAST_JAX_PATH[0] = "sharded"
            mask = np.asarray(sharded(a, r, s_bits, h_bits))[:n]
        else:
            LAST_JAX_PATH[0] = "persig"
            mask = np.asarray(verify_prepared(a, r, s_bits, h_bits))[:n]
    except Exception as e:
        _trace.mark_device_call(ok=False, error=repr(e))
        raise
    _trace.mark_device_call(ok=True)
    LAST_FLUSH_DETAIL["transfer_s"] = time.perf_counter() - t_dev
    return mask & precheck


def _bisect_recover(pubkeys, msgs, sigs, chunk_bucket: bool = False) -> np.ndarray:
    """Exact-mask recovery after a combined-check failure, in
    O(bad · log(chunks)) flushes instead of one monolithic per-sig pass.

    The failed range splits at the largest power of two below its size —
    sub-ranges land on the SAME warm pow2 lane buckets (_bucket /
    _LANE_BUCKETS) a whole-flush fast path compiled. Where the fast path
    was the chunk-bucket flush (`chunk_bucket`, _verify_batch_pipelined)
    it compiled ONE shape, the planner's chunk bucket, and every
    sub-range's combined check rides that as one chunk: a whole-flush
    program per pow2 size (six under 10,624 rows, minutes each cold, PR 29)
    would load or compile behind the flush while every lane waits.
    Either way recovery compiles no new shape for its combined checks.
    Each half gets one combined check (sharded when meshed);
    a passing half is done (RLC pass returns the exact precheck mask, the
    same invariant the fast path rests on), a failing half recurses. When
    the first half passes, the second is KNOWN bad (the parent failed) and
    descends without re-checking. Ranges at/below the leaf size — and
    everything after _BISECT_MAX_BAD poisoned leaves (dense flood:
    splitting costs more than it saves) — recover per-signature, the
    byte-identical code path the straight fallback has always used.

    Cost for one bad row over C = ceil(n/leaf) chunks: at most
    2·ceil(log2 C)+1 device flushes (<= 2 combined checks per level, one
    per-sig leaf), vs 1 monolithic per-sig flush of n rows — the win is
    that n-leaf rows short-circuit through combined checks and the leaf
    flush is tiny, so a poisoned flood degrades the vote path by a log
    factor, not a linear one."""
    n = len(pubkeys)
    out = np.zeros(n, dtype=bool)
    leaf = _BISECT_LEAF
    max_bad = _BISECT_MAX_BAD
    flushes = 0
    bad_leaves = 0

    def _combined(lo, hi):
        # Mirrors the fast-path rung choice: sharded combined while a mesh
        # stands; if the mesh fell MID-CHECK, retry single-chip rather than
        # mislabel a device loss as a poisoned range.
        nonlocal flushes
        flushes += 1
        pk, ms, sg = pubkeys[lo:hi], msgs[lo:hi], sigs[lo:hi]
        if _sharded_runner() is not None:
            mask = _verify_batch_rlc_sharded(pk, ms, sg)
            if mask is not None or _sharded_runner() is not None:
                return mask
            flushes += 1
        if chunk_bucket:  # a sub-range of an in-budget flush: it fits
            return _verify_batch_pipelined(pk, ms, sg)
        return _verify_batch_rlc(pk, ms, sg)

    def _leaf(lo, hi):
        nonlocal flushes, bad_leaves
        flushes += 1
        bad_leaves += 1
        out[lo:hi] = _persig_flush(
            pubkeys[lo:hi], msgs[lo:hi], sigs[lo:hi], _sharded_runner()
        )

    def _go(lo, hi):
        # invariant: [lo, hi) is known to contain at least one bad row
        m = hi - lo
        if m <= leaf or m < 2 * RLC_MIN or bad_leaves >= max_bad:
            _leaf(lo, hi)
            return
        half = 1 << ((m - 1).bit_length() - 1)  # largest pow2 < m
        mid = lo + half
        first = _combined(lo, mid)
        if first is not None:
            out[lo:mid] = first
            _go(mid, hi)  # parent failed, first half clean: second is bad
            return
        _go(lo, mid)
        if hi - mid >= RLC_MIN and bad_leaves < max_bad:
            second = _combined(mid, hi)
            if second is not None:
                out[mid:hi] = second
                return
        _go(mid, hi)

    _go(0, n)
    LAST_FLUSH_DETAIL["recovery_flushes"] = (
        LAST_FLUSH_DETAIL.get("recovery_flushes", 0) + flushes
    )
    if bad_leaves > 1 or flushes > 1:
        LAST_JAX_PATH[0] = "rlc-bisect"
    return out


class _Route(NamedTuple):
    """Where a flush's rows run. `path` is the label on the flush record
    when the executor's first attempt answers (a failed combined check
    relabels itself: `rlc-bisect`, `rlc-streamed-recovery`, `cpu-degraded`)."""

    path: str
    backend: str  # the record's `backend`: "cpu" | "jax"
    async_ok: bool  # verify_batch_submit may leave the device work unsynced


def _flush_route(
    n: int,
    backend: str | None = None,
    key_types: Sequence[str] | None = None,
    *,
    on_device: bool = False,
) -> _Route:
    """THE routing rule: which executor takes an n-row flush, from what the
    code can observe (the backend asked for, the breaker, the key types, the
    row count against the thresholds, whether a mesh runner stands).
    verify_batch, verify_batch_jax, verify_batch_submit, prewarm and the
    scheduler's inline fallback all ask here; the executors only guard their
    own geometry.

    `on_device`: the caller already holds the decision "device"
    (verify_batch_jax: whoever sent the rows there asked the breaker, and a
    chunk of a streamed recovery must not change sides mid-flush); only the
    row count and the mesh are asked."""
    be = "jax" if on_device else (backend or backend_default())
    device = on_device or (be == "jax" and BREAKER.allow_device())
    if key_types is not None and any(t != "ed25519" for t in key_types):
        # Mixed sets above the RLC threshold verify both key types in ONE
        # device MSM (ed lanes via compressed-edwards decode, sr lanes via
        # ristretto decode; reference verifies each vote by its key type,
        # types/vote_set.go:203 — serial there, one batch here). Anything
        # else takes the exact per-type split, whose ed25519 rows re-enter
        # verify_batch: an over-budget mixed set streams through the
        # planner that way and never compiles an over-budget shape; and the
        # mixed kernel only knows these two types — a row of any other type
        # carrying an ed25519-valid triple would diverge between paths.
        if (
            device
            and n >= RLC_MIN
            and not planner_engaged(n)
            and all(t in ("ed25519", "sr25519") for t in key_types)
            and _sharded_runner() is None
        ):
            # an auto-selected backend submits nothing asynchronously under
            # _JAX_MIN_BATCH rows (seen only where a test lowers RLC_MIN
            # beneath it)
            return _Route("rlc-mixed", be, backend is not None or n >= _JAX_MIN_BATCH)
        return _Route("mixed", be, False)
    if not on_device:
        # Auto-selected jax falls back to the host loop for tiny batches: a
        # handful of signatures is faster on CPU than one device round
        # trip, and a 1-2 validator chain should never block on a kernel
        # compile. An EXPLICIT backend="jax" is honored regardless (tests,
        # benches).
        if be == "jax" and backend is None and n < _JAX_MIN_BATCH:
            be = "cpu"
        if be == "cpu":
            return _Route("cpu", "cpu", False)
        if be != "jax":
            raise ValueError(f"unknown crypto backend {be!r}")
        if not device:
            # Breaker OPEN: sticky CPU degrade — no device submit, no retry
            # storm; the probe thread re-arms the device path out of band.
            return _Route("cpu-breaker", "cpu", False)
    mesh = _sharded_runner() is not None
    if n < RLC_MIN:
        # the per-signature kernel's latency is fine at this size
        return _Route("sharded" if mesh else "persig", "jax", False)
    if planner_engaged(n):
        # over the device budget: fixed-bucket chunks through the flush
        # planner, which IS the submit/finish overlap, chunk-pipelined
        return _Route("rlc-sharded-streamed" if mesh else "rlc-streamed", "jax", False)
    if mesh:
        return _Route("rlc-sharded", "jax", False)
    # in-budget, one device: over the stream floor ONE chunk on the planner's
    # warm bucket, under it the per-size program's own, smaller bucket
    return _Route("rlc-pipelined" if n >= _stream_floor() else "rlc", "jax", True)


def verify_batch_jax(
    pubkeys: Sequence[bytes], msgs: Sequence[bytes], sigs: Sequence[bytes]
) -> np.ndarray:
    """The device executor: the route's program first, then exact recovery
    where a combined check said no."""
    path = _flush_route(len(pubkeys), on_device=True).path
    if path in ("persig", "sharded"):
        return _persig_flush(pubkeys, msgs, sigs, _sharded_runner())
    if path in ("rlc-streamed", "rlc-sharded-streamed"):
        # includes its own chunked exact-mask recovery: always a mask
        return _verify_batch_streamed(pubkeys, msgs, sigs)
    if path == "rlc-sharded":
        mask = _verify_batch_rlc_sharded(pubkeys, msgs, sigs)
    elif path == "rlc-pipelined":
        mask = _verify_batch_pipelined(pubkeys, msgs, sigs)
    else:
        mask = _verify_batch_rlc(pubkeys, msgs, sigs)
    if mask is not None:
        LAST_JAX_PATH[0] = path
        return mask
    # Combined check failed: at least one signature is bad (or an
    # encoding was invalid) — recover the exact per-signature mask,
    # bisecting over warm pow2 buckets so one poisoned row costs
    # O(log chunks) flushes, not a monolithic per-sig pass.
    LAST_FLUSH_DETAIL["rlc_fallback"] = True
    if _bisect_enabled():
        return _bisect_recover(
            pubkeys, msgs, sigs, chunk_bucket=path == "rlc-pipelined"
        )
    # Re-fetch the mesh runner: the RLC attempt above may have rebuilt
    # the mesh (survivor topology) or lost it entirely — the per-sig
    # fallback must not dispatch onto a dead mesh captured earlier.
    mask = _persig_flush(pubkeys, msgs, sigs, _sharded_runner())
    LAST_FLUSH_DETAIL["recovery_flushes"] = (
        LAST_FLUSH_DETAIL.get("recovery_flushes", 0) + 1
    )
    return mask


def _verify_batch_mixed_exact(
    pubkeys, msgs, sigs, key_types, backend=None
) -> np.ndarray:
    """Exact per-type routing for mixed sets: ed25519 rows through the
    selected backend, sr25519 rows through the host schnorrkel path,
    bls12_381 rows through the bls_ref host verifier (per-signature; the
    aggregate fast path lives in types/validator_set.verify_aggregate_commit
    — a commit that ARRIVES unaggregated pays per-sig pairing cost here),
    any unknown type False."""
    from tendermint_tpu.crypto.sr25519 import sr25519_verify

    out = np.zeros(len(pubkeys), dtype=bool)
    ed_idx = [i for i, t in enumerate(key_types) if t == "ed25519"]
    sr_idx = [i for i, t in enumerate(key_types) if t == "sr25519"]
    bls_idx = [i for i, t in enumerate(key_types) if t == "bls12_381"]
    if sr_idx:
        record_backend_rows("sr25519", len(sr_idx))
    if bls_idx:
        record_backend_rows("bls12_381", len(bls_idx))
        from tendermint_tpu.crypto import bls_ref

        for i in bls_idx:
            sig = bytes(sigs[i])
            out[i] = len(sig) == bls_ref.SIGNATURE_SIZE and bls_ref.verify(
                bytes(pubkeys[i]), bytes(msgs[i]), sig
            )
    if ed_idx:
        sub = verify_batch(
            [pubkeys[i] for i in ed_idx],
            [msgs[i] for i in ed_idx],
            [sigs[i] for i in ed_idx],
            backend,
        )
        out[ed_idx] = sub
    if sr_idx:
        from tendermint_tpu import native

        # Length pre-filter BEFORE packing: upstream ValidateBasic only
        # bounds signatures at <= 64 bytes, and a short row would misalign
        # the fixed-stride blobs (corrupting every later verdict and
        # reading past the buffer). Mirrors native.sr25519_verify's check.
        sr_ok = [
            i for i in sr_idx if len(bytes(sigs[i])) == 64 and len(bytes(pubkeys[i])) == 32
        ]
        if sr_ok and native.available():
            # one multithreaded native call instead of a per-sig loop
            srm = [bytes(msgs[i]) for i in sr_ok]
            moffs = np.zeros(len(sr_ok) + 1, dtype=np.int64)
            np.cumsum(
                np.fromiter(map(len, srm), dtype=np.int64, count=len(srm)),
                out=moffs[1:],
            )
            mask = native.sr25519_verify_batch(
                b"".join(bytes(pubkeys[i]) for i in sr_ok),
                b"".join(srm),
                moffs,
                b"".join(bytes(sigs[i]) for i in sr_ok),
            )
            out[sr_ok] = mask
        else:
            for i in sr_ok:
                out[i] = sr25519_verify(bytes(pubkeys[i]), bytes(msgs[i]), bytes(sigs[i]))
    return out


# ---------------------------------------------------------------------------
# Global verification scheduler hook (crypto/scheduler.py). When a consumer
# thread sits inside `scheduler.lane_scope(...)`, verify_batch /
# verify_batch_submit route their rows through the node-wide scheduler lane
# instead of dispatching their own flush — one global read + None check on
# every call when no scheduler is installed.

_LANE_ROUTER = None


def set_lane_router(router) -> None:
    """Install the scheduler's row router: callable(pubkeys, msgs, sigs,
    backend, key_types) -> mask | None (None = route normally)."""
    global _LANE_ROUTER
    _LANE_ROUTER = router


class FlushAccumulator:
    """Cross-request flush accumulation (light/service.py): while installed
    on this thread via `accumulate_flushes()`, every `verify_batch_submit`
    appends its (pubkey, msg, sig) rows here instead of dispatching its own
    device call, and `flush()` verifies ALL accumulated rows as ONE batch —
    many independent commit verifications (many clients x many heights)
    share a single device flush. Each submit's `verify_batch_finish`
    returns its own contiguous slice of the combined mask.

    Verdicts are byte-identical to per-request verification: the combined
    RLC check only short-circuits when EVERY row is valid, and any failure
    recovers the exact per-row mask (verify_batch's fallback ladder), so a
    bad signature in one client's commit never changes another client's
    verdict."""

    __slots__ = ("backend", "pubkeys", "msgs", "sigs", "key_types",
                 "_mask", "_flushed", "_error", "flush_count")

    def __init__(self, backend: Optional[str] = None):
        self.backend = backend
        self.pubkeys: list = []
        self.msgs: list = []
        self.sigs: list = []
        self.key_types: list = []
        self._mask: Optional[np.ndarray] = None
        self._flushed = False
        self._error: Optional[BaseException] = None
        self.flush_count = 0  # device flushes this accumulator issued

    @property
    def lanes(self) -> int:
        return len(self.pubkeys)

    def add(self, pubkeys, msgs, sigs, key_types) -> tuple:
        """Append one submit's rows; returns its (start, end) slice."""
        if self._flushed:
            raise RuntimeError("FlushAccumulator already flushed")
        start = len(self.pubkeys)
        self.pubkeys.extend(pubkeys)
        self.msgs.extend(msgs)
        self.sigs.extend(sigs)
        self.key_types.extend(
            key_types if key_types is not None else ["ed25519"] * len(pubkeys)
        )
        return start, len(self.pubkeys)

    def flush(self) -> np.ndarray:
        """Verify every accumulated row in one batch (idempotent — a failed
        flush latches its error and re-raises it for every later finish,
        rather than retrying the device or returning None). Must be called
        OUTSIDE the accumulate_flushes() scope or on an accumulator no
        longer installed — verify_batch itself routes normally."""
        if self._flushed:
            if self._error is not None:
                raise self._error
            return self._mask
        self._flushed = True
        if not self.pubkeys:
            self._mask = np.zeros(0, dtype=bool)
            return self._mask
        kt = (
            self.key_types
            if any(t != "ed25519" for t in self.key_types)
            else None
        )
        self.flush_count += 1
        try:
            self._mask = verify_batch(
                self.pubkeys, self.msgs, self.sigs, self.backend, kt
            )
        except BaseException as e:
            self._error = e
            raise
        return self._mask


_ACC_TLS = threading.local()


def current_accumulator() -> Optional[FlushAccumulator]:
    return getattr(_ACC_TLS, "current", None)


@contextlib.contextmanager
def accumulate_flushes(acc: Optional[FlushAccumulator] = None,
                       backend: Optional[str] = None):
    """Install a FlushAccumulator on THIS thread: verify_batch_submit calls
    inside the scope accumulate instead of dispatching. The scope exit does
    NOT flush — callers flush explicitly (or lazily via the first
    verify_batch_finish) so the one device call happens exactly where the
    coalescing window decides. Thread-local, like nothing else in this
    module is: the light service runs whole windows inside one worker
    thread, and an accumulator must never capture an unrelated thread's
    flushes."""
    acc = acc or FlushAccumulator(backend=backend)
    prev = getattr(_ACC_TLS, "current", None)
    _ACC_TLS.current = acc
    try:
        yield acc
    finally:
        _ACC_TLS.current = prev


class BatchHandle:
    """An in-flight verify_batch: device work submitted, not yet synced.
    Lets independent verification sites (e.g. the light client's
    trusting+light pair, reference light/verifier.go:32) overlap their
    device round trips instead of paying one each, serially."""

    __slots__ = ("_mask", "_call", "_args", "_t0", "_acc", "_acc_range",
                 "_digests")

    def __init__(self, mask=None, call=None, args=None, t0=None,
                 acc=None, acc_range=None, digests=None):
        self._mask = mask
        self._call = call
        self._args = args
        # verified-row memo digests (ISSUE 18), stashed at submit so finish
        # can insert the rows that verified OK without re-hashing
        self._digests = digests
        # submit-side wall-clock start: the flush record's total_s must span
        # submit THROUGH finish (docs/OBSERVABILITY.md: total = end-to-end),
        # not just the finish-side sync
        self._t0 = t0
        # cross-request accumulation (FlushAccumulator): finish() slices the
        # shared mask instead of syncing its own device call
        self._acc = acc
        self._acc_range = acc_range


def verify_batch_submit(
    pubkeys: Sequence[bytes],
    msgs: Sequence[bytes],
    sigs: Sequence[bytes],
    backend: str | None = None,
    key_types: Sequence[str] | None = None,
) -> BatchHandle:
    """Start a batch verification; pair with verify_batch_finish. RLC-eligible
    batches return with device work merely SUBMITTED (JAX async dispatch) so
    multiple submits queue back-to-back on device; anything else computes
    eagerly inside the handle."""
    acc = current_accumulator()
    if acc is not None:
        # cross-request accumulation scope (light/service.py): append the
        # rows to the shared flush; finish() slices the combined mask
        return BatchHandle(
            acc=acc, acc_range=acc.add(pubkeys, msgs, sigs, key_types)
        )
    if _LANE_ROUTER is not None and len(pubkeys) > 0:
        # scheduler lane scope (crypto/scheduler.py): the lane's combined
        # flush IS the async overlap — the handle comes back resolved
        mask = _LANE_ROUTER(pubkeys, msgs, sigs, backend, key_types)
        if mask is not None:
            return BatchHandle(mask=mask)
    mixed = key_types is not None and any(t != "ed25519" for t in key_types)
    if not (
        len(pubkeys) > 0
        and _flush_route(len(pubkeys), backend, key_types).async_ok
    ):
        # the eager path's own memo wiring (verify_batch) covers these rows
        return BatchHandle(
            mask=verify_batch(pubkeys, msgs, sigs, backend, key_types)
        )
    memo_digests = None
    if _MEMO.capacity:
        from tendermint_tpu import native

        with _trace.timed("verify_batch.memo", rows=len(pubkeys)) as ms:
            with _trace.span("memo.digest", rows=len(pubkeys), native=native.available()):
                memo_digests = _MEMO.digest_rows(pubkeys, msgs, sigs, key_types)
            nh = int(_MEMO.lookup(memo_digests).sum()) if len(_MEMO) else 0
            ms.set(hits=nh)
        if nh == len(pubkeys):
            # every row already verified OK: hand back a resolved handle —
            # no submit, no device round trip (the deferred-verified shape)
            _record_memo_answer(len(pubkeys), len(pubkeys), ms)
            return BatchHandle(mask=np.ones(len(pubkeys), dtype=bool))
    t0 = time.perf_counter()
    try:
        call = _rlc_submit(pubkeys, msgs, sigs, key_types if mixed else None)
    except Exception:
        import logging

        logging.getLogger("tendermint_tpu.crypto.batch").exception(
            "RLC submit failed; falling back to synchronous verification"
        )
        return BatchHandle(mask=verify_batch(pubkeys, msgs, sigs, backend, key_types))
    return BatchHandle(
        call=call, args=(pubkeys, msgs, sigs, backend, key_types, mixed), t0=t0,
        digests=memo_digests,
    )


# What a flush's executors leave in LAST_FLUSH_DETAIL for its record.
_DETAIL_FIELDS = (
    "prep_s", "transfer_s", "jit_bucket", "padding_lanes", "cache_hits",
    "cache_misses", "fused", "h2d_bytes", "device_dispatches", "chunks",
    "chunk_lanes", "prep_overlap_s", "prep_stages",
)


def _record_flush(detail: dict, **head) -> None:
    """One flush record (libs/trace.record_flush): `head` is what the caller
    knows (backend, path, counts, total), the rest is read out of the
    flush's detail dictionary. `rlc_fallback` and `recovery_flushes` are the
    caller's to pass: an async finish's detail may still hold an earlier
    flush's."""
    _trace.record_flush(**head, **{k: detail.get(k) for k in _DETAIL_FIELDS})


def _record_memo_answer(rows: int, hits: int, pass_) -> None:
    """The flush record of rows answered from the verified-row memo with no
    verify at all: path `memo`, `n` = the rows answered, `memo_rows` the rows
    asked. Its total is the memo's own pass `pass_` (digests and look-up)."""
    _trace.record_flush(
        backend="memo",
        path="memo",
        n=hits,
        total_s=pass_.seconds,
        n_valid=hits,
        memo_hits=hits,
        memo_rows=rows,
        memo_s=pass_.seconds,
        tracer_=_trace.tracer if pass_.recording else None,
    )


def verify_batch_finish(h: BatchHandle) -> np.ndarray:
    if h._mask is not None:
        return h._mask
    if h._acc is not None:
        # accumulated submit: the shared flush (lazy if the owner didn't
        # flush explicitly) already verified every row exactly once
        start, end = h._acc_range
        h._mask = h._acc.flush()[start:end]
        return h._mask
    pubkeys, msgs, sigs, backend, key_types, mixed = h._args
    tr = _trace.tracer if _trace.tracer.enabled else None  # record_flush's event
    # total spans submit through finish (h._t0); prep happened at submit
    t0 = h._t0 if h._t0 is not None else time.perf_counter()
    # breaker deadline clock starts at FINISH: submit-to-finish includes
    # host-side queueing (the caller batches finishes deliberately), which
    # must not read as device slowness and trip the flush deadline
    t_fin = time.perf_counter()
    try:
        if not BREAKER.allow_device():
            # OPEN means no device work AT ALL: in the hang failure mode a
            # sync on an already-submitted handle blocks for the full device
            # timeout — once per queued handle. Abandon the in-flight result
            # and recover below on the host.
            mask = None
        else:
            with _trace.span("rlc.finish", n=len(pubkeys), async_=True):
                mask = _rlc_finish(h._call)
    except Exception as e:
        # a device failure, not a combined-check failure: count it toward
        # the breaker's trip so the per-sig fallback below can short-circuit
        # to CPU once the threshold is hit (instead of re-dispatching every
        # queued handle into a dead device)
        BREAKER.record_failure(repr(e))
        if h._call is not None and h._call.fused:
            # a fused-pipeline execution failure: later submits must build
            # the unfused reference graph (this flush recovers below)
            from tendermint_tpu.ops import msm_jax

            msm_jax.disable_fused(repr(e))
        import logging

        logging.getLogger("tendermint_tpu.crypto.batch").exception(
            "RLC finish failed; falling back to exact verification"
        )
        mask = None
    detail = dict(LAST_FLUSH_DETAIL)
    if not mixed:
        record_backend_rows("ed25519", len(pubkeys))
    elif mask is not None:
        # successful mixed RLC finish: attribute here — the FAILED mixed
        # path recurses through _verify_batch_mixed_exact below, which
        # records its own per-scheme rows (submit eligibility limits the
        # mixed RLC branch to these two types)
        for kt in ("ed25519", "sr25519"):
            kn = sum(1 for t in key_types if t == kt)
            if kn:
                record_backend_rows(kt, kn)
    if mask is not None:
        h._mask = mask
        BREAKER.record_success(time.perf_counter() - t_fin)
        _record_flush(
            detail,
            backend="jax",
            path="rlc-async",
            n=len(pubkeys),
            total_s=time.perf_counter() - t0,
            n_valid=int(mask.sum()),
            tracer_=tr,
        )
        _MEMO.insert(h._digests, mask)
        return mask
    # combined check failed (or errored): recover the exact per-row mask.
    # The fallback rides verify_batch-instrumented paths (mixed-exact
    # recursion) or records its own persig-async flush below.
    if mixed:
        LAST_FLUSH_DETAIL["rlc_fallback"] = True
        h._mask = _verify_batch_mixed_exact(pubkeys, msgs, sigs, key_types, backend)
    elif not BREAKER.allow_device():
        # The handle was submitted before the breaker tripped (e.g. an
        # earlier finish in this same drain opened it): recover on the host
        # instead of dispatching yet another doomed device call — OPEN means
        # no device work, including for in-flight handles.
        h._mask = verify_batch_cpu(pubkeys, msgs, sigs)
        _trace.record_flush(
            backend="cpu",
            path="cpu-breaker",
            n=len(pubkeys),
            total_s=time.perf_counter() - t0,
            n_valid=int(h._mask.sum()),
            rlc_fallback=True,
            tracer_=tr,
        )
    else:
        try:
            # no mesh: the route that made this handle found none standing
            h._mask = _persig_flush(pubkeys, msgs, sigs, None)
        except Exception as e:
            h._mask = _degrade_flush_to_cpu(pubkeys, msgs, sigs, e)
            _trace.record_flush(
                backend="cpu",
                path="cpu-degraded",
                n=len(pubkeys),
                total_s=time.perf_counter() - t0,
                n_valid=int(h._mask.sum()),
                rlc_fallback=True,
                tracer_=tr,
            )
            return h._mask
        transfer_s = LAST_FLUSH_DETAIL.get("transfer_s")  # the leaf's device time
        BREAKER.record_success(transfer_s)
        _trace.record_flush(
            backend="jax",
            path="persig-async",
            n=len(pubkeys),
            total_s=time.perf_counter() - t0,
            n_valid=int(h._mask.sum()),
            transfer_s=transfer_s,
            jit_bucket=LAST_FLUSH_DETAIL.get("jit_bucket"),
            padding_lanes=LAST_FLUSH_DETAIL.get("padding_lanes"),
            rlc_fallback=True,
            tracer_=tr,
        )
    # exact recovery masks memoize too: every True row individually verified
    _MEMO.insert(h._digests, h._mask)
    return h._mask


def verify_batch(
    pubkeys: Sequence[bytes],
    msgs: Sequence[bytes],
    sigs: Sequence[bytes],
    backend: str | None = None,
    key_types: Sequence[str] | None = None,
    *,
    sources: Sequence[str] | None = None,
) -> np.ndarray:
    """Verify N (pubkey, msg, sig) triples; returns bool[N].

    sources: optional per-row provenance tags (crypto/provenance.py:
    "peer:<id>"/"sender:<id>"/"lane:<lane>"). Verdicts feed the suspicion
    scorer so sources whose rows fail get quarantined; None skips scoring.

    key_types: per-row key type ("ed25519"/"sr25519"); None means all
    ed25519. Mixed sets (BASELINE config 5) above RLC_MIN verify BOTH key
    types in one device MSM (sr lanes ristretto-decoded,
    ops/ristretto_jax.py); smaller mixed sets route ed25519 rows through the
    selected backend and sr25519 rows through the host schnorrkel path.

    Every flush is flight-recorded (libs/trace.py): one span + structured
    event naming the chosen path and batch size, plus the
    tendermint_batch_verify_* registry series. With tracing disabled the
    only added work is ONE flag read and the (always-on) metrics update."""
    if not (len(pubkeys) == len(msgs) == len(sigs)):
        raise ValueError("pubkeys/msgs/sigs length mismatch")
    if len(pubkeys) == 0:
        return np.zeros(0, dtype=bool)
    memo_digests = None
    if _MEMO.capacity:
        from tendermint_tpu import native

        with _trace.timed("verify_batch.memo", rows=len(pubkeys)) as ms:
            with _trace.span("memo.digest", rows=len(pubkeys), native=native.available()):
                memo_digests = _MEMO.digest_rows(pubkeys, msgs, sigs, key_types)
            hit = _MEMO.lookup(memo_digests) if len(_MEMO) else np.zeros(
                len(memo_digests), dtype=bool
            )
            nh = int(hit.sum())
            ms.set(hits=nh)
        if nh == len(pubkeys):
            # every row already verified OK in an earlier flush (the
            # deferred-verified commit shape): no residue, no device work
            _record_memo_answer(nh, nh, ms)
            if sources is not None:
                # memo-answered rows verified clean in an earlier flush:
                # they still count toward a quarantined source's parole
                try:
                    from tendermint_tpu.crypto import provenance as _prov

                    with _trace.span("provenance.score", rows=nh):
                        _prov.default_scorer().record_rows(
                            sources, np.ones(nh, dtype=bool)
                        )
                except Exception:
                    pass
            return np.ones(nh, dtype=bool)
        if nh:
            # partial hit: verify only the unseen residue (the recursive
            # call re-misses the residue digests and inserts its True rows)
            _record_memo_answer(len(pubkeys), nh, ms)
            if sources is not None:
                # memo-answered rows verified clean in an earlier flush:
                # they still count toward a quarantined source's parole
                # (same contract as the full-hit path above)
                try:
                    from tendermint_tpu.crypto import provenance as _prov

                    with _trace.span("provenance.score", rows=nh):
                        _prov.default_scorer().record_rows(
                            [sources[i] for i in np.flatnonzero(hit)],
                            np.ones(nh, dtype=bool),
                        )
                except Exception:
                    pass
            miss = ~hit
            idx = np.flatnonzero(miss)
            out = np.ones(len(pubkeys), dtype=bool)
            out[idx] = verify_batch(
                [pubkeys[i] for i in idx],
                [msgs[i] for i in idx],
                [sigs[i] for i in idx],
                backend,
                [key_types[i] for i in idx] if key_types is not None else None,
                sources=(
                    [sources[i] for i in idx] if sources is not None else None
                ),
            )
            return out
    if _LANE_ROUTER is not None:
        # scheduler lane scope (crypto/scheduler.py): these rows join the
        # node-wide combined flush; the router returns None outside a scope
        # (and for the scheduler's own dispatch flush), costing one global
        # read + None check on the unrouted path
        mask = _LANE_ROUTER(pubkeys, msgs, sigs, backend, key_types, sources)
        if mask is not None:
            return mask
    LAST_FLUSH_DETAIL.clear()
    compile0 = _trace.compile_seconds_total()
    with _trace.timed("verify_batch", n=len(pubkeys)) as vb:
        mask, be, path = _verify_batch_routed(
            pubkeys, msgs, sigs, backend, key_types
        )
        detail = dict(LAST_FLUSH_DETAIL)
        compile_s = _trace.compile_seconds_total() - compile0
        quarantined = None
        if sources is not None:
            # provenance feed (crypto/provenance.py): count rows whose source
            # was ALREADY quarantined when this flush ran (attribution for
            # the quarantine lane), then advance the suspicion state machines
            # with this flush's verdicts. Advisory: never allowed to break
            # the path.
            try:
                from tendermint_tpu.crypto import provenance as _prov

                with _trace.span("provenance.score", rows=len(sources)):
                    scorer = _prov.default_scorer()
                    q = scorer.quarantined_sources()
                    if q:
                        quarantined = sum(1 for s in sources if s in q) or None
                    scorer.record_rows(sources, mask)
            except Exception:
                quarantined = None
        # the flush's total closes HERE: the record's own body (flush.record)
        # and the memo insert below are the caller's time, not the flush's
        total_s = vb.elapsed()
        memo = {}
        if memo_digests is not None:
            # memoize the rows that verified OK (never on exception — we only
            # get here when the flush produced an exact per-row mask); before
            # the record, which says what the memo's two passes did and took
            with _trace.timed("verify_batch.memo", rows=len(pubkeys), insert=True) as mi:
                inserted = _MEMO.insert(memo_digests, mask)
                mi.set(inserted=inserted)
            memo = dict(memo_rows=len(pubkeys), memo_hits=0, memo_inserted=inserted,
                        memo_s=ms.seconds + mi.seconds)
        with _trace.span("flush.record"):
            _record_flush(
                detail,
                backend=be,
                path=path,
                n=len(pubkeys),
                total_s=total_s,
                n_valid=int(mask.sum()),
                compile_s=compile_s if compile_s > 0 else None,
                rlc_fallback=detail.get("rlc_fallback", False),
                recovery_flushes=detail.get("recovery_flushes"),
                quarantined=quarantined,
                tracer_=_trace.tracer if vb.recording else None,
                **memo,
            )
        vb.set(path=path, backend=be)
    return mask


def _verify_batch_routed(
    pubkeys, msgs, sigs, backend, key_types
) -> tuple:
    """verify_batch's executor dispatch; returns (mask, backend, path) so the
    flight recorder can label the flush with what actually ran."""
    route = _flush_route(len(pubkeys), backend, key_types)
    rlc_fell_back = False
    if route.path == "rlc-mixed":
        mask = _verify_batch_rlc(pubkeys, msgs, sigs, key_types)
        if mask is not None:
            LAST_JAX_PATH[0] = "rlc-mixed"
            for kt in ("ed25519", "sr25519"):
                kn = sum(1 for t in key_types if t == kt)
                if kn:
                    record_backend_rows(kt, kn)
            return mask, route.backend, "rlc-mixed"
        rlc_fell_back = True
    if rlc_fell_back or route.path == "mixed":
        mask = _verify_batch_mixed_exact(pubkeys, msgs, sigs, key_types, backend)
        if rlc_fell_back:
            # re-set AFTER mixed-exact: its per-type recursion through
            # verify_batch clears LAST_FLUSH_DETAIL for its own flush record
            LAST_FLUSH_DETAIL["rlc_fallback"] = True
        return mask, route.backend, "mixed"
    record_backend_rows("ed25519", len(pubkeys))
    if route.backend == "cpu":
        return verify_batch_cpu(pubkeys, msgs, sigs), "cpu", route.path
    t_dev = time.perf_counter()
    try:
        mask = verify_batch_jax(pubkeys, msgs, sigs)
    except Exception as e:
        return _degrade_flush_to_cpu(pubkeys, msgs, sigs, e), "cpu", "cpu-degraded"
    BREAKER.record_success(time.perf_counter() - t_dev)
    return mask, "jax", LAST_JAX_PATH[0]


def _prewarm_bls() -> None:
    """Warm the BLS aggregate path in the prewarm thread: module-level
    constant derivation (bls_ref's Frobenius/psi tables), one hash-to-G2
    + pairing, and the MSM bitmap-fold bucket (ops/bls12_msm) — so a
    node's FIRST aggregate-commit verify doesn't pay the import/derive
    cost inside the consensus receive loop. Throwaway key material only."""
    from tendermint_tpu.crypto import bls_ref
    from tendermint_tpu.ops import bls12_msm

    sk = bls_ref.gen_sk()
    pk = bls_ref.sk_to_pk(sk)
    sig = bls_ref.sign(sk, b"prewarm")
    aff = bls_ref._jac_to_affine(bls_ref.g1_from_bytes(pk))
    bls12_msm.g1_aggregate_bitmap([(aff[0].v, aff[1].v)] * 4, [True] * 4)
    bls_ref.verify(pk, b"prewarm", sig)


def _prewarm_survivor_mesh(pk: bytes, msg: bytes, sig: bytes) -> None:
    """Elastic-mesh satellite (ISSUE 19): pre-build the HALF-mesh runners
    (the next power-of-two down — the exact topology a single device loss
    rebuilds to) and push one minimal 2-chunk streamed flush through them.
    The runners land in _RUNNER_CACHE, which is exactly where a
    post-failure _sharded_env() rebuild looks first, so the first flush on
    the survivor mesh is a warm dispatch instead of a fresh XLA compile.
    Runs in prewarm's background thread; never raises."""
    try:
        env = _sharded_env()
        if env is None or env[0] < 4:
            return  # a 2-device mesh degrades to single-chip, not half-mesh
        import jax

        healthy = _mesh_health().healthy_devices(jax.devices())
        nd2 = env[0] // 2
        if len(healthy) < nd2:
            return
        surv = _build_sharded_env(healthy[:nd2])
        rows = planner_chunk_rows() + 1
        _verify_batch_rlc_sharded_streamed(
            [pk] * rows, [msg] * rows, [sig] * rows, env=surv
        )
    except Exception:
        import logging

        logging.getLogger("tendermint_tpu.crypto.batch").debug(
            "survivor-mesh prewarm failed", exc_info=True
        )


def prewarm(
    n_vals: int,
    backend: str | None = None,
    pubkeys: Sequence[bytes] | None = None,
    planner_chunk: bool = True,
    bls: bool = False,
) -> None:
    """Compile (or load from the persistent cache) the kernels a node with an
    n_vals validator set will hit: the plain RLC kernel (first sight of a
    key), the cached-A RLC kernel (steady state), and — by routing through
    verify_batch_jax — the sharded variants on multi-device hosts. When the
    node's REAL validator pubkeys are provided, their decoded coordinates are
    also pre-filled into the A cache so the very first consensus flush takes
    the steady-state path. With planner_chunk, the flush planner's chunk
    bucket (the ONE shape every streamed super-batch runs: rlc_partial +
    fold + identity, ops/msm_jax.py) is warmed in the same background
    thread, so the first oversized catch-up flush doesn't eat a multi-minute
    compile mid-sync.

    Called from node startup in a BACKGROUND thread (node/node.py) so a node
    cold-starting into a vote storm doesn't stall consensus for the first
    compile: jit compilation holds a per-executable lock, so a consensus
    flush that arrives mid-prewarm blocks until the compile finishes instead
    of compiling again. The throwaway signing key is random (os.urandom), so
    nothing derivable ever enters the cache."""
    if bls:
        _prewarm_bls()
    route = _flush_route(n_vals, backend)
    if route.backend != "jax":
        return  # a flush of this set rides the host loop; nothing to compile
    from tendermint_tpu.crypto.keys import gen_ed25519

    priv = gen_ed25519()
    pk = priv.pub_key().bytes()
    msg = b"prewarm"
    sig = priv.sign(msg)
    dummy = [pk] * n_vals
    msgs = [msg] * n_vals
    sigs = [sig] * n_vals
    # Spin the host-side prep machinery up front (ISSUE 18): the
    # single-worker flush-prep executor and the native worker pool, so the
    # first live staged flush never pays thread/pool startup. (The native
    # pool parks at the configured width when the library loads; touching
    # prep_pool_size() forces that load here, in the background thread.)
    _prep_pool()
    from tendermint_tpu import native

    if native.available():
        native.prep_pool_size()
    # The two single-flush warms below must exercise the PLAIN and CACHED-A
    # per-size kernels even when n_vals clears the stream floor (an async
    # submit of that size runs them; the chunk-bucket flush's shapes are the
    # planner-chunk shapes warmed further down): where the route is a
    # single-device in-budget flush the `rlc` executor is called directly,
    # elsewhere (per-signature sizes, a mesh) the route's own programs.
    warm = (
        _verify_batch_rlc if route.path in ("rlc", "rlc-pipelined")
        else verify_batch_jax
    )
    # 1st call: A cache cold for the dummy key -> PLAIN kernel (the variant
    # the first sight of any new validator set runs); fills the dummy entry.
    warm(dummy, msgs, sigs)
    # 2nd call: cache hit -> CACHED-A kernel (the steady-state variant).
    warm(dummy, msgs, sigs)
    if planner_chunk:
        # minimal 2-chunk streamed flush: warms the chunk-bucket partial
        # kernel (both chunks pad to the same shape), the padd fold, and
        # the identity check — the steady-state streamed shapes. The
        # in-budget _verify_batch_pipelined is one chunk on the same
        # bucket (partial kernel + identity check): this warm covers it
        rows = planner_chunk_rows() + 1
        verify_batch_jax([pk] * rows, [msg] * rows, [sig] * rows)
        # ISSUE 19: also warm the SURVIVOR half-mesh chunk bucket, so the
        # first post-device-loss flush pays a warm dispatch, not a compile
        _prewarm_survivor_mesh(pk, msg, sig)
    if pubkeys:
        # decode the real validator keys so consensus's first flush is a
        # cache hit (this is the exact decode steady state amortizes away)
        good = [
            np.frombuffer(bytes(k), dtype=np.uint8) for k in pubkeys if len(k) == 32
        ]
        if good:
            _fill_a_cache(np.stack(good))


class Ed25519BatchVerifier:
    """Accumulate-and-flush batch verifier (the interface the consensus vote
    path and commit verification use)."""

    def __init__(self, backend: str | None = None) -> None:
        self._backend = backend
        self._pubkeys: List[bytes] = []
        self._msgs: List[bytes] = []
        self._sigs: List[bytes] = []

    def add(self, pubkey: bytes, msg: bytes, sig: bytes) -> None:
        self._pubkeys.append(bytes(pubkey))
        self._msgs.append(bytes(msg))
        self._sigs.append(bytes(sig))

    def __len__(self) -> int:
        return len(self._pubkeys)

    def verify(self) -> np.ndarray:
        """Verify all accumulated triples; the batch stays (call reset())."""
        return verify_batch(self._pubkeys, self._msgs, self._sigs, self._backend)

    def reset(self) -> None:
        self._pubkeys.clear()
        self._msgs.clear()
        self._sigs.clear()
