"""Random-linear-combination (RLC) batch Ed25519 verification on TPU.

The fast path for large batches: instead of N independent double-scalar
ladders (ops/ed25519_jax.py, ~3.5k field muls per signature), check ONE
group equation over random 128-bit coefficients z_i:

    [sum z_i s_i mod L] B  ==  sum [z_i] R_i  +  sum [z_i h_i mod 8L] A_i

rearranged as  sum [w_i] A_i + [(L-u) mod L] B + sum [z_i] R_i == identity,
with w_i = z_i h_i mod 8L and u = sum z_i s_i mod L. Coefficients z_i are
random ~124-bit values FORCED to multiples of 8 and scalars are reduced mod
8L (the full curve-group order, so reduction is exact for points of ANY
order): the cofactor-8 torsion component of every lane is annihilated
deterministically, making the combined check exactly the COFACTORED batch
equation [8] sum z'_i (s_i B - h_i A_i - R_i) == identity. If every
per-signature cofactored equation holds the combination is the identity; if
any fails, it is the identity with probability <= ~2^-120 over the z_i. The
caller falls back to the per-signature kernel when the batch check fails to
recover the exact per-signature mask. COFACTORED (ZIP-215-style) is the
framework's single verification predicate on EVERY path — this batch check,
the per-sig kernel (ops/ed25519_jax.py), and the host wrapper
(crypto/keys.py via ed25519_ref.verify_cofactored) — so acceptance never
depends on which path a node runs. Honest keys and signatures are
torsion-free, where cofactored agrees exactly with the reference's
cofactorless check (types/validator_set.go:680-702); only crafted torsion
inputs ever see the (deliberate, documented) divergence from Go.

sr25519 (schnorrkel) shares the SAME equation shape (s B == R + k A over
ristretto255, which is this curve quotiented by its torsion): sr lanes join
the MSM with ristretto-decoded points (ops/ristretto_jax.py) and
transcript challenges k_i in place of h_i. Multiples-of-8 coefficients make
edwards-coordinate identity exactly equivalent to ristretto equality, so
the sr device path has NO semantic divergence from the host verifier.

The multiscalar multiplication is Pippenger reshaped for a vector machine
(no scatter, no data-dependent control flow on device):

  host   per 8-bit window: stable-sort lane indices by digit; compute
         per-bucket boundary positions; decompose each boundary prefix
         into its Fenwick (binary-representation) tree nodes.
  device 1. decompress points (invalid -> identity, flagged);
         2. gather lanes into sorted order per window;
         3. pair-tree up-sweep: node (l, k) = sum of sorted lanes
            [k*2^l, (k+1)*2^l)  — log2(N) unrolled vector adds, total
            work ~N lane-adds per window;
         4. gather <=16 tree nodes per bucket boundary and add them:
            prefix[v] = exact sum of all lanes with digit <= v;
         5. bucket_v = prefix[v] - prefix[v-1]; weighted bucket reduce
            via suffix sums (sum_v v*S_v = sum_j suffix_j);
         6. Horner combine across windows (8 doublings + 1 add each, on
            a single point).

Per signature this costs ~80 batched point additions + 2 point
decompressions, vs ~770 add-equivalents for the per-sig ladder — the
doubling chains (the per-lane ladder's fixed cost) are shared across the
whole batch, which is the entire idea of Pippenger.

Window size is fixed at 8 bits so digits are exactly the scalar bytes.

A-point caching: consensus verifies the SAME validator public keys every
height, so decompression of A (a ~250-mul sqrt chain per point) is cached
across calls keyed by pubkey bytes — see crypto/batch.py. The kernel
variant `_rlc_core_cached` accepts predecompressed A coordinates.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Sequence, Tuple

import os

import jax
import jax.numpy as jnp
import numpy as np

from tendermint_tpu.libs import trace as _trace
from tendermint_tpu.ops import aot_cache
from tendermint_tpu.ops import fe25519 as fe
from tendermint_tpu.ops.ed25519_jax import (
    FieldCtx,
    Point,
    decompress,
    identity,
    make_ctx,
)

WINDOW_BITS = 8
NWIN = 32  # 256 bits / 8
NBUCKETS = 1 << WINDOW_BITS
FENWICK_K = 17  # max tree levels: boundary prefixes reach N <= 2^16 lanes


# --------------------------------------------------------------------------
# Small-constant context: rank-agnostic (20,) buffers reshaped per use.
# The MSM kernel works at many intermediate shapes (per tree level, per
# bucket phase), so full-batch materialized constants (FieldCtx) are only
# used for the single decompress shape; everything else uses these.


class SmallCtx(NamedTuple):
    comp: jnp.ndarray  # (20,)
    corr: jnp.ndarray  # (20,)
    one: jnp.ndarray  # (20,)
    d2: jnp.ndarray  # (20,)


def make_small_ctx() -> SmallCtx:
    return SmallCtx(
        comp=jnp.asarray(np.asarray(fe.COMP)),
        corr=jnp.asarray(np.asarray(fe.CORR)),
        one=jnp.asarray(fe.from_int(1)),
        d2=jnp.asarray(fe.from_int(fe.D2)),
    )


def _rs(c: jnp.ndarray, ndim: int) -> jnp.ndarray:
    """Reshape a (20,) constant buffer for broadcasting against rank-ndim."""
    return c.reshape((fe.NLIMBS,) + (1,) * (ndim - 1))


def _sub(C: SmallCtx, a, b):
    return fe.sub(a, b, _rs(C.comp, a.ndim), _rs(C.corr, a.ndim))


def _neg(C: SmallCtx, a):
    return _sub(C, jnp.zeros_like(a), a)


def _use_pallas() -> bool:
    from tendermint_tpu.ops import pallas_fe

    return pallas_fe.enabled()


# ---------------------------------------------------------------------------
# Fused-pipeline selection (ops/pallas_msm.py). The fused schedule keeps the
# gather/up-sweep/prefix/bucket stages VMEM-resident in one packed layout;
# the unfused per-level schedule below stays as the differential reference
# and the fallback for lane counts no chunk size tiles.

# Sticky runtime kill switch: the first hardware failure of the fused path
# (e.g. a Mosaic lowering rejection on some TPU generation) flips this and
# every later submit builds the unfused graph — crypto/batch.py retries the
# failed flush unfused, so one bad compile costs one retry, not the RLC path.
_FUSED_DISABLED: list = [None]  # reason string once disabled

# Submit-path accounting is PER THREAD: the prewarm thread and the
# consensus event loop may submit concurrently, and thread-local state
# keeps one flush's byte/dispatch deltas and fused flag from being
# attributed to another's — without serializing the submit path (host
# prep plus a first-call kernel compile can take minutes) behind a lock.
class _FlushThreadState(__import__("threading").local):
    def __init__(self):
        self.counters = {"h2d_bytes": 0, "dispatches": 0}
        self.last_fused = False


_FLUSH_TLS = _FlushThreadState()


def flush_counters() -> dict:
    """This thread's cumulative submit-path device-traffic counters
    ("h2d_bytes", "dispatches"). Tests pin a per-flush budget on the deltas
    (tests/test_flush_budget.py) so a regression that reintroduces per-flush
    uploads or extra dispatches fails tier-1 instead of only showing up in a
    lost bench round."""
    return _FLUSH_TLS.counters


def last_submit_fused() -> bool:
    """Whether this thread's most recent rlc_check_*_submit built the fused
    graph (observability: crypto/batch.py copies it into the flush detail)."""
    return _FLUSH_TLS.last_fused


def _set_submit_fused(fused: bool) -> None:
    _FLUSH_TLS.last_fused = bool(fused)


def _dispatch(name: str, jit_fn, *args):
    """aot_cache.call with device-traffic accounting: every numpy leaf is a
    host->device upload on this call; jax-array leaves are device-resident.
    The `dispatch` span covers the accounting, the upload and the (async)
    launch of the AOT program `name`."""
    with _trace.span("dispatch", program=name) as sp:
        c = _FLUSH_TLS.counters
        c["dispatches"] += 1
        h2d = 0
        for leaf in jax.tree_util.tree_leaves(args):
            if isinstance(leaf, np.ndarray):
                h2d += leaf.nbytes
        c["h2d_bytes"] += h2d
        sp.set(h2d_bytes=h2d)
        return aot_cache.call(name, jit_fn, *args)


def fused_for_lanes(n_lanes: int) -> bool:
    """Route this lane count through the fused pipeline? TMTPU_FUSED_MSM:
    "0" never, "1" always (CPU twins included — tests), "auto" (default)
    with the Pallas kernels only."""
    if _FUSED_DISABLED[0] is not None:
        return False
    mode = os.environ.get("TMTPU_FUSED_MSM", "auto")
    if mode == "0":
        return False
    from tendermint_tpu.ops import pallas_msm

    if pallas_msm.chunk_for_lanes(n_lanes) is None:
        return False
    return True if mode == "1" else _use_pallas()


def disable_fused(reason: str) -> None:
    """Sticky per-process disable after a fused-path failure (see
    crypto/batch.py's retry); re-enabled only by a fresh process."""
    if _FUSED_DISABLED[0] is None:
        _FUSED_DISABLED[0] = reason
        import logging

        logging.getLogger("tendermint_tpu.ops.msm").warning(
            "fused MSM pipeline disabled for this process: %s", reason
        )


def _padd(C: SmallCtx, p: Point, q: Point) -> Point:
    """Unified a=-1 extended add (same formula as ed25519_jax.point_add but
    with rank-agnostic constants). On TPU this routes through the fused
    Pallas kernel (ops/pallas_fe.py) — ~11x the XLA fusion's field-mul
    throughput (the XLA conv churns its accumulator through HBM) and one
    custom call instead of ~500 HLO ops per add."""
    if _use_pallas():
        from tendermint_tpu.ops import pallas_fe

        return pallas_fe.padd(p, q)
    a = fe.mul(_sub(C, p.y, p.x), _sub(C, q.y, q.x))
    b = fe.mul(fe.add(p.y, p.x), fe.add(q.y, q.x))
    c = fe.mul(fe.mul(p.t, q.t), _rs(C.d2, p.t.ndim))
    d = fe.mul_small(fe.mul(p.z, q.z), 2)
    e = _sub(C, b, a)
    f = _sub(C, d, c)
    g = fe.add(d, c)
    h = fe.add(b, a)
    return Point(fe.mul(e, f), fe.mul(g, h), fe.mul(f, g), fe.mul(e, h))


def _pdbl(C: SmallCtx, p: Point) -> Point:
    if _use_pallas():
        from tendermint_tpu.ops import pallas_fe

        return pallas_fe.pdbl(p)
    xx = fe.square(p.x)
    yy = fe.square(p.y)
    zz2 = fe.mul_small(fe.square(p.z), 2)
    xy2 = fe.square(fe.add(p.x, p.y))
    e = _sub(C, xy2, fe.add(xx, yy))
    g = _sub(C, yy, xx)
    f = _sub(C, g, zz2)
    h = _neg(C, fe.add(xx, yy))
    return Point(fe.mul(e, f), fe.mul(g, h), fe.mul(f, g), fe.mul(e, h))


def _pdbl_n(C: SmallCtx, p: Point, n: int) -> Point:
    """[2^n] p. On TPU, doublings fuse into Pallas kernels in runs of 8
    (single-kernel chains longer than ~8 blow up Mosaic compile time for no
    runtime gain); elsewhere a plain unrolled loop."""
    if _use_pallas():
        from tendermint_tpu.ops import pallas_fe

        while n > 0:
            k = min(n, 8)
            p = pallas_fe.pdbl(p, times=k)
            n -= k
        return p
    for _ in range(n):
        p = _pdbl(C, p)
    return p


def _pneg(C: SmallCtx, p: Point) -> Point:
    return Point(_neg(C, p.x), p.y, p.z, _neg(C, p.t))


def _pidentity(C: SmallCtx, batch_shape) -> Point:
    z = jnp.zeros((fe.NLIMBS, *batch_shape), dtype=jnp.int32)
    one = jnp.broadcast_to(_rs(C.one, 1 + len(batch_shape)), z.shape)
    return Point(z, one, one, z)


def _pselect(cond, a: Point, b: Point) -> Point:
    return Point(
        fe.select(cond, a.x, b.x),
        fe.select(cond, a.y, b.y),
        fe.select(cond, a.z, b.z),
        fe.select(cond, a.t, b.t),
    )


# --------------------------------------------------------------------------
# Level geometry (shared host/device so Fenwick indices line up).


def level_widths(n_lanes: int) -> list:
    """Widths of the pair-tree levels: level 0 = n_lanes, each next level
    halves (odd widths padded up by one identity lane first)."""
    widths = [n_lanes]
    w = n_lanes
    while w > 1:
        w = (w + 1) // 2
        widths.append(w)
    return widths


def level_offsets(n_lanes: int) -> Tuple[list, int]:
    widths = level_widths(n_lanes)
    offs = []
    total = 0
    for w in widths:
        offs.append(total)
        total += w
    return offs, total


# --------------------------------------------------------------------------
# Host-side preparation.


def fenwick_node_indices(ends: np.ndarray, n_lanes: int) -> np.ndarray:
    """ends: (T, NBUCKETS) int32, ends[w, v] = number of lanes whose window-w
    digit is <= v. Returns (T, NBUCKETS, FENWICK_K) int32 of global indices
    into the concatenated tree-levels array; slot l holds the level-l node of
    the Fenwick decomposition of prefix [0, ends[w, v]) — or the identity
    lane (index = total width) when bit l of the boundary is clear.

    Derivation: writing e = sum over set bits 2^l, the prefix [0, e)
    decomposes into one aligned block per set bit: the level-l block starting
    at offset (e >> (l+1)) << (l+1), i.e. node index (e >> (l+1)) << 1."""
    offs, total = level_offsets(n_lanes)
    e = ends.astype(np.int64)
    out = np.full((*ends.shape, FENWICK_K), total, dtype=np.int32)  # identity pad
    for lvl in range(min(FENWICK_K, len(offs))):
        bit = (e >> lvl) & 1
        idx = offs[lvl] + ((e >> (lvl + 1)) << 1)
        out[..., lvl] = np.where(bit == 1, idx, total).astype(np.int32)
    return out


def sort_windows(digits: np.ndarray, zero16_from: int = 0):
    """digits: (n_lanes, T) uint8 — window w digit of lane i is byte w of
    its scalar. Returns (perm (T, N), ends (T, NBUCKETS) int32).

    Upload-lean by design (warm-call argument bytes are latency; what the
    host-to-device link moves is not re-measured on today's chip): perm
    ships as uint16 whenever the
    lane count fits (every production bucket), and instead of the
    (T, 256, 17) Fenwick node table only the (T, 256) bucket-boundary `ends`
    go to the device — ~32 KB vs ~0.5 MB — with the node decomposition
    recomputed on-device (fenwick_nodes_device, pure elementwise int ops).

    Routed through the native C counting sort (tendermint_tpu/native) when
    available: ~20x the numpy stable argsort at 20k lanes."""
    n, t = digits.shape
    idt = np.uint16 if n < (1 << 16) else np.int32
    if t == NWIN:
        from tendermint_tpu import native

        if native.available():
            perm32, ends = native.sort_windows(digits, zero16_from)
            return np.ascontiguousarray(perm32.astype(idt)), ends
    # per-column stable argsort in ONE call (axis=0), then counts via a
    # single bincount over offset digits
    perm = np.ascontiguousarray(
        np.argsort(digits, axis=0, kind="stable").T.astype(idt)
    )  # (T, n)
    offs = (np.arange(t, dtype=np.int64) * NBUCKETS)[None, :]
    flat = digits.astype(np.int64) + offs  # (n, T)
    counts = np.bincount(flat.ravel(), minlength=t * NBUCKETS).reshape(t, NBUCKETS)
    ends = np.cumsum(counts, axis=1).astype(np.int32)
    return perm, ends


def fenwick_nodes_device(ends: jnp.ndarray, n_lanes: int) -> jnp.ndarray:
    """Device-side fenwick_node_indices: ends (T, NBUCKETS) int32 ->
    (T, NBUCKETS, FENWICK_K) int32. Same derivation, elementwise."""
    offs, total = level_offsets(n_lanes)
    lvls = min(FENWICK_K, len(offs))
    e = jnp.asarray(ends).astype(jnp.int32)[..., None]  # (T, 256, 1)
    lvl = jnp.arange(lvls, dtype=jnp.int32)
    bit = (e >> lvl) & 1
    idx = jnp.asarray(np.asarray(offs[:lvls], dtype=np.int32)) + (
        (e >> (lvl + 1)) << 1
    )
    out = jnp.where(bit == 1, idx, jnp.int32(total))
    if lvls < FENWICK_K:
        pad = jnp.full((*out.shape[:-1], FENWICK_K - lvls), total, jnp.int32)
        out = jnp.concatenate([out, pad], axis=-1)
    return out



def scalars_to_bytes(scalars, n_lanes: int) -> np.ndarray:
    """Little-endian (n_lanes, 32) uint8; rows past len(scalars) are zero.

    Accepts a ready (m, 32) uint8 digit array as-is (the native host-prep
    path stays in the bytes domain end to end — crypto/batch.py). For int
    lists: one join + one frombuffer instead of a frombuffer per row, ~20x
    faster at 20k lanes."""
    if isinstance(scalars, np.ndarray) and scalars.dtype == np.uint8:
        if scalars.shape[0] == n_lanes:
            return scalars
        padded = np.zeros((n_lanes, 32), dtype=np.uint8)
        padded[: scalars.shape[0]] = scalars
        return padded
    blob = b"".join(int(s).to_bytes(32, "little") for s in scalars)
    out = np.frombuffer(blob, dtype=np.uint8).reshape(len(scalars), 32)
    if len(scalars) == n_lanes:
        return out
    padded = np.zeros((n_lanes, 32), dtype=np.uint8)
    padded[: len(scalars)] = out
    return padded


# --------------------------------------------------------------------------
# Device kernel.


def _pad_lanes(C: SmallCtx, p: Point, to: int) -> Point:
    w = p.x.shape[-1]
    if w == to:
        return p
    pad = _pidentity(C, p.x.shape[1:-1] + (to - w,))
    return Point(*(jnp.concatenate([a, b], axis=-1) for a, b in zip(p, pad)))


def _halve(C: SmallCtx, p: Point) -> Point:
    """One tree level: pairwise add over the (even-width) last axis."""
    return _padd(
        C,
        Point(*(a[..., 0::2] for a in p)),
        Point(*(a[..., 1::2] for a in p)),
    )


_TREE_SCAN_WIDTH = 256  # levels at or below this width run in one scan body


def _scan_structures() -> bool:
    """XLA:CPU's LLVM codegen cannot hold the fully-unrolled point-op
    graphs (compile memory exhaustion), so the CPU backend keeps the
    compile-sized scan forms; on TPU the unrolled forms measured ~18%
    faster end-to-end (loop-iteration overhead on narrow tensors)."""
    return jax.default_backend() == "cpu"


def _tree_levels(C: SmallCtx, p: Point) -> Point:
    """Build the concatenated pair-tree over the last axis, appending one
    identity lane at the end (the Fenwick pad target). p: (20, T, N).

    Compile-time shaping: wide levels (width > 256) are unrolled (the work
    shrinks geometrically, so unrolling is also the work-efficient layout);
    the tail levels run as ONE lax.scan body over fixed (…, 256)-padded
    arrays, so the whole tail costs a single point-add in the compiled
    graph — a fully-unrolled tree blew past XLA:CPU's compile memory.
    Level geometry must match level_widths()/level_offsets()."""
    widths = level_widths(p.x.shape[-1])
    levels = [p]
    cur = p
    floor = _TREE_SCAN_WIDTH if _scan_structures() else 1
    while cur.x.shape[-1] > floor:
        w = cur.x.shape[-1]
        if w % 2 == 1:
            cur = _pad_lanes(C, cur, w + 1)
        cur = _halve(C, cur)
        levels.append(cur)

    n_tail = len(widths) - len(levels)
    if n_tail > 0:
        # Fixed-width tail: state is the current level padded to a power of
        # two; each iteration halves and re-pads. ys collects every produced
        # level; logical widths come from level_widths().
        w0 = 1 << (max(cur.x.shape[-1] - 1, 1)).bit_length()  # pow2 >= width
        w0 = max(w0, 2)
        state = tuple(_pad_lanes(C, cur, w0))

        def body(st, _):
            pt = Point(*st)
            nxt = _pad_lanes(C, _halve(C, pt), w0)
            return tuple(nxt), tuple(nxt)

        _, ys = jax.lax.scan(body, state, None, length=n_tail)
        base = len(levels)
        for i in range(n_tail):
            lw = widths[base + i]
            levels.append(Point(*(ys[c][i][..., :lw] for c in range(4))))

    pad = _pidentity(C, p.x.shape[1:-1] + (1,))
    return Point(
        *(
            jnp.concatenate(
                [lv[i][..., : widths[k]] for k, lv in enumerate(levels)] + [pad[i]],
                axis=-1,
            )
            for i in range(4)
        )
    )


def _gather_lanes(p: Point, perm: jnp.ndarray) -> Point:
    """p coords (20, N); perm (T, N) -> coords (20, T, N).

    Layout matters enormously here: gathering scalars along the MINOR axis
    (`c[:, perm]`) ran at ~21 GB/s on TPU (15 ns/element — 19.5 ms of the
    62 ms r4 kernel). Instead gather whole ROWS of an (N, 4*20) table — all
    four coordinates' limbs contiguous per lane (320 B) — and let XLA fuse
    the surrounding transposes (slope-measured r5: lane 8.2 -> 5.3 ms,
    fenwick 23.3 -> 4.6 ms on the same index sets)."""
    perm = jnp.asarray(perm).astype(jnp.int32)  # uint16 on the wire
    n = p.x.shape[-1]
    t_ = perm.shape[0]
    arr = jnp.stack([c.T for c in p], axis=1).reshape(n, 4 * fe.NLIMBS)
    g = arr[perm].reshape(t_, perm.shape[1], 4, fe.NLIMBS)  # (T, N, 4, 20)
    return Point(*(jnp.moveaxis(g[:, :, c, :], -1, 0) for c in range(4)))


def _gather_nodes(tree: Point, node_idx: jnp.ndarray) -> Point:
    """tree coords (20, T, Wtot+1); node_idx (T, NBUCKETS, K) ->
    (20, T, NBUCKETS, K). Row-gather layout — see _gather_lanes."""
    node_idx = jnp.asarray(node_idx).astype(jnp.int32)  # uint16 on the wire
    t_, nb, k_ = node_idx.shape
    w = tree.x.shape[-1]
    arr = jnp.stack([jnp.moveaxis(c, 0, -1) for c in tree], axis=-2)  # (T, W, 4, 20)
    arr = arr.reshape(t_, w, 4 * fe.NLIMBS)
    g = jnp.take_along_axis(arr, node_idx.reshape(t_, nb * k_)[..., None], axis=1)
    g = g.reshape(t_, nb, k_, 4, fe.NLIMBS)
    return Point(*(jnp.moveaxis(g[..., c, :], -1, 0) for c in range(4)))


def _reduce_last_axis(C: SmallCtx, p: Point) -> Point:
    """Pair-tree sum over the last axis (odd widths identity-padded)."""
    while p.x.shape[-1] > 1:
        w = p.x.shape[-1]
        if w % 2 == 1:
            p = _pad_lanes(C, p, w + 1)
        p = _halve(C, p)
    return Point(*(a[..., 0] for a in p))


def _sum_last_axis(C: SmallCtx, p: Point) -> Point:
    """Tree-sum over the last axis (any width) as ONE scan body: state stays
    at a fixed power-of-two width, each iteration halves and re-pads with
    identity (compile-size over the small extra work)."""
    w = p.x.shape[-1]
    if w == 1:
        return Point(*(a[..., 0] for a in p))
    if not _scan_structures():
        while p.x.shape[-1] > 1:
            wd = p.x.shape[-1]
            if wd % 2 == 1:
                p = _pad_lanes(C, p, wd + 1)
            p = _halve(C, p)
        return Point(*(a[..., 0] for a in p))
    w0 = max(1 << (w - 1).bit_length(), 2)
    state = tuple(_pad_lanes(C, p, w0))

    def body(st, _):
        nxt = tuple(_pad_lanes(C, _halve(C, Point(*st)), w0))
        return nxt, None

    steps = (w0 - 1).bit_length()
    st, _ = jax.lax.scan(body, state, None, length=steps)
    return Point(*(a[..., 0] for a in st))


def _weighted_bucket_sum(C: SmallCtx, prefix: Point) -> Point:
    """prefix: (20, T, NBUCKETS) — prefix[v] = exact sum of all sorted lanes
    with digit <= v. Returns per-window W = sum_{v>=1} v * bucket_v, (20, T).

    The bucket differences telescope: with bucket_v = P_v - P_{v-1},
        sum_{v=1}^{V} v (P_v - P_{v-1})  =  V*P_V  -  sum_{v=0}^{V-1} P_v
    (V = 255). No per-bucket subtraction or suffix scan is needed, and the
    bucket-0 contribution (zero-scalar / padding lanes) appears in every
    P_v, so it cancels exactly: V*P_V carries V copies, the sum carries V."""
    v_max = prefix.x.shape[-1] - 1  # 255
    p_last = Point(*(a[..., -1] for a in prefix))  # (20, T)
    rest = Point(*(a[..., :-1] for a in prefix))  # v = 0..254
    s = _sum_last_axis(C, rest)

    # [255] P_255 = [256] P_255 - P_255: 8 doublings + one add of the negation.
    if not _scan_structures():
        m = _pdbl_n(C, p_last, v_max.bit_length())
    else:
        def dbl_body(st, _):
            return tuple(_pdbl(C, Point(*st))), None

        st, _ = jax.lax.scan(dbl_body, tuple(p_last), None, length=v_max.bit_length())
        m = Point(*st)
    m = _padd(C, m, _pneg(C, p_last))  # [256]P - P = [255]P
    return _padd(C, m, _pneg(C, s))


def _combine_windows(C: SmallCtx, w_pts: Point) -> Point:
    """w_pts coords (20, T) with window w weight 256^w -> sum [256^w] W_w.

    The ~248-doubling sequential depth is inherent (it equals the scalar
    bit-width), but HOW it is scheduled matters enormously on TPU: the
    round-3 Horner (lax.scan over 31 window steps) measured ~64 ms at 10k —
    ~2 ms/iteration of while-loop overhead on width-1 tensors, a third of
    total kernel time. The Pallas form is an unrolled pairwise fold:
        level k: V_i = U_{2i} + [2^(8*2^k)] U_{2i+1}
    — same 248 sequential doublings, but zero loop machinery, shrinking
    widths (16, 8, 4, 2, 1), and each point-op ONE custom call so the graph
    stays ~300 HLO ops. The fold is PALLAS-ONLY: expressed in raw jnp its
    ~253 point-ops inline to >15k HLO and the XLA:TPU compile ran >30 min
    before being killed (XLA:CPU dies the same way) — scan stays the
    non-pallas form on both backends."""
    t_ = w_pts.x.shape[-1]
    if _use_pallas():
        return _fold_windows(C, w_pts)

    acc = Point(*(a[..., t_ - 1] for a in w_pts))  # (20,)
    xs = jnp.stack(
        [jnp.moveaxis(a[..., : t_ - 1], -1, 0) for a in w_pts], axis=1
    )  # (T-1, 4, 20)
    xs = xs[::-1]  # MSB-first over remaining windows

    unroll_dbl = not _scan_structures()  # TPU: unrolled dblings inside body

    def body(acc_coords, wp):
        if unroll_dbl:
            p = Point(*acc_coords)
            for _ in range(WINDOW_BITS):
                p = _pdbl(C, p)
            acc_coords = tuple(p)
        else:
            def dbl(_, st):
                return tuple(_pdbl(C, Point(*st)))

            acc_coords = jax.lax.fori_loop(0, WINDOW_BITS, dbl, acc_coords)
        acc = _padd(C, Point(*acc_coords), Point(wp[0], wp[1], wp[2], wp[3]))
        return tuple(acc), None

    acc_coords, _ = jax.lax.scan(body, tuple(acc), xs)
    return Point(*acc_coords)


def _fold_windows(C: SmallCtx, w_pts: Point) -> Point:
    """The pairwise window fold (see _combine_windows docstring): level k
    computes V_i = U_{2i} + [2^(8*2^k)] U_{2i+1}. On TPU every point op is
    a Pallas call; on CPU the same schedule runs through the jnp point ops,
    which is what the differential test exercises (the fold itself is
    Pallas-only in production, so without this split a pairing/shift bug
    would only surface as end-to-end verification failure on hardware)."""
    p = w_pts
    shift = WINDOW_BITS
    while p.x.shape[-1] > 1:
        w = p.x.shape[-1]
        if w % 2 == 1:
            p = _pad_lanes(C, p, w + 1)
        even = Point(*(a[..., 0::2] for a in p))
        odd = Point(*(a[..., 1::2] for a in p))
        odd = _pdbl_n(C, odd, shift)
        p = _padd(C, even, odd)
        shift *= 2
    return Point(*(a[..., 0] for a in p))


def _window_points(C: SmallCtx, pts: Point, perm, node_idx) -> Point:
    """One window group: gather lanes, pair-tree, Fenwick prefix extraction,
    weighted bucket sums. pts (20, N); perm (T, N); returns (20, T)."""
    gathered = _gather_lanes(pts, perm)  # (20, T, N)
    tree = _tree_levels(C, gathered)  # (20, T, Wtot+1)
    nodes = _gather_nodes(tree, node_idx)  # (20, T, 256, K)
    prefix = _reduce_last_axis(C, nodes)  # (20, T, 256)
    return _weighted_bucket_sum(C, prefix)  # (20, T)


def _msm_total(C: SmallCtx, pts: Point, perm, node_idx) -> Point:
    """pts: decompressed valid points (20, N); perm (T, N). Returns the full
    multiscalar sum as a single point (20,). (A window-split variant — high
    windows over the A block only, since R-lane coefficients are < 2^128 —
    was tried and measured 4x SLOWER on TPU: two half-width pipelines lose
    to one fused full-width one.)"""
    w_pts = _window_points(C, pts, perm, node_idx)  # (20, T)
    return _combine_windows(C, w_pts)  # (20,)


def point_is_identity(C: SmallCtx, total: Point) -> jnp.ndarray:
    """Projective identity check with the degenerate-output guard: an
    exceptional unified addition (possible only on crafted torsion inputs)
    yields (0,0,0,0), which must read as "check failed" (-> per-sig
    fallback), not as the identity."""
    return fe.is_zero(total.x) & fe.eq(total.y, total.z) & ~fe.is_zero(total.z)


def _msm_is_identity(C: SmallCtx, pts: Point, perm, node_idx) -> jnp.ndarray:
    return point_is_identity(C, _msm_total(C, pts, perm, node_idx))


# ---------------------------------------------------------------------------
# Fused pipeline (ops/pallas_msm.py): the same MSM with the tree/prefix/
# bucket stages as VMEM-resident fused kernels in ONE packed limb layout.
#
# Storage map (row indices into the concatenated gatherable row table):
#   [0, T*N)                       level-0 lanes, bit-reversed within chunks
#   [G1, G1 + T*ncw*rows_out*128)  chunk trees (levels 1..lc, chunk-major)
#   [G2, G2 + T*(Wtop+1))          top tree over chunk roots + identity lane
# A bucket boundary e decomposes as: full chunks [0, e>>lc) via the top
# tree's Fenwick nodes (the old aligned-block derivation over ncw chunk
# totals), plus the bits of e & (ch-1) via level-0/chunk-tree nodes of the
# partial chunk — at bit-reversed in-level positions (pallas_msm docstring).


def fused_node_indices_device(ends: jnp.ndarray, n_lanes: int, ch: int) -> jnp.ndarray:
    """ends (T, NBUCKETS) int32 -> (NBUCKETS, T, Kf) int32 global row
    indices, bucket-major (v-major) so the downstream reduce/bucket kernels
    see flat lane order v*T + t."""
    from tendermint_tpu.ops import pallas_msm as PM

    g = PM.chunk_geometry(ch)
    ncw = n_lanes // ch
    t_ = ends.shape[0]
    toffs, ttot = level_offsets(ncw)
    wtop1 = ttot + 1
    g1 = t_ * n_lanes
    g2 = g1 + t_ * ncw * g.rows_out * 128

    e = jnp.asarray(ends).astype(jnp.int32).T[..., None]  # (NB, T, 1)
    w = jnp.arange(t_, dtype=jnp.int32)[None, :, None]
    ce = e >> g.lc
    r = e & (ch - 1)
    idn = g2 + w * wtop1 + ttot  # per-window identity lane

    # partial-chunk part: levels 0..lc-1, present iff bit l of r
    lvl = jnp.arange(g.lc, dtype=jnp.int32)
    bit = (r >> lvl) & 1
    j = (r >> (lvl + 1)) << 1
    q = PM.brev_jnp(j, g.lc - lvl)  # in-level bit-reversed position
    roff = jnp.asarray(g.row_off, dtype=jnp.int32)
    idx0 = w * n_lanes + ce * ch + q
    idxl = (
        g1
        + (w * ncw + ce) * (g.rows_out * 128)
        + (roff[lvl] + (q >> 7)) * 128
        + (q & 127)
    )
    cidx = jnp.where(lvl == 0, idx0, idxl)
    cidx = jnp.where(bit == 1, cidx, idn)

    # full-chunks part: the old Fenwick derivation over ncw chunk totals
    lt = len(toffs)
    lvl2 = jnp.arange(lt, dtype=jnp.int32)
    bit2 = (ce >> lvl2) & 1
    jt = (ce >> (lvl2 + 1)) << 1
    tidx = g2 + w * wtop1 + jnp.asarray(toffs, dtype=jnp.int32)[lvl2] + jt
    tidx = jnp.where(bit2 == 1, tidx, idn)
    return jnp.concatenate([cidx, tidx], axis=-1)


def _msm_total_fused(C: SmallCtx, pts: Point, perm, ends) -> Point:
    """The fused-schedule twin of _msm_total: identical group element,
    different (VMEM-resident) evaluation order. pts (20, N); perm (T, N)
    natural sorted order (the bit-reversal is composed in here); ends
    (T, NBUCKETS)."""
    from tendermint_tpu.ops import pallas_msm as PM

    perm = jnp.asarray(perm).astype(jnp.int32)
    t_ = perm.shape[0]
    n = pts.x.shape[-1]
    ch = PM.chunk_for_lanes(n)
    g = PM.chunk_geometry(ch)
    ncw = n // ch

    # gather lanes directly into fused order: whole 320-byte point rows
    # (the r5 row-gather layout), chunk-wise bit-reversed via the composed
    # permutation — the only big gather the tree phase pays.
    # (each stage under a jax.named_scope: metadata only, so that an XLA
    # operation's op_name in a device trace says which stage made it)
    with jax.named_scope("row_gather"):
        perm_f = jnp.take(perm, jnp.asarray(PM.brev_positions(n, ch)), axis=1)
        rowtab = jnp.stack([c.T for c in pts], axis=1).reshape(n, 4 * fe.NLIMBS)
        g_rows = rowtab[perm_f.reshape(-1)]  # (T*N, 80)

    # chunk trees: ONE kernel computes levels 1..lc per chunk in VMEM; rows
    # in, rows out (the change of layout is the kernel's own, in VMEM)
    with jax.named_scope("uptree"):
        ctree_rows = PM.uptree(g_rows, ch)  # (T*ncw*rows_out*128, 80)

    # top tree over the T*ncw chunk roots (tiny; existing limb-major path)
    with jax.named_scope("top_tree"):
        root_row = g.row_off[g.lc]
        roots = ctree_rows[root_row * 128 :: g.rows_out * 128].T  # (80, T*ncw)
        roots = roots.reshape(4, fe.NLIMBS, t_ * ncw)
        roots_pt = Point(*(roots[c].reshape(fe.NLIMBS, t_, ncw) for c in range(4)))
        top = _tree_levels(C, roots_pt)  # (20, T, Wtop+1) incl. identity lane
        wtop1 = top.x.shape[-1]
        top_rows = jnp.stack(
            [jnp.moveaxis(c, 0, -1) for c in top], axis=-2
        ).reshape(t_ * wtop1, 4 * fe.NLIMBS)

    # Fenwick prefix extraction: row-gather the decomposition nodes, reduce
    # them in ONE accumulating kernel (no materialized (T,256,K) tensor)
    with jax.named_scope("fenwick_gather"):
        all_rows = jnp.concatenate([g_rows, ctree_rows, top_rows], axis=0)
        node_idx = fused_node_indices_device(ends, n, ch)  # (NB, T, Kf)
        kf = node_idx.shape[-1]
        gathered = all_rows[node_idx.reshape(-1)]  # (NB*T*Kf, 80)
        gk = jnp.moveaxis(gathered.reshape(NBUCKETS * t_, kf, 4 * fe.NLIMBS), 1, 0)
        gk = jnp.moveaxis(gk, -1, 1).reshape(
            kf, 4, fe.NLIMBS, NBUCKETS * t_ // 128, 128
        )
    with jax.named_scope("fenwick_reduce"):
        prefix = PM.fenwick_reduce(gk)  # packed, v-major

    # weighted bucket sum: one fused fold kernel + the tiny (20, T) tail
    with jax.named_scope("bucket_fold"):
        s_coords, p255_coords = PM.bucket_fold(prefix, t_)
        s_pt = Point(*s_coords)
        p_last = Point(*p255_coords)
        m = _pdbl_n(C, p_last, WINDOW_BITS)  # [256] P_255
        m = _padd(C, m, _pneg(C, p_last))  # [255] P_255
        w_pts = _padd(C, m, _pneg(C, s_pt))  # (20, T) per-window sums
    with jax.named_scope("window_combine"):
        return _combine_windows(C, w_pts)


def _msm_check(C: SmallCtx, pts: Point, perm, ends, fused: bool) -> jnp.ndarray:
    """Batch-identity check routing: fused (VMEM-resident schedule) vs the
    unfused per-level reference. `fused` is trace-static — the two variants
    are distinct jit programs (and distinct AOT artifacts)."""
    if fused:
        total = _msm_total_fused(C, pts, perm, ends)
        with jax.named_scope("identity_check"):
            return point_is_identity(C, total)
    node_idx = fenwick_nodes_device(ends, pts.x.shape[-1])
    return _msm_is_identity(C, pts, perm, node_idx)


def _rlc_core(
    pts_bytes: jnp.ndarray,  # (32, N) uint8 — A lanes, B lane, R lanes, pads
    perm: jnp.ndarray,  # (T, N) int/uint
    ends: jnp.ndarray,  # (T, NBUCKETS) int32 bucket boundaries
    fctx: FieldCtx,  # materialized at batch shape (N,) for decompress
    C: SmallCtx,
    fused: bool = False,
) -> jnp.ndarray:
    """Returns bool (1+N,): [batch_ok, lane_ok...] packed into ONE array so
    the caller syncs in a single D2H round trip."""
    with jax.named_scope("decompress"):
        p, ok = decompress(fctx, pts_bytes)
        p = _pselect(ok, p, identity(fctx))
    bok = _msm_check(C, p, perm, ends, fused)
    return jnp.concatenate([bok[None], ok])


def _rlc_partial_core(
    pts_bytes: jnp.ndarray,  # (32, N) uint8 — chunk lanes [A | B | R | pads]
    perm: jnp.ndarray,  # (T, N)
    ends: jnp.ndarray,  # (T, NBUCKETS) int32
    fctx: FieldCtx,  # at shape (N,)
    C: SmallCtx,
    fused: bool = False,
):
    """One streamed-planner chunk (crypto/batch.py): the full Pippenger
    pipeline over this chunk's lanes, WITHOUT the identity check — the MSM
    is a sum over lanes, so an arbitrarily large flush decomposes into
    fixed-bucket partial sums accumulated on device (_partial_fold_core)
    with one identity check at the end (_partial_identity_core).

    Returns (coords (4, 20) int32 — the chunk's partial point in extended
    limbs, ok (N,) bool — per-lane decompress validity)."""
    with jax.named_scope("decompress"):
        p, ok = decompress(fctx, pts_bytes)
        p = _pselect(ok, p, identity(fctx))
    if fused:
        part = _msm_total_fused(C, p, perm, ends)
    else:
        node_idx = fenwick_nodes_device(ends, pts_bytes.shape[-1])
        part = _msm_total(C, p, perm, node_idx)
    return jnp.stack(part), ok


def _partial_fold_core(a: jnp.ndarray, b: jnp.ndarray, C: SmallCtx) -> jnp.ndarray:
    """Fold two (4, 20) partial points: ONE unified add — the tiny combine
    kernel the streamed planner dispatches per chunk (device-resident
    accumulation; nothing but the two points ever lives in HBM)."""
    s = _padd(C, Point(a[0], a[1], a[2], a[3]), Point(b[0], b[1], b[2], b[3]))
    return jnp.stack(s)


def _partial_identity_core(a: jnp.ndarray, C: SmallCtx) -> jnp.ndarray:
    """Identity check on an accumulated (4, 20) partial point — the streamed
    flush's combined-check verdict."""
    with jax.named_scope("identity_check"):
        return point_is_identity(C, Point(a[0], a[1], a[2], a[3]))


def _rlc_core_cached(
    ax, ay, az, at,  # (20, Na) predecompressed A block (incl. B lane)
    r_bytes,  # (32, Nr) uint8
    perm,
    ends,  # (T, NBUCKETS) int32
    fctx: FieldCtx,  # at shape (Nr,)
    C: SmallCtx,
    fused: bool = False,
) -> jnp.ndarray:
    """Cached-A variant: lanes = [A block | R block]; only R is decompressed.
    Returns bool (1+Nr,): [batch_ok, r_ok...]."""
    with jax.named_scope("decompress"):
        r, r_ok = decompress(fctx, r_bytes)
        r = _pselect(r_ok, r, identity(fctx))
    pts = Point(
        *(
            jnp.concatenate([a, b], axis=-1)
            for a, b in zip(Point(ax, ay, az, at), r)
        )
    )
    bok = _msm_check(C, pts, perm, ends, fused)
    return jnp.concatenate([bok[None], r_ok])


def _rlc_core_cached_mixed(
    ax, ay, az, at,  # (20, Na) predecoded A block (incl. B lane, both key types)
    ed_r_bytes,  # (32, Ne) uint8 — ed25519 R encodings
    sr_r_bytes,  # (32, Ns) uint8 — ristretto255 R encodings
    perm,
    ends,  # (T, NBUCKETS) int32
    fctx_ed: FieldCtx,  # at shape (Ne,)
    fctx_sr: FieldCtx,  # at shape (Ns,)
    C: SmallCtx,
    fused: bool = False,
) -> jnp.ndarray:
    """Mixed-key-type cached-A variant: lanes = [A block | edR | srR].
    Returns bool (1+Ne+Ns,): [batch_ok, ed_r_ok..., sr_r_ok...]."""
    from tendermint_tpu.ops.ristretto_jax import ristretto_decode

    with jax.named_scope("decompress"):
        er, er_ok = decompress(fctx_ed, ed_r_bytes)
        er = _pselect(er_ok, er, identity(fctx_ed))
        sr, sr_ok = ristretto_decode(fctx_sr, sr_r_bytes)
        sr = _pselect(sr_ok, sr, identity(fctx_sr))
    pts = Point(
        *(
            jnp.concatenate([a, b, c], axis=-1)
            for a, b, c in zip(Point(ax, ay, az, at), er, sr)
        )
    )
    bok = _msm_check(C, pts, perm, ends, fused)
    return jnp.concatenate([bok[None], er_ok, sr_ok])


# The fused/unfused variants are separate jit objects (and carry distinct
# AOT-cache names below): `fused` changes the traced graph, so it must never
# share a compiled-program cache slot with the other variant.
_rlc_jit = jax.jit(_rlc_core)
_rlc_jit_fused = jax.jit(functools.partial(_rlc_core, fused=True))
_rlc_cached_jit = jax.jit(_rlc_core_cached)
_rlc_cached_jit_fused = jax.jit(functools.partial(_rlc_core_cached, fused=True))
_rlc_cached_mixed_jit = jax.jit(_rlc_core_cached_mixed)
_rlc_cached_mixed_jit_fused = jax.jit(
    functools.partial(_rlc_core_cached_mixed, fused=True)
)
_rlc_partial_jit = jax.jit(_rlc_partial_core)
_rlc_partial_jit_fused = jax.jit(functools.partial(_rlc_partial_core, fused=True))
_partial_fold_jit = jax.jit(_partial_fold_core)
_partial_identity_jit = jax.jit(_partial_identity_core)


def basepoint_coords() -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Host constants: the ed25519 basepoint in extended limbs (20,) int32."""
    from tendermint_tpu.crypto.ed25519_ref import BASE

    x, y, z, t = BASE
    return (fe.from_int(x), fe.from_int(y), fe.from_int(z), fe.from_int(t))


_decompress_jit = jax.jit(lambda b, fctx: decompress(fctx, b))


def decompress_rows(rows: np.ndarray) -> Tuple[Tuple[np.ndarray, ...], np.ndarray]:
    """rows (m, 32) uint8 -> ((x, y, z, t) each (20, m) int32, ok (m,) bool).
    Pads to a small shape-bucket internally; used to fill the pubkey cache."""
    m = rows.shape[0]
    pad = 1 << max(6, (m - 1).bit_length())
    buf = np.zeros((pad, 32), dtype=np.uint8)
    buf[:, 1] = 0x80  # y=2^255-ish: invalid, but masked by slicing below
    buf[:m] = rows
    p, ok = _decompress_jit(np.ascontiguousarray(buf.T), make_ctx((pad,)))
    coords = tuple(np.asarray(c)[:, :m] for c in p)
    return coords, np.asarray(ok)[:m]


def rlc_check_submit(
    pts_bytes: np.ndarray, scalars: Sequence[int], zero16_from: int = 0,
    presorted=None,
):
    """Host prep + async device submit: pts_bytes (N, 32) uint8 encodings,
    [A block | R block] with scalars to match (0 = excluded lane; R-block
    scalars < 2^128). zero16_from: the A/R boundary when known (R-block
    scalars being < 2^128 lets the sort skip those rows in the high
    windows). `presorted=(perm, ends)` skips the digit expansion AND the
    window sort — the stage-overlapped submit (crypto/batch.py ISSUE 18)
    sorts on the prep side so this call dispatches immediately. Returns an
    unsynced device bool (1+N,): [batch_ok, lane_ok...] — np.asarray() it
    to sync."""
    n = pts_bytes.shape[0]
    with _trace.span("kernel.rlc_submit", variant="plain", lanes=n):
        if presorted is not None:
            perm, ends = presorted
        else:
            digits = scalars_to_bytes(scalars, n)
            perm, ends = sort_windows(digits, zero16_from=zero16_from)
        fctx = make_ctx((n,))
        fused = fused_for_lanes(n)
        _set_submit_fused(fused)
        return _dispatch(
            "rlc_plain_f" if fused else "rlc_plain",
            _rlc_jit_fused if fused else _rlc_jit,
            np.ascontiguousarray(pts_bytes.T), perm, ends, fctx, make_small_ctx(),
        )


def rlc_check(pts_bytes: np.ndarray, scalars: Sequence[int]) -> Tuple[bool, np.ndarray]:
    out = np.asarray(rlc_check_submit(pts_bytes, scalars))
    return bool(out[0]), out[1:]


def rlc_partial_submit(
    pts_bytes: np.ndarray, scalars, zero16_from: int = 0, presorted=None
):
    """Host prep + async submit of ONE streamed-flush chunk's partial MSM
    (crypto/batch.py's flush planner): same prep as rlc_check_submit, but
    the kernel returns the chunk's partial point instead of a verdict.
    `presorted=(perm, ends)` skips the window sort here — the planner's
    prep WORKER sorts chunk k+1 while chunk k's kernels execute, so the
    sort must not re-run on the submitting thread.
    Returns (coords (4, 20) int32 device array, ok (N,) bool device array)
    — both unsynced; np.asarray() to sync."""
    n = pts_bytes.shape[0]
    with _trace.span("kernel.rlc_partial_submit", variant="partial", lanes=n):
        if presorted is not None:
            perm, ends = presorted
        else:
            digits = scalars_to_bytes(scalars, n)
            perm, ends = sort_windows(digits, zero16_from=zero16_from)
        fctx = make_ctx((n,))
        fused = fused_for_lanes(n)
        _set_submit_fused(fused)
        return _dispatch(
            "rlc_partial_f" if fused else "rlc_partial",
            _rlc_partial_jit_fused if fused else _rlc_partial_jit,
            np.ascontiguousarray(pts_bytes.T), perm, ends, fctx, make_small_ctx(),
        )


def partial_fold_submit(acc, part):
    """Device-resident accumulation of streamed-chunk partials: one tiny
    padd kernel over two (4, 20) points (async; a no-sync dispatch)."""
    return _dispatch("partial_fold", _partial_fold_jit, acc, part, make_small_ctx())


def partial_identity_submit(acc):
    """The streamed flush's combined-check verdict on the accumulated
    partial point. Returns an unsynced device bool scalar."""
    return _dispatch(
        "partial_ident", _partial_identity_jit, acc, make_small_ctx()
    )


def rlc_check_cached_submit(
    a_coords: Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray],
    r_bytes: np.ndarray,  # (Nr, 32)
    scalars: Sequence[int],  # length Na + Nr, A block first
    presorted=None,
):
    """Cached-A variant of rlc_check_submit (A predecompressed, R by bytes).
    `presorted=(perm, ends)` skips the window sort here, as there. Returns
    an unsynced device bool (1+Nr,): [batch_ok, r_ok...]."""
    na = a_coords[0].shape[-1]
    nr = r_bytes.shape[0]
    n = na + nr
    with _trace.span("kernel.rlc_submit", variant="cached", lanes=n):
        fctx = make_ctx((nr,))
        fused = fused_for_lanes(n)
        _set_submit_fused(fused)
        # rows >= na are the z-lane (128-bit scalars) + padding: zero digits
        # in windows 16-31, so the sort skips their count pass
        if presorted is not None:
            perm, ends = presorted
        else:
            digits = scalars_to_bytes(scalars, n)
            perm, ends = sort_windows(digits, zero16_from=na)
        return _dispatch(
            "rlc_cached_f" if fused else "rlc_cached",
            _rlc_cached_jit_fused if fused else _rlc_cached_jit,
            *a_coords,
            np.ascontiguousarray(r_bytes.T),
            perm,
            ends,
            fctx,
            make_small_ctx(),
        )


def rlc_check_cached(
    a_coords: Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray],
    r_bytes: np.ndarray,
    scalars: Sequence[int],
) -> Tuple[bool, np.ndarray]:
    out = np.asarray(rlc_check_cached_submit(a_coords, r_bytes, scalars))
    return bool(out[0]), out[1:]


def rlc_check_cached_mixed_submit(
    a_coords: Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray],
    ed_r_bytes: np.ndarray,  # (Ne, 32)
    sr_r_bytes: np.ndarray,  # (Ns, 32)
    scalars: Sequence[int],  # length Na + Ne + Ns: A block, ed R, sr R
):
    """Mixed ed25519+sr25519 cached-A RLC submit (no sync). Returns an
    unsynced device bool (1+Ne+Ns,): [batch_ok, ed_r_ok..., sr_r_ok...]."""
    na = a_coords[0].shape[-1]
    ne = ed_r_bytes.shape[0]
    ns = sr_r_bytes.shape[0]
    n = na + ne + ns
    with _trace.span("kernel.rlc_submit", variant="mixed", lanes=n):
        digits = scalars_to_bytes(scalars, n)
        # rows >= na are the (128-bit) z-lane scalars of both R blocks
        perm, ends = sort_windows(digits, zero16_from=na)
        fused = fused_for_lanes(n)
        _set_submit_fused(fused)
        return _dispatch(
            "rlc_mixed_f" if fused else "rlc_mixed",
            _rlc_cached_mixed_jit_fused if fused else _rlc_cached_mixed_jit,
            *a_coords,
            np.ascontiguousarray(ed_r_bytes.T),
            np.ascontiguousarray(sr_r_bytes.T),
            perm,
            ends,
            make_ctx((ne,)),
            make_ctx((ns,)),
            make_small_ctx(),
        )
