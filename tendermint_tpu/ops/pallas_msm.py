"""Fused MSM pipeline stages as Pallas TPU kernels.

Why this exists (PERF.md rounds 4-6): the Pippenger MSM's curve arithmetic
is ~10 ms of Pallas kernels at 10k validators, but the PIPELINE around it
burns ~3-4x that in HBM traffic — every tree level materializes through HBM
between per-level `padd` calls, every Pallas wrapper re-packs (stack +
reshape + pad) its inputs and unpacks its outputs, and the stride-2
even/odd halving slices relayout each level before the kernel even starts.
This module removes the inter-kernel traffic for the three memory-bound MSM
stages by (a) fusing whole stages into single kernels that keep every
intermediate level in VMEM, computing on ONE packed form — per coordinate
and limb a (sublane-group, 128-lane) plane, the form ops/pallas_fe.py uses
INSIDE its kernels — and (b) keeping ONE layout outside the tree kernel:
point ROWS of 4*NL words, which both big gathers read and write. The tree
kernel changes rows to planes and back on its block in VMEM (PR 32; XLA
made four copies of the two transposes, a quarter of the device's time);
the packed (4, NL, S, 128) array crosses kernel boundaries only from the
Fenwick gather on, where the data is a fifteenth the size:

  uptree          chunk-local pair-tree up-sweep: one kernel computes ALL
                  tree levels of a 2048-lane (or 1024-lane) chunk in VMEM
                  and writes the concatenated levels once. Lanes arrive
                  BIT-REVERSED within each chunk (the host perm composes the
                  reversal for free), which turns the stride-2 even/odd
                  pairing into contiguous-half adds: fold(v) = first half +
                  second half, expressible as offset-0 slices + tpu rolls —
                  no in-kernel shuffle-heavy strided slicing, no per-level
                  HBM round trip.
  fenwick_reduce  the Fenwick prefix extraction: the K gathered tree nodes
                  per bucket boundary reduce in-kernel via the standard
                  grid-accumulation pattern (output block revisited across
                  the K grid steps) — the unfused form materialized a
                  (T, 256, K) point tensor and five padd levels through HBM.
  bucket_fold     the weighted bucket sum's big reduction: masks bucket 255,
                  folds the 256*T prefix points (v-major layout) down to
                  per-window sums, and extracts P_255 — one kernel replacing
                  eight padd calls + slice plumbing.

Pairing correctness relies on the bit-reversal invariant: placing sorted
lane j of a chunk at physical position rev(j) makes every fold level
"first half + second half" compute exactly the aligned-block sums the
Fenwick decomposition needs, with level-l node k stored at position
rev_{lc-l}(k) (fused_node_position below; lc = log2(chunk)). Chunks are
powers of two even though lane buckets are not — any bucket divisible by
1024 fuses (all production buckets; smaller batches keep the unfused path).

Every stage has a pure-jnp twin selected when Pallas is off: the SAME fold
schedule over the SAME packed layout, but with the compact fe25519/XLA point
add instead of the in-kernel row convolution (the row math traces to ~8k HLO
per point add — fine inside one Mosaic kernel, a compile-memory explosion as
an XLA:CPU graph; PERF.md "what was tried and rejected"). Schedule equality
between kernel body and twin is pinned by running both with a mocked integer
add (tests/test_fused_msm.py; the tree kernel under the Pallas interpreter,
its changes of layout go through VMEM refs), and the row math itself is
pinned to the fe ops by tests/test_pallas_fe.py — so the CPU differential
covers the fused schedule end to end.

Enabled with ops/pallas_fe.py (TMTPU_PALLAS); the pipeline-level flag lives
in ops/msm_jax.py (TMTPU_FUSED_MSM).
"""

from __future__ import annotations

import functools
from typing import List, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tendermint_tpu.ops import fe25519 as fe
from tendermint_tpu.ops import pallas_fe
from tendermint_tpu.ops.pallas_fe import LANE, NL, _padd_rows

NW = 4 * NL  # int32 words of a point row: coordinate-major, limb-minor

# Observability counter (tests/test_flush_budget.py pins it): changes of
# layout that XLA makes around the fused kernels, per traced MSM: the bucket
# extract on the kernels' path (the tree kernel changes layout itself), and
# the tree stage's two transposes where its CPU twin runs. The whole point of
# the fused pipeline is that these do NOT scale with the number of point-op
# calls.
LAYOUT_CONVERSIONS = [0]


def chunk_for_lanes(n_lanes: int) -> int | None:
    """Largest supported chunk that tiles n_lanes, or None (-> unfused).
    2048 preferred (deeper in-VMEM tree); 1024 covers the Na=1536 bucket."""
    for ch in (2048, 1024):
        if n_lanes >= ch and n_lanes % ch == 0:
            return ch
    return None


# ---------------------------------------------------------------------------
# Bit reversal (host + device twins; m <= 11 bits).


def brev_np(x: np.ndarray, m: int) -> np.ndarray:
    x = x.astype(np.int64)
    r = np.zeros_like(x)
    for b in range(m):
        r |= ((x >> b) & 1) << (m - 1 - b)
    return r


def _brev16_jnp(x: jnp.ndarray) -> jnp.ndarray:
    """Bit-reverse the low 16 bits of an int32 (elementwise)."""
    x = x & 0xFFFF
    x = ((x & 0x5555) << 1) | ((x >> 1) & 0x5555)
    x = ((x & 0x3333) << 2) | ((x >> 2) & 0x3333)
    x = ((x & 0x0F0F) << 4) | ((x >> 4) & 0x0F0F)
    x = ((x & 0x00FF) << 8) | ((x >> 8) & 0x00FF)
    return x


def brev_jnp(x: jnp.ndarray, m) -> jnp.ndarray:
    """rev_m(x) for m bits; m may be a (broadcastable) array of bit counts."""
    return _brev16_jnp(x) >> (16 - jnp.asarray(m, dtype=jnp.int32))


@functools.lru_cache(maxsize=32)
def brev_positions(n_lanes: int, ch: int) -> np.ndarray:
    """Within-window gather order for the fused tree: position p reads the
    sorted lane (p & ~(ch-1)) | rev(p & (ch-1)) — so each chunk's lanes land
    bit-reversed and every fold level pairs contiguous halves."""
    lc = ch.bit_length() - 1
    i = np.arange(n_lanes, dtype=np.int64)
    out = (i & ~(ch - 1)) | brev_np(i & (ch - 1), lc)
    return out.astype(np.int32)


# ---------------------------------------------------------------------------
# Chunk-tree geometry. The uptree kernel writes, per chunk, the concatenated
# levels 1..lc as ROWS of 128 lanes: levels with width >= 128 are row-packed
# (width/128 rows, node at flat position q -> row q>>7, lane q&127); levels
# with width < 128 occupy one row each with the valid nodes in lanes
# [0, width) (roll-fold garbage beyond). Node (l, k) sits at position
# q = rev_{lc-l}(k) — see fused_node_position.


class ChunkGeometry(NamedTuple):
    ch: int  # lanes per chunk (power of two)
    lc: int  # log2(ch): levels computed in-kernel
    rows_in: int  # ch // 128
    rows_out: int  # output rows per chunk (padded to a multiple of 8)
    row_off: Tuple[int, ...]  # row_off[l] = first output row of level l (l>=1)


@functools.lru_cache(maxsize=8)
def chunk_geometry(ch: int) -> ChunkGeometry:
    lc = ch.bit_length() - 1
    assert ch == 1 << lc and ch >= 256
    offs = [0]  # index 0 unused (level 0 lives in the gather output)
    total = 0
    for lvl in range(1, lc + 1):
        offs.append(total)
        width = ch >> lvl
        total += max(width // LANE, 1)
    rows_out = -(-total // 8) * 8
    return ChunkGeometry(ch, lc, ch // LANE, rows_out, tuple(offs))


def fused_node_position(g: ChunkGeometry, lvl: int, k) -> "np.ndarray":
    """Flat in-level position of chunk-tree node k at level lvl (numpy)."""
    return brev_np(np.asarray(k), g.lc - lvl)


# ---------------------------------------------------------------------------
# fe25519-based point add for the CPU twins (same unified a=-1 formula as
# msm_jax._padd; coordinates are 4-tuples of (NL, ...) arrays). The twins
# must NOT use the in-kernel row convolution: it inlines to ~8k HLO per add,
# which is the exact XLA:CPU compile explosion PERF.md documents.

_COMP_NP = np.asarray(fe.COMP)
_CORR_NP = np.asarray(fe.CORR)
_D2_NP = np.asarray(fe.from_int(fe.D2))


def _rs_c(c: np.ndarray, ndim: int) -> np.ndarray:
    return c.reshape((NL,) + (1,) * (ndim - 1))


def _fe_sub(a, b):
    return fe.sub(a, b, _rs_c(_COMP_NP, a.ndim), _rs_c(_CORR_NP, a.ndim))


def _padd_fe(p, q):
    """Unified extended add on 4-tuples of (NL, ...batch) coordinates."""
    a = fe.mul(_fe_sub(p[1], p[0]), _fe_sub(q[1], q[0]))
    b = fe.mul(fe.add(p[1], p[0]), fe.add(q[1], q[0]))
    c = fe.mul(fe.mul(p[3], q[3]), _rs_c(_D2_NP, p[3].ndim))
    d = fe.mul_small(fe.mul(p[2], q[2]), 2)
    e = _fe_sub(b, a)
    f = _fe_sub(d, c)
    g = fe.add(d, c)
    h = fe.add(b, a)
    return (fe.mul(e, f), fe.mul(g, h), fe.mul(f, g), fe.mul(e, h))


# ---------------------------------------------------------------------------
# Fold primitives. Values inside kernels are per-coordinate lists of NL limb
# rows, each row a (R, 128) int32 — exactly pallas_fe's in-kernel form with
# a sublane-group axis. Folds pair position p with p + half:
#   sublane fold: (2h, 128) rows -> roll the top half down and add -> (h, 128)
#   lane fold:    one (1, 128) row -> roll lanes left by w and add; valid
#                 lanes shrink to [0, w) with garbage beyond (never indexed).
# Only offset-0 static slices and tpu rolls — no strided slicing in-kernel.


def _roll(real: bool, v, shift: int, axis: int):
    if shift == 0:
        return v
    if real:
        return pltpu.roll(v, shift, axis)
    return jnp.roll(v, shift, axis=axis)


def _fold_rows_coords(coords, h: int, real: bool):
    """coords: 4-tuple of NL-lists of (2h, 128) rows -> same with (h, 128):
    out[s] = v[s] + v[s + h] for s < h."""
    lo = tuple([r[:h] for r in rows] for rows in coords)
    hi = tuple([_roll(real, r, h, 0)[:h] for r in rows] for rows in coords)
    return _padd_rows(lo, hi)


def _fold_lanes_coords(coords, w: int, real: bool):
    """coords rows are (1, 128); out[q] = v[q] + v[q + w] for q < w."""
    rolled = tuple(
        [_roll(real, r, LANE - w, 1) for r in rows] for rows in coords
    )
    return _padd_rows(coords, rolled)


def _read_coords(block) -> Tuple[List, List, List, List]:
    return tuple([block[c, i] for i in range(NL)] for c in range(4))


def _stack_coords(coords) -> jnp.ndarray:
    return jnp.stack([jnp.stack(rows) for rows in coords])


# ---------------------------------------------------------------------------
# Stage 1: chunk-local pair-tree up-sweep.


def _uptree_block(cur, g: ChunkGeometry, real: bool):
    """One chunk: cur = per coordinate the NL limb planes (rows_in, 128) of
    the bit-reversed level-0 lanes -> the same shape of (rows_out, 128)
    planes, the concatenated levels 1..lc (see chunk_geometry)."""
    levels: List = []  # per level: 4-tuple of NL-lists of planes

    rows = g.rows_in
    while rows > 1:  # levels down to width 128: sublane folds
        rows //= 2
        cur = _fold_rows_coords(cur, rows, real)
        levels.append(cur)
    w = LANE // 2  # remaining levels fold within the single (1, 128) row
    while w >= 1:
        cur = _fold_lanes_coords(cur, w, real)
        levels.append(cur)
        w //= 2
    # assemble: concat emitted levels per (coord, limb), zero-pad to rows_out
    pad = g.rows_out - sum(lvl[0][0].shape[0] for lvl in levels)
    tail = [jnp.zeros((pad, LANE), jnp.int32)] if pad else []
    return tuple(
        [jnp.concatenate([lvl[c][i] for lvl in levels] + tail, axis=0) for i in range(NL)]
        for c in range(4)
    )


def _uptree_kernel(g: ChunkGeometry):
    """One chunk, rows in and rows out: x_ref (ch, NW) point rows of the
    bit-reversed level-0 lanes -> o_ref (rows_out*128, NW) rows of levels
    1..lc, tree position p at row p. The change of layout happens here, on
    the block in VMEM: each 128-row tile is transposed on its own, and a
    sublane-strided access through a scratch gathers (scatters) word w of
    every tile into (from) the (rows, 128) plane _uptree_block folds."""

    def kernel(x_ref, o_ref, lvl0_ref, tree_ref):
        for s in range(g.rows_in):
            lvl0_ref[s * NW : (s + 1) * NW, :] = x_ref[s * LANE : (s + 1) * LANE, :].T
        lvl0 = tuple(
            [lvl0_ref[pl.ds(c * NL + i, g.rows_in, stride=NW), :] for i in range(NL)]
            for c in range(4)
        )
        tree = _uptree_block(lvl0, g, real=not pallas_fe._interpret())
        for c in range(4):
            for i in range(NL):
                tree_ref[pl.ds(c * NL + i, g.rows_out, stride=NW), :] = tree[c][i]
        for r in range(g.rows_out):
            o_ref[r * LANE : (r + 1) * LANE, :] = tree_ref[r * NW : (r + 1) * NW, :].T

    return kernel


@functools.lru_cache(maxsize=64)
def _uptree_call(n_rows: int, ch: int):
    g = chunk_geometry(ch)
    nchunks = n_rows // ch
    return pl.pallas_call(
        _uptree_kernel(g),
        grid=(nchunks,),
        in_specs=[pl.BlockSpec((ch, NW), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((g.rows_out * LANE, NW), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nchunks * g.rows_out * LANE, NW), jnp.int32),
        scratch_shapes=[
            pltpu.VMEM((g.rows_in * NW, LANE), jnp.int32),
            pltpu.VMEM((g.rows_out * NW, LANE), jnp.int32),
        ],
        interpret=pallas_fe._interpret(),
        name="msm_uptree",
    )


def _uptree_jnp(lvl0_rows: jnp.ndarray, g: ChunkGeometry) -> jnp.ndarray:
    """CPU twin of _uptree_kernel over ALL chunks at once, rows in and rows
    out: identical fold schedule (slices for row folds, rolls for lane folds
    — garbage included, so outputs match the kernel positionally), fe25519
    point math. Its two changes of layout are XLA transposes, and counted."""
    LAYOUT_CONVERSIONS[0] += 2
    nchunks = lvl0_rows.shape[0] // g.ch
    v = lvl0_rows.T.reshape(4, NL, nchunks, g.rows_in, LANE)
    cur = tuple(v[c] for c in range(4))  # (NL, nchunks, R, 128)
    levels = []
    rows = g.rows_in
    while rows > 1:
        rows //= 2
        cur = _padd_fe(
            tuple(c[:, :, :rows] for c in cur),
            tuple(c[:, :, rows:] for c in cur),
        )
        levels.append(cur)
    w = LANE // 2
    while w >= 1:
        rolled = tuple(jnp.roll(c, LANE - w, axis=-1) for c in cur)
        cur = _padd_fe(cur, rolled)
        levels.append(cur)
        w //= 2
    used = sum(lv[0].shape[2] for lv in levels)
    pad = jnp.zeros((NL, nchunks, g.rows_out - used, LANE), jnp.int32)
    out = jnp.stack(
        [
            jnp.concatenate([lv[c] for lv in levels] + [pad], axis=2)
            for c in range(4)
        ]
    )  # (4, NL, nchunks, rows_out, 128)
    return out.reshape(NW, nchunks * g.rows_out * LANE).T


def uptree(lvl0_rows: jnp.ndarray, ch: int) -> jnp.ndarray:
    """Point rows (M, NW) of the bit-reversed level-0 lanes, M a multiple of
    ch -> the chunk trees as rows ((M//ch)*rows_out*128, NW): the layout the
    row gather writes and the Fenwick gather reads, on both sides."""
    g = chunk_geometry(ch)
    m = lvl0_rows.shape[0]
    assert m % ch == 0 and lvl0_rows.shape[1] == NW
    if pallas_fe.enabled():
        return _uptree_call(m, ch)(lvl0_rows)
    return _uptree_jnp(lvl0_rows, g)


# ---------------------------------------------------------------------------
# Stage 2: Fenwick prefix reduce — accumulate K gathered node planes.


def _padd_block(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    return _stack_coords(_padd_rows(_read_coords(a), _read_coords(b)))


def _fenwick_kernel(p_ref, o_ref):
    k = pl.program_id(1)
    node = p_ref[:][0]  # (4, NL, blk, 128)

    @pl.when(k == 0)
    def _init():
        o_ref[:] = node

    @pl.when(k != 0)
    def _acc():
        o_ref[:] = _padd_block(o_ref[:], node)


@functools.lru_cache(maxsize=64)
def _fenwick_call(kf: int, s: int, blk: int):
    return pl.pallas_call(
        _fenwick_kernel,
        grid=(s // blk, kf),
        in_specs=[
            pl.BlockSpec((1, 4, NL, blk, LANE), lambda c, k: (k, 0, 0, c, 0))
        ],
        out_specs=pl.BlockSpec((4, NL, blk, LANE), lambda c, k: (0, 0, c, 0)),
        out_shape=jax.ShapeDtypeStruct((4, NL, s, LANE), jnp.int32),
        interpret=pallas_fe._interpret(),
        name="msm_fenwick_reduce",
    )


def fenwick_reduce(nodes: jnp.ndarray) -> jnp.ndarray:
    """(K, 4, NL, S, 128) gathered node planes -> (4, NL, S, 128) sums.
    In-kernel sequential accumulation: the output block stays in VMEM across
    the K grid steps (standard revisiting-accumulator pattern)."""
    kf, _, _, s, _ = nodes.shape
    if pallas_fe.enabled():
        # block rows must divide S exactly — grid=(s // blk, kf) would
        # silently truncate otherwise, leaving output rows uninitialized
        # (production S=64 uses 8; reduced-T tests can hit S=4)
        import math

        return _fenwick_call(kf, s, math.gcd(8, s))(nodes)
    acc = tuple(nodes[0, c] for c in range(4))
    for k in range(1, kf):
        acc = _padd_fe(acc, tuple(nodes[k, c] for c in range(4)))
    return jnp.stack(acc)


# ---------------------------------------------------------------------------
# Stage 3: bucket fold. Input: prefix points P_v per (bucket v, window t) in
# packed V-MAJOR order (flat lane index = v*T + t). Output rows:
#   row 0, lanes [0, T): sum over v in [0, 255) of P_v   (per window)
#   row 1, lanes [0, T): P_255                            (per window)
# The caller finishes W = [255]P_255 - sum on tiny (20, T) data.


def _bucket_block(block: jnp.ndarray, t_windows: int, real: bool) -> jnp.ndarray:
    n_rows = block.shape[2]
    nb = n_rows * LANE // t_windows  # buckets (256)
    coords = _read_coords(block)

    # P_255 row: flat positions [ (nb-1)*T, nb*T ) live in the last row at
    # lanes [128 - T, 128): roll rows down by 1 (last row -> row 0), then
    # lanes left so window t lands at lane t.
    def extract_last(r):
        top = _roll(real, r, 1, 0)[:1]
        return _roll(real, top, t_windows, 1)

    p255 = tuple([extract_last(r) for r in rows] for rows in coords)

    # mask bucket 255 to the identity so the fold sums v in [0, 255)
    sub = jax.lax.broadcasted_iota(jnp.int32, (n_rows, LANE), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (n_rows, LANE), 1)
    keep = (sub * LANE + lane) < (nb - 1) * t_windows
    one = jnp.where(keep, 0, 1).astype(jnp.int32)  # identity limb-0 rows

    def mask_coord(rows, is_one):
        out = [jnp.where(keep, r, 0) for r in rows]
        if is_one:
            out[0] = out[0] + one
        return out

    cur = (
        mask_coord(coords[0], False),  # x -> 0
        mask_coord(coords[1], True),  # y -> 1
        mask_coord(coords[2], True),  # z -> 1
        mask_coord(coords[3], False),  # t -> 0
    )

    rows = n_rows
    while rows > 1:
        rows //= 2
        cur = _fold_rows_coords(cur, rows, real)
    w = LANE // 2
    while w >= t_windows:
        cur = _fold_lanes_coords(cur, w, real)
        w //= 2

    pad = 8 - 2
    out = []
    for c in range(4):
        limb_rows = []
        for i in range(NL):
            limb_rows.append(
                jnp.concatenate(
                    [cur[c][i], p255[c][i], jnp.zeros((pad, LANE), jnp.int32)],
                    axis=0,
                )
            )
        out.append(jnp.stack(limb_rows))
    return jnp.stack(out)


def _bucket_kernel(t_windows: int):
    def kernel(x_ref, o_ref):
        o_ref[:] = _bucket_block(
            x_ref[:], t_windows, real=not pallas_fe._interpret()
        )

    return kernel


@functools.lru_cache(maxsize=16)
def _bucket_call(s: int, t_windows: int):
    return pl.pallas_call(
        _bucket_kernel(t_windows),
        grid=(1,),
        in_specs=[pl.BlockSpec((4, NL, s, LANE), lambda i: (0, 0, 0, 0))],
        out_specs=pl.BlockSpec((4, NL, 8, LANE), lambda i: (0, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((4, NL, 8, LANE), jnp.int32),
        interpret=pallas_fe._interpret(),
        name="msm_bucket_fold",
    )


def _bucket_jnp(block: jnp.ndarray, t_windows: int) -> jnp.ndarray:
    """CPU twin of _bucket_block: identical mask/fold/extract schedule,
    fe25519 point math."""
    n_rows = block.shape[2]
    nb = n_rows * LANE // t_windows
    coords = tuple(block[c] for c in range(4))  # (NL, R, 128)

    def extract_last(c):
        top = jnp.roll(c, 1, axis=1)[:, :1]
        return jnp.roll(top, t_windows, axis=-1)

    p255 = tuple(extract_last(c) for c in coords)

    sub = jax.lax.broadcasted_iota(jnp.int32, (n_rows, LANE), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (n_rows, LANE), 1)
    keep = (sub * LANE + lane) < (nb - 1) * t_windows
    idc = np.zeros((NL, 1, 1), dtype=np.int32)
    idc_one = idc.copy()
    idc_one[0] = 1
    cur = (
        jnp.where(keep, coords[0], idc),
        jnp.where(keep, coords[1], idc_one),
        jnp.where(keep, coords[2], idc_one),
        jnp.where(keep, coords[3], idc),
    )

    rows = n_rows
    while rows > 1:
        rows //= 2
        cur = _padd_fe(
            tuple(c[:, :rows] for c in cur), tuple(c[:, rows:] for c in cur)
        )
    w = LANE // 2
    while w >= t_windows:
        rolled = tuple(jnp.roll(c, LANE - w, axis=-1) for c in cur)
        cur = _padd_fe(cur, rolled)
        w //= 2

    pad = jnp.zeros((NL, 8 - 2, LANE), jnp.int32)
    return jnp.stack(
        [
            jnp.concatenate([cur[c], p255[c], pad], axis=1)
            for c in range(4)
        ]
    )


def bucket_fold(prefix_packed: jnp.ndarray, t_windows: int):
    """Packed v-major prefix points -> (sum_{v<255} P_v, P_255), each a
    4-tuple of (NL, T) coordinate arrays (limb-major, ready for the tiny
    window-combine tail)."""
    s = prefix_packed.shape[2]
    assert (s * LANE) % t_windows == 0
    assert t_windows <= LANE and LANE % t_windows == 0
    if pallas_fe.enabled():
        out = _bucket_call(s, t_windows)(prefix_packed)
    else:
        out = _bucket_jnp(prefix_packed, t_windows)
    LAYOUT_CONVERSIONS[0] += 1
    s_pt = tuple(out[c, :, 0, :t_windows] for c in range(4))
    p255 = tuple(out[c, :, 1, :t_windows] for c in range(4))
    return s_pt, p255
