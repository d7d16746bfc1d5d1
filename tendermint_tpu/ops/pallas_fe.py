"""Fused curve-point arithmetic as Pallas TPU kernels.

Why this exists (measured on v5e, round 4): the jnp/XLA field multiply
(ops/fe25519.py) runs at ~18 ms per 655k lanes because its 20 shifted
`.at[].add` accumulator updates materialize the 39-row product accumulator
to HBM repeatedly — ~8 GB of traffic per multiply. The same convolution as
ONE Pallas kernel holds every intermediate in VMEM/registers and runs in
~1.65 ms (11x). A whole unified point addition (9 muls + adds/subs/carries)
fuses into a single kernel, so the MSM pipeline's tree/prefix/bucket phases
(ops/msm_jax.py) — which are nothing but batched point adds — ride these
kernels. A second structural win: each call site becomes one HLO custom
call instead of ~500 fused ops, collapsing XLA graph size and compile time.

Layout: coordinates are int32[20, S, 128] — limb axis leading, lanes split
into (sublane-group, 128-lane) tiles so every per-limb row is a full-tile
2D array (no sublane waste, no lane shuffles). Wrappers accept the
(20, ...batch) layout used everywhere else and reshape/pad.

In-kernel field elements are PYTHON LISTS of 20 (S, 128) rows; the
algorithms (uniform radix-2^13 convolution, parallel carry passes, 2^260
wrap = 608) mirror ops/fe25519.py line for line — differential tests pin
them together (tests/test_pallas_fe.py).

Enabled on the TPU backend (TMTPU_PALLAS=0 disables; =interpret runs the
Mosaic interpreter for CPU correctness tests)."""

from __future__ import annotations

import functools
import os
from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from tendermint_tpu.ops import fe25519 as fe
from tendermint_tpu.ops.ed25519_jax import Point

NL = fe.NLIMBS  # 20
RADIX = fe.RADIX  # 13
MASK = fe.MASK
WRAP = fe.WRAP  # 608
LANE = 128
BLK = 16  # sublane groups per grid step: blocks of 16*128 = 2048 lanes

_COMP = [int(x) for x in np.asarray(fe.COMP)]
_CORR = [int(x) for x in np.asarray(fe.CORR)]
_D2 = [int(x) for x in fe.from_int(fe.D2)]

Rows = List[jnp.ndarray]  # 20 rows of (S, 128) int32


def _mode() -> str:
    return os.environ.get("TMTPU_PALLAS", "auto")


def enabled() -> bool:
    m = _mode()
    if m == "0":
        return False
    if m == "interpret":
        return True
    return jax.default_backend() == "tpu"


def _interpret() -> bool:
    return _mode() == "interpret"


# ---------------------------------------------------------------------------
# In-kernel field ops on row lists. Invariant mirrors fe25519: "carried"
# rows satisfy row_i <= 2^13 (+608 slack at row 0).


def _rcarry(rows: Rows, passes: int = 4) -> Rows:
    """fe25519.carry, row-wise: parallel carry passes + 2^260 wrap."""
    for _ in range(passes):
        cs = [r >> RADIX for r in rows]
        rows = [
            (rows[i] & MASK) + (cs[i - 1] if i > 0 else WRAP * cs[NL - 1])
            for i in range(NL)
        ]
    return rows


def _radd(a: Rows, b: Rows) -> Rows:
    return _rcarry([x + y for x, y in zip(a, b)])


def _rsub(a: Rows, b: Rows) -> Rows:
    # a - b == a + (COMP - b) + CORR (fe25519.sub)
    return _rcarry(
        [a[i] - b[i] + (_COMP[i] + _CORR[i]) for i in range(NL)]
    )


def _rmul_small(a: Rows, k: int) -> Rows:
    return _rcarry([r * k for r in a])


def _product_rows(a: Rows, b: Rows) -> Rows:
    """Raw 39-row schoolbook convolution (fe25519.mul's acc)."""
    rows: List = [None] * (2 * NL - 1)
    for i in range(NL):
        ai = a[i]
        for j in range(NL):
            t = ai * b[j]
            k = i + j
            rows[k] = t if rows[k] is None else rows[k] + t
    return rows


def _square_rows(a: Rows) -> Rows:
    """fe25519.square's symmetric convolution (half the multiplies)."""
    rows: List = [None] * (2 * NL - 1)
    a2 = [x + x for x in a]
    for i in range(NL):
        t = a[i] * a[i]
        rows[2 * i] = t if rows[2 * i] is None else rows[2 * i] + t
        for j in range(i + 1, NL):
            t = a[i] * a2[j]
            k = i + j
            rows[k] = t if rows[k] is None else rows[k] + t
    return rows


def _reduce_39(acc: Rows) -> Rows:
    """fe25519.mul's reduction: 2 parallel passes over 39 rows (top carry
    folds onto row 19 with factor 608), fold rows >= 20 with 608, carry."""
    n = 2 * NL - 1
    for _ in range(2):
        cs = [r >> RADIX for r in acc]
        acc = [
            (acc[i] & MASK) + (cs[i - 1] if i > 0 else 0)
            for i in range(n)
        ]
        acc[NL - 1] = acc[NL - 1] + WRAP * cs[n - 1]
    out = [
        acc[k] + (WRAP * acc[k + NL] if k + NL < n else 0)
        for k in range(NL)
    ]
    return _rcarry(out)


def _rmul(a: Rows, b: Rows) -> Rows:
    return _reduce_39(_product_rows(a, b))


def _rsquare(a: Rows) -> Rows:
    return _reduce_39(_square_rows(a))


def _rmul_const(a: Rows, c: Sequence[int]) -> Rows:
    """Multiply by a constant field element given as canonical limb ints."""
    rows: List = [None] * (2 * NL - 1)
    for i in range(NL):
        ai = a[i]
        for j in range(NL):
            if c[j] == 0:
                continue
            t = ai * c[j]
            k = i + j
            rows[k] = t if rows[k] is None else rows[k] + t
    for k in range(2 * NL - 1):
        if rows[k] is None:
            rows[k] = jnp.zeros_like(a[0])
    return _reduce_39(rows)


# ---------------------------------------------------------------------------
# Point kernels. A point block is int32[4, 20, S, 128] (x, y, z, t).


def _read_point(ref) -> Tuple[Rows, Rows, Rows, Rows]:
    v = ref[:]
    return tuple([v[c, i] for i in range(NL)] for c in range(4))


def _write_point(ref, coords: Tuple[Rows, Rows, Rows, Rows]) -> None:
    ref[:] = jnp.stack([jnp.stack(rows) for rows in coords])


def _padd_rows(p, q):
    """Unified a=-1 extended add (add-2008-hwcd-3), all in-kernel
    (mirrors ops/msm_jax._padd / ed25519_jax.point_add)."""
    px, py, pz, pt = p
    qx, qy, qz, qt = q
    a = _rmul(_rsub(py, px), _rsub(qy, qx))
    b = _rmul(_radd(py, px), _radd(qy, qx))
    c = _rmul_const(_rmul(pt, qt), _D2)
    d = _rmul_small(_rmul(pz, qz), 2)
    e = _rsub(b, a)
    f = _rsub(d, c)
    g = _radd(d, c)
    h = _radd(b, a)
    return (_rmul(e, f), _rmul(g, h), _rmul(f, g), _rmul(e, h))


def _pdbl_rows(p):
    """dbl-2008-hwcd for a=-1 (mirrors ops/msm_jax._pdbl)."""
    px, py, pz, pt = p
    xx = _rsquare(px)
    yy = _rsquare(py)
    zz2 = _rmul_small(_rsquare(pz), 2)
    xy2 = _rsquare(_radd(px, py))
    s = _radd(xx, yy)
    e = _rsub(xy2, s)
    g = _rsub(yy, xx)
    f = _rsub(g, zz2)
    zero = [jnp.zeros_like(r) for r in s]
    h = _rsub(zero, s)
    return (_rmul(e, f), _rmul(g, h), _rmul(f, g), _rmul(e, h))


def _fsq_n_kernel(n: int):
    """x -> x^(2^n) on a single field element block (NL, S, 128)."""

    def kernel(x_ref, o_ref):
        v = x_ref[:]
        rows = [v[i] for i in range(NL)]
        for _ in range(n):
            rows = _rsquare(rows)
        o_ref[:] = jnp.stack(rows)

    return kernel


def _padd_kernel(p_ref, q_ref, o_ref):
    _write_point(o_ref, _padd_rows(_read_point(p_ref), _read_point(q_ref)))


def _pdbl_kernel(p_ref, o_ref):
    _write_point(o_ref, _pdbl_rows(_read_point(p_ref)))


def _pdbl_n_kernel(n: int):
    def kernel(p_ref, o_ref):
        p = _read_point(p_ref)
        for _ in range(n):
            p = _pdbl_rows(p)
        _write_point(o_ref, p)

    return kernel


@functools.lru_cache(maxsize=256)
def _padd_call(s: int, blk: int):
    spec = pl.BlockSpec((4, NL, blk, LANE), lambda i: (0, 0, i, 0))
    return pl.pallas_call(
        _padd_kernel,
        grid=(s // blk,),
        in_specs=[spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((4, NL, s, LANE), jnp.int32),
        interpret=_interpret(),
        name="fe_padd",
    )


@functools.lru_cache(maxsize=256)
def _pdbl_call(s: int, blk: int, n: int = 1):
    spec = pl.BlockSpec((4, NL, blk, LANE), lambda i: (0, 0, i, 0))
    return pl.pallas_call(
        _pdbl_kernel if n == 1 else _pdbl_n_kernel(n),
        grid=(s // blk,),
        in_specs=[spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((4, NL, s, LANE), jnp.int32),
        interpret=_interpret(),
        name="fe_pdbl",
    )


@functools.lru_cache(maxsize=256)
def _fsq_call(s: int, blk: int, n: int):
    spec = pl.BlockSpec((NL, blk, LANE), lambda i: (0, i, 0))
    return pl.pallas_call(
        _fsq_n_kernel(n),
        grid=(s // blk,),
        in_specs=[spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((NL, s, LANE), jnp.int32),
        interpret=_interpret(),
        name="fe_fsq",
    )


def fsquare_chain(a: jnp.ndarray, k: int) -> jnp.ndarray:
    """a^(2^k) for a field element (20, ...batch) — the sqrt/inversion
    ladders' ~250 sequential squarings, fused 16-deep into Pallas kernels.
    The fori_loop form spent ~14 ms/call in device `while` overhead at 1k
    lanes (traced); the fused chunks remove the loop machinery entirely."""
    batch_shape = a.shape[1:]
    n = 1
    for d in batch_shape:
        n *= d
    flat = a.reshape(NL, n)
    pad = (-n) % (8 * LANE)
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((NL, pad), jnp.int32)], axis=-1)
    s = (n + pad) // LANE
    x = flat.reshape(NL, s, LANE)
    blk = _pick_blk(s)
    while k > 0:
        step = min(k, 16)
        x = _fsq_call(s, blk, step)(x)
        k -= step
    return x.reshape(NL, -1)[:, :n].reshape(NL, *batch_shape)


# ---------------------------------------------------------------------------
# Public wrappers: Point with coords (20, ...batch) -> same shape out.


def _pack(p: Point):
    """Point (20, ...batch) -> (packed (4,20,S,128), batch_shape, n_lanes)."""
    batch_shape = p.x.shape[1:]
    n = 1
    for d in batch_shape:
        n *= d
    flat = jnp.stack([c.reshape(NL, n) for c in p], axis=0)  # (4, 20, n)
    # pad to a multiple of 8*128 lanes: Mosaic requires the sublane-group
    # block dim divisible by 8 (or whole-array)
    pad = (-n) % (8 * LANE)
    if pad:
        flat = jnp.concatenate(
            [flat, jnp.zeros((4, NL, pad), jnp.int32)], axis=-1
        )
    s = (n + pad) // LANE
    return flat.reshape(4, NL, s, LANE), batch_shape, n


def _unpack(packed, batch_shape, n) -> Point:
    flat = packed.reshape(4, NL, -1)[:, :, :n]
    return Point(*(flat[c].reshape(NL, *batch_shape) for c in range(4)))


def _pick_blk(s: int) -> int:
    # s is a multiple of 8 by construction (_pack); blocks must be too
    return BLK if s % BLK == 0 else 8


def padd(p: Point, q: Point) -> Point:
    pp, bs, n = _pack(p)
    qq, _, _ = _pack(q)
    s = pp.shape[2]
    out = _padd_call(s, _pick_blk(s))(pp, qq)
    return _unpack(out, bs, n)


def pdbl(p: Point, times: int = 1) -> Point:
    """[2^times] p — chained doublings fused into ONE kernel (the Horner
    fold and bucket phases need runs of 8+ doublings; fusing them kills the
    per-call overhead that made the round-3 combine cost 64 ms)."""
    pp, bs, n = _pack(p)
    s = pp.shape[2]
    out = _pdbl_call(s, _pick_blk(s), times)(pp)
    return _unpack(out, bs, n)
