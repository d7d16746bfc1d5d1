"""Batched Ed25519 verification on TPU (JAX).

The validator-axis hot loop of the whole framework: verifies N signatures at
once, replacing the reference's serial per-signature loop
(reference: types/validator_set.go:680-702, types/vote_set.go:203,
crypto/ed25519/ed25519.go:148).

Semantics: COFACTORED verification (ZIP-215-style) — accept iff
[8]([s]B + [h](-A) - R) == identity, with canonical A/R encodings and s < L
(enforced host-side). This is the framework's single verification predicate:
the host wrapper (crypto/keys.py), this kernel, and the RLC batch path
(ops/msm_jax.py) all implement it exactly, so acceptance never depends on
which path a node runs. Divergences from golang.org/x/crypto (cofactorless,
accepts non-canonical A) exist only for crafted torsion/non-canonical
inputs; honest keys and signatures are torsion-free and canonical, where
all predicates agree (see crypto/ed25519_ref.verify_cofactored).

Layout: batch on the TRAILING axis everywhere (limbs/bytes/digits leading) so
the batch maps onto TPU vector lanes. Points are (X, Y, Z, T) extended twisted
Edwards coordinates; adds use the unified a=-1 formulas, so identity and
doubling need no special cases inside the scan.

The scalar multiplication is a joint windowed double-scalar ladder in signed
radix-16: scalars are recoded host-side into 64 digits in [-8, 8] (LSB-first
in memory, scanned MSB-first). Each scan step does 4 doublings, one mixed add
from the basepoint table (j*B in affine niels form, j=0..8, negation by
coordinate swap) and one unified add from the per-signature table j*(-A)
(j=0..8 extended points, built with 7 adds + 1 double before the scan).

TPU performance note (measured on v5e): XLA compiles per-limb CONSTANT
broadcasts (a (20,1) constant against a (20,B) tensor) into fusions ~200x
slower than the same op against a real (20,B) buffer. Every non-uniform
constant the kernel needs — field constants, the basepoint niels table —
is therefore materialized ONCE as a device array (FieldCtx) outside the jit
and passed in as an argument. Inside foreign traces (shard_map on CPU, the
multichip dryrun) the ctx falls back to in-trace broadcasts, which is
correct everywhere and only slow where it doesn't matter.
"""

from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from tendermint_tpu.crypto import ed25519_ref as _ref
from tendermint_tpu.crypto.ed25519_ref import BX as _BX, _BY
from tendermint_tpu.ops import fe25519 as fe

SCALAR_BITS = 253  # s, h < L < 2^253
NUM_DIGITS = 64  # signed radix-16 digits covering 256 bits
WINDOW = 8  # table holds j*P for j in 0..8; sign handled by negation


class Point(NamedTuple):
    x: jnp.ndarray
    y: jnp.ndarray
    z: jnp.ndarray
    t: jnp.ndarray


def _basepoint_niels_table() -> np.ndarray:
    """Host precompute: j*B for j=0..8 in affine niels form (y+x, y-x, 2dxy),
    canonical limbs. Shape (9, 3, 20) int32. Entry 0 is the identity (1,1,0),
    so digit 0 rides the same unified mixed-add formula."""
    tab = np.zeros((WINDOW + 1, 3, fe.NLIMBS), dtype=np.int32)
    tab[0, 0] = fe.from_int(1)
    tab[0, 1] = fe.from_int(1)
    for j in range(1, WINDOW + 1):
        X, Y, Z, _T = _ref.point_mul(j, _ref.BASE)
        zinv = pow(Z, fe.P - 2, fe.P)
        x, y = X * zinv % fe.P, Y * zinv % fe.P
        tab[j, 0] = fe.from_int((y + x) % fe.P)
        tab[j, 1] = fe.from_int((y - x) % fe.P)
        tab[j, 2] = fe.from_int(2 * fe.D * x * y % fe.P)
    return tab


_B_NIELS_HOST = _basepoint_niels_table()  # (9, 3, 20)


class FieldCtx(NamedTuple):
    """Materialized per-batch-shape constants (see module docstring)."""

    comp: jnp.ndarray  # (20, ...batch) — fe.COMP
    corr: jnp.ndarray  # (20, ...batch) — fe.CORR
    one: jnp.ndarray  # (20, ...batch) — field 1
    d: jnp.ndarray  # (20, ...batch) — curve d
    d2: jnp.ndarray  # (20, ...batch) — 2d
    sqrt_m1: jnp.ndarray  # (20, ...batch)
    bniels: jnp.ndarray  # (9, 3, 20, ...batch) — basepoint niels table

    # -- field helpers bound to the materialized constants ------------------

    def sub(self, a, b):
        return fe.sub(a, b, self.comp, self.corr)

    def neg(self, a):
        return fe.sub(jnp.zeros_like(a), a, self.comp, self.corr)

    def zero(self):
        return jnp.zeros_like(self.one)


def _broadcast(x: np.ndarray, batch_shape) -> jnp.ndarray:
    return jnp.asarray(
        np.broadcast_to(
            x.reshape(x.shape + (1,) * len(batch_shape)), x.shape + tuple(batch_shape)
        ).copy()
    )


_CTX_CACHE: dict = {}
_CTX_CACHE_MAX = 8  # bniels is ~2.6KB/element; bound the device pinning


def make_ctx(batch_shape) -> FieldCtx:
    """Eagerly build (and cache, FIFO-bounded) the materialized constants for
    a batch shape. Must be called OUTSIDE any jax trace to produce real
    device buffers."""
    key = tuple(batch_shape)
    ctx = _CTX_CACHE.get(key)
    if ctx is None:
        while len(_CTX_CACHE) >= _CTX_CACHE_MAX:
            _CTX_CACHE.pop(next(iter(_CTX_CACHE)))
        ctx = FieldCtx(
            comp=_broadcast(np.asarray(fe.COMP), batch_shape),
            corr=_broadcast(np.asarray(fe.CORR), batch_shape),
            one=_broadcast(fe.from_int(1), batch_shape),
            d=_broadcast(fe.from_int(fe.D), batch_shape),
            d2=_broadcast(fe.from_int(fe.D2), batch_shape),
            sqrt_m1=_broadcast(fe.from_int(fe.SQRT_M1), batch_shape),
            bniels=_broadcast(_B_NIELS_HOST, batch_shape),
        )
        _CTX_CACHE[key] = ctx
    return ctx


def _trace_ctx(batch_shape) -> FieldCtx:
    """In-trace fallback: plain broadcast constants (correct, not fast)."""

    def bc(x):
        x = jnp.asarray(np.asarray(x, dtype=np.int32))
        return jnp.broadcast_to(
            x.reshape(x.shape + (1,) * len(batch_shape)), x.shape + tuple(batch_shape)
        )

    return FieldCtx(
        comp=bc(fe.COMP),
        corr=bc(fe.CORR),
        one=bc(fe.from_int(1)),
        d=bc(fe.from_int(fe.D)),
        d2=bc(fe.from_int(fe.D2)),
        sqrt_m1=bc(fe.from_int(fe.SQRT_M1)),
        bniels=bc(_B_NIELS_HOST),
    )


def identity(ctx: FieldCtx) -> Point:
    z = ctx.zero()
    return Point(z, ctx.one, ctx.one, z)


def point_add(ctx: FieldCtx, p: Point, q: Point) -> Point:
    """Unified a=-1 extended addition (add-2008-hwcd-3): 8M + 1 const-mul."""
    a = fe.mul(ctx.sub(p.y, p.x), ctx.sub(q.y, q.x))
    b = fe.mul(fe.add(p.y, p.x), fe.add(q.y, q.x))
    c = fe.mul(fe.mul(p.t, q.t), ctx.d2)
    d = fe.mul_small(fe.mul(p.z, q.z), 2)
    e = ctx.sub(b, a)
    f = ctx.sub(d, c)
    g = fe.add(d, c)
    h = fe.add(b, a)
    return Point(fe.mul(e, f), fe.mul(g, h), fe.mul(f, g), fe.mul(e, h))


def point_double(ctx: FieldCtx, p: Point) -> Point:
    """dbl-2008-hwcd for a=-1: 4M + 4S (cheaper than unified add)."""
    xx = fe.square(p.x)  # A
    yy = fe.square(p.y)  # B
    zz2 = fe.mul_small(fe.square(p.z), 2)  # C
    xy2 = fe.square(fe.add(p.x, p.y))
    e = ctx.sub(xy2, fe.add(xx, yy))  # E = (X+Y)^2 - A - B = 2XY
    g = ctx.sub(yy, xx)  # G = D + B = B - A   (D = aA = -A)
    f = ctx.sub(g, zz2)  # F = G - C
    h = ctx.neg(fe.add(xx, yy))  # H = D - B = -(A + B)
    return Point(fe.mul(e, f), fe.mul(g, h), fe.mul(f, g), fe.mul(e, h))


def point_neg(ctx: FieldCtx, p: Point) -> Point:
    return Point(ctx.neg(p.x), p.y, p.z, ctx.neg(p.t))


def point_select(cond: jnp.ndarray, a: Point, b: Point) -> Point:
    """cond ? a : b, cond shaped like the batch."""
    return Point(
        fe.select(cond, a.x, b.x),
        fe.select(cond, a.y, b.y),
        fe.select(cond, a.z, b.z),
        fe.select(cond, a.t, b.t),
    )


def decompress(ctx: FieldCtx, s_bytes: jnp.ndarray) -> Tuple[Point, jnp.ndarray]:
    """uint8[32, ...batch] -> (Point, ok mask). RFC 8032 §5.1.3."""
    s_bytes = jnp.asarray(s_bytes)
    sign = (s_bytes[31] >> 7).astype(jnp.int32)
    y = fe.from_bytes(s_bytes, mask_high_bit=True)
    canonical = fe.is_canonical_bytes(s_bytes)

    one = ctx.one
    yy = fe.square(y)
    u = ctx.sub(yy, one)
    v = fe.add(fe.mul(yy, ctx.d), one)
    v3 = fe.mul(fe.square(v), v)
    v7 = fe.mul(fe.square(v3), v)
    t = fe.pow_p58(fe.mul(u, v7))
    x = fe.mul(fe.mul(u, v3), t)  # candidate sqrt(u/v)

    vxx = fe.mul(v, fe.square(x))
    ok_direct = fe.eq(vxx, u)
    ok_flipped = fe.eq(vxx, ctx.neg(u))
    x = fe.select(ok_direct, x, fe.mul(x, ctx.sqrt_m1))
    ok = canonical & (ok_direct | ok_flipped)

    x_frozen = fe.freeze(x)
    x_is_zero = fe.is_zero(x)
    ok = ok & ~(x_is_zero & (sign == 1))
    flip = fe.bit(x_frozen, 0) != sign
    x = fe.select(flip, ctx.neg(x), x)
    return Point(x, y, one, fe.mul(x, y)), ok


def compress(p: Point) -> jnp.ndarray:
    """Point -> canonical encoding uint8[32, ...batch]."""
    zinv = fe.inv(p.z)
    x = fe.freeze(fe.mul(p.x, zinv))
    y = fe.mul(p.y, zinv)
    out = fe.to_bytes(y)
    sign = (fe.bit(x, 0) << jnp.int32(7)).astype(jnp.uint8)
    return out.at[31].set(out[31] | sign)


def _onehot(digit_mag: jnp.ndarray) -> jnp.ndarray:
    """int32[...batch] in [0,8] -> int32[9, ...batch] one-hot."""
    idx = jnp.arange(WINDOW + 1, dtype=jnp.int32).reshape(
        (WINDOW + 1,) + (1,) * digit_mag.ndim
    )
    return (digit_mag[None] == idx).astype(jnp.int32)


def _select_b_niels(ctx: FieldCtx, digit: jnp.ndarray):
    """Signed select from the materialized basepoint table.
    digit int32 in [-8,8]."""
    oh = _onehot(jnp.abs(digit))  # (9, ...batch)
    sel = jnp.sum(ctx.bniels * oh[:, None, None], axis=0)  # (3, 20, ...batch)
    yplus, yminus, xy2d = sel[0], sel[1], sel[2]
    neg = digit < 0
    yplus2 = fe.select(neg, yminus, yplus)
    yminus2 = fe.select(neg, yplus, yminus)
    xy2d2 = fe.select(neg, ctx.neg(xy2d), xy2d)
    return yplus2, yminus2, xy2d2


def add_niels(ctx: FieldCtx, p: Point, yplus, yminus, xy2d) -> Point:
    """Mixed add of an affine niels point (7M): the unified a=-1 formula with
    Z2=1 and the (y2+x2, y2-x2, 2d*x2*y2) products precomputed."""
    a = fe.mul(ctx.sub(p.y, p.x), yminus)
    b = fe.mul(fe.add(p.y, p.x), yplus)
    c = fe.mul(p.t, xy2d)
    d = fe.mul_small(p.z, 2)
    e = ctx.sub(b, a)
    f = ctx.sub(d, c)
    g = fe.add(d, c)
    h = fe.add(b, a)
    return Point(fe.mul(e, f), fe.mul(g, h), fe.mul(f, g), fe.mul(e, h))


def _select_point_table(ctx: FieldCtx, tx, ty, tz, tt, digit: jnp.ndarray) -> Point:
    """Signed select of an extended point from a per-batch table
    (9, 20, ...batch) per coordinate. Negation: x -> -x, t -> -t."""
    oh = _onehot(jnp.abs(digit))[:, None]  # (9, 1, ...batch)
    x = jnp.sum(tx * oh, axis=0)
    y = jnp.sum(ty * oh, axis=0)
    z = jnp.sum(tz * oh, axis=0)
    t = jnp.sum(tt * oh, axis=0)
    neg = digit < 0
    return Point(fe.select(neg, ctx.neg(x), x), y, z, fe.select(neg, ctx.neg(t), t))


def _verify_core(
    a_bytes: jnp.ndarray,
    r_bytes: jnp.ndarray,
    s_digits: jnp.ndarray,
    h_digits: jnp.ndarray,
    ctx: FieldCtx,
) -> jnp.ndarray:
    """Core batched check (cofactored): [8]([s]B + [h](-A) - R) == identity.
    Returns bool[...batch]."""
    a_bytes = jnp.asarray(a_bytes)
    r_bytes = jnp.asarray(r_bytes)
    s_digits = jnp.asarray(s_digits, dtype=jnp.int8).astype(jnp.int32)
    h_digits = jnp.asarray(h_digits, dtype=jnp.int8).astype(jnp.int32)

    neg_a, ok_a = decompress(ctx, a_bytes)
    neg_a = point_neg(ctx, neg_a)
    r_pt, ok_r = decompress(ctx, r_bytes)
    r_pt = point_select(ok_r, r_pt, identity(ctx))

    # Per-signature table: j*(-A) for j=0..8 (identity, -A, 2(-A), ..., 8(-A)).
    entries = [identity(ctx), neg_a]
    entries.append(point_double(ctx, neg_a))
    for _ in range(3, WINDOW + 1):
        entries.append(point_add(ctx, entries[-1], neg_a))
    ta_x = jnp.stack([e.x for e in entries])  # (9, 20, ...batch)
    ta_y = jnp.stack([e.y for e in entries])
    ta_z = jnp.stack([e.z for e in entries])
    ta_t = jnp.stack([e.t for e in entries])

    # MSB-first scan over digit pairs.
    xs = jnp.stack([s_digits[::-1], h_digits[::-1]], axis=1)  # (64, 2, ...batch)

    def step(acc: Point, dd):
        ds, dh = dd[0], dd[1]
        acc = point_double(ctx, point_double(ctx, point_double(ctx, point_double(ctx, acc))))
        acc = add_niels(ctx, acc, *_select_b_niels(ctx, ds))
        acc = point_add(ctx, acc, _select_point_table(ctx, ta_x, ta_y, ta_z, ta_t, dh))
        return acc, None

    acc, _ = jax.lax.scan(step, identity(ctx), xs)
    # Cofactored acceptance: q = acc - R, then [8]q must be the identity.
    # (Replacing the old enc(acc) == enc(R) compare also drops a field
    # inversion from the kernel.) The z != 0 guard rejects the (0,0,0,0)
    # output an exceptional unified addition on crafted torsion inputs
    # could produce, instead of silently accepting it.
    q = point_add(ctx, acc, point_neg(ctx, r_pt))
    for _ in range(3):
        q = point_double(ctx, q)
    is_id = fe.is_zero(q.x) & fe.eq(q.y, q.z) & ~fe.is_zero(q.z)
    return ok_a & ok_r & is_id


_verify_jit = jax.jit(_verify_core)


def verify_prepared(
    a_bytes: jnp.ndarray,
    r_bytes: jnp.ndarray,
    s_digits: jnp.ndarray,
    h_digits: jnp.ndarray,
) -> jnp.ndarray:
    """Public entry: batched cofactorless verification, bool[...batch].

    Outside a trace, materialized constants are built eagerly (fast path);
    inside someone else's jit/shard_map the in-trace fallback keeps it
    correct."""
    batch = jnp.shape(a_bytes)[1:]
    if any(
        isinstance(x, jax.core.Tracer)
        for x in (a_bytes, r_bytes, s_digits, h_digits)
    ):
        return _verify_core(a_bytes, r_bytes, s_digits, h_digits, _trace_ctx(batch))
    from tendermint_tpu.libs import trace as _trace
    from tendermint_tpu.ops import aot_cache  # lazy: avoids import cycle

    with _trace.span("kernel.persig", lanes=int(batch[0]) if batch else 1):
        return aot_cache.call(
            "persig", _verify_jit, a_bytes, r_bytes, s_digits, h_digits,
            make_ctx(batch),
        )
