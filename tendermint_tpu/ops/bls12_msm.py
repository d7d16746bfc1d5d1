"""General-base BLS12-381 G1 MSM on the fused Pippenger schedule.

Generalizes the ed25519 RLC engine (ops/msm_jax.py) to a general-base,
general-scalar 381-bit multiscalar multiplication — the aggregate-pubkey
workload of BLS aggregate commits (types/validator_set.py) and the opening
move for the ZK-prover serving scenario (ROADMAP item 4):

- HOST PREP IS SHARED: BLS scalars are < r < 2^255, so the existing 8-bit
  x 32-window digit schedule, `msm_jax.scalars_to_bytes`, and the native
  counting sort `msm_jax.sort_windows` are reused unchanged.
- POINT ARITHMETIC IS BRANCHLESS-COMPLETE: Renes-Costello-Batina 2015
  algorithm 7 (complete addition, a = 0, b3 = 12) over ops/fp381 Montgomery
  limbs — one formula covers add, double, identity and inverses, so bucket
  accumulation needs no exceptional-case lanes (the edwards engine gets the
  same property from the unified extended-coordinate add).
- BUCKET ACCUMULATION is a sorted-lane SEGMENTED SUFFIX SUM: lanes sorted
  by (window, digit) reduce in ceil(log2 n) distance-doubling rounds of
  one complete-add each (the same data movement the fused uptree kernel
  performs in VMEM; ops/pallas_bls.py carries the in-kernel form), then
  per-window weighted bucket sums via the standard 255-step suffix
  accumulation and a Horner window combine.

Like ops/fp381, every op runs identically on numpy (the tier-1 CPU twin —
and the production HOST path for aggregate-pubkey accumulation on
wheel-less containers: ~30x the pure-python Jacobian loop at 10k keys) and
on jax arrays. tests/test_bls_kernels.py pins both the point ops and full
MSMs bit-for-bit against crypto/bls_ref.py on real curve points.

Memory discipline: lanes are processed in WINDOW GROUPS of
`WINDOW_GROUP` x n rows (a 100k-key MSM peaks ~320 MB instead of 1.3 GB),
mirroring the crypto/batch.py flush planner's fixed-footprint chunking.
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import numpy as np

from tendermint_tpu.ops import fp381 as F
from tendermint_tpu.ops.msm_jax import NBUCKETS, NWIN, scalars_to_bytes, sort_windows

B3 = 12  # 3 * b, b = 4
WINDOW_GROUP = 8  # windows per segmented-sum block (memory bound)

# A point is (X, Y, Z) stacked (33, ...batch) int32 Montgomery limbs;
# the projective identity is (0 : 1 : 0).
Point = Tuple[np.ndarray, np.ndarray, np.ndarray]

_ONE_MONT = F.mont_from_int(1)


def identity(batch_shape=(), xp=np) -> Point:
    z = xp.zeros((F.NLIMBS, *batch_shape), dtype=np.int32)
    one = xp.broadcast_to(
        xp.asarray(_ONE_MONT).reshape((F.NLIMBS,) + (1,) * len(batch_shape)),
        (F.NLIMBS, *batch_shape),
    ).astype(np.int32)
    return (z, one, z)


def padd(p: Point, q: Point, xp=np) -> Point:
    """Complete addition (RCB15 algorithm 7, a = 0, b3 = 12): covers
    P+Q, P+P, P+(-P) and either operand the identity, branch-free.

    The b3 scaling of Y3 is applied to BOTH sub operands BEFORE the
    subtraction (sub(12*X3, 12*Y3) instead of 12*(X3 - Y3)) to respect the
    fp381 value-bound discipline (a scaled sub output would exceed the
    Montgomery mul precondition; see fp381.COMP_LIMBS)."""
    X1, Y1, Z1 = (F.rows_of(c) for c in p)
    X2, Y2, Z2 = (F.rows_of(c) for c in q)
    mul, add, sub, small = F.mul_rows, F.add_rows, F.sub_rows, F.mul_small_rows
    t0 = mul(X1, X2)
    t1 = mul(Y1, Y2)
    t2 = mul(Z1, Z2)
    t3 = sub(mul(add(X1, Y1), add(X2, Y2)), add(t0, t1))  # X1Y2 + X2Y1
    t4 = sub(mul(add(Y1, Z1), add(Y2, Z2)), add(t1, t2))  # Y1Z2 + Y2Z1
    t0_3 = add(add(t0, t0), t0)  # 3*t0
    t2b = small(t2, B3)
    z3 = add(t1, t2b)
    t1s = sub(t1, t2b)
    # y3 = b3 * (X1Z2 + X2Z1), with b3 distributed into both sub operands
    # so the subtrahend stays a mul_small output (fp381 bound discipline)
    y3 = sub(
        small(mul(add(X1, Z1), add(X2, Z2)), B3), small(add(t0, t2), B3)
    )
    X3 = sub(mul(t3, t1s), mul(t4, y3))
    Y3 = add(mul(t1s, z3), mul(y3, t0_3))
    Z3 = add(mul(z3, t4), mul(t0_3, t3))
    return (F.stack(X3, xp), F.stack(Y3, xp), F.stack(Z3, xp))


def pselect(cond, a: Point, b: Point, xp=np) -> Point:
    """cond ? a : b with cond shaped like the batch."""
    c = cond[None] if hasattr(cond, "shape") else cond
    return tuple(xp.where(c, x, y) for x, y in zip(a, b))


# --------------------------------------------------------------------------
# host conversions


def points_from_affine_ints(coords: Sequence[Tuple[int, int]]) -> Point:
    """[(x, y), ...] affine ints -> batched Montgomery point block (Z = 1)."""
    n = len(coords)
    X = np.zeros((F.NLIMBS, n), dtype=np.int32)
    Y = np.zeros((F.NLIMBS, n), dtype=np.int32)
    Z = np.zeros((F.NLIMBS, n), dtype=np.int32)
    for j, (x, y) in enumerate(coords):
        X[:, j] = F.mont_from_int(x)
        Y[:, j] = F.mont_from_int(y)
        Z[:, j] = _ONE_MONT
    return (X, Y, Z)


def point_to_affine_int(pt: Point, lane: int = 0) -> Optional[Tuple[int, int]]:
    """One lane -> affine (x, y) python ints, or None for the identity.
    Host-side (python-int inversion); results are tiny (one point)."""
    xs = F.mont_to_ints(np.asarray(pt[0]).reshape(F.NLIMBS, -1)[:, lane : lane + 1])
    ys = F.mont_to_ints(np.asarray(pt[1]).reshape(F.NLIMBS, -1)[:, lane : lane + 1])
    zs = F.mont_to_ints(np.asarray(pt[2]).reshape(F.NLIMBS, -1)[:, lane : lane + 1])
    x, y, z = xs[0], ys[0], zs[0]
    if z == 0:
        return None
    zinv = pow(z, F.P - 2, F.P)
    return (x * zinv % F.P, y * zinv % F.P)


def _gather(pt: Point, idx, xp=np) -> Point:
    return tuple(xp.take(c, idx, axis=1) for c in pt)


# --------------------------------------------------------------------------
# segmented suffix-sum bucket accumulation


def _segment_sums(pt: Point, seg, n_rounds: int, xp=np) -> Point:
    """Rows sorted by segment id; after ceil(log2(max seg len)) distance-
    doubling rounds, the row at each segment HEAD holds the segment sum.
    Identity-padded partners carry seg id -1 (never equal)."""
    m = seg.shape[0]
    ident = identity((1,), xp)
    step = 1
    for _ in range(n_rounds):
        if step >= m:
            break
        part = tuple(
            xp.concatenate(
                [c[:, step:], xp.broadcast_to(i, (F.NLIMBS, step)).astype(np.int32)],
                axis=1,
            )
            for c, i in zip(pt, ident)
        )
        pseg = xp.concatenate([seg[step:], xp.full((step,), -1, seg.dtype)])
        summed = padd(pt, part, xp)
        pt = pselect(seg == pseg, summed, pt, xp)
        step *= 2
    return pt


def _weighted_window_sums(buckets: Point, xp=np) -> Point:
    """buckets: (33, T, 256) per coord -> per-window sums sum_d d*B[d]
    via the suffix-accumulation identity sum_d d*B[d] = sum_{j>=1} S_j,
    S_j = sum_{d>=j} B[d], computed LOG-DEPTH: 8 distance-doubling rounds
    build all suffix sums, 8 halving rounds reduce S_1..S_255. This is the
    device-path form (16 complete-adds total; under jit the python op count
    is irrelevant); the numpy twin's g1_msm uses the host tail instead."""
    t = buckets[0].shape[1]
    s = buckets
    step = 1
    while step < NBUCKETS:
        ident = identity((t, step), xp)
        part = tuple(
            xp.concatenate([c[:, :, step:], i], axis=2) for c, i in zip(s, ident)
        )
        s = padd(s, part, xp)
        step *= 2
    # drop S_0 (weight 0) then tree-reduce S_1..S_255 (+ one identity pad)
    ident = identity((t, 1), xp)
    s = tuple(
        xp.concatenate([c[:, :, 1:], i], axis=2) for c, i in zip(s, ident)
    )
    while s[0].shape[2] > 1:
        half = s[0].shape[2] // 2
        s = padd(
            tuple(c[:, :, :half] for c in s),
            tuple(c[:, :, half:] for c in s),
            xp,
        )
    return tuple(c[:, :, 0] for c in s)


def _combine_windows(w_sums: Point, xp=np) -> Point:
    """Horner over 8-bit windows: acc = 2^8 * acc + W[t], t = T-1 .. 0."""
    t = w_sums[0].shape[1]
    acc = tuple(c[:, t - 1 : t] for c in w_sums)
    for wi in range(t - 2, -1, -1):
        for _ in range(8):
            acc = padd(acc, acc, xp)
        acc = padd(acc, tuple(c[:, wi : wi + 1] for c in w_sums), xp)
    return acc


def g1_msm(
    coords: Sequence[Tuple[int, int]],
    scalars: Sequence[int],
    xp=np,
) -> Optional[Tuple[int, int]]:
    """General-base MSM: sum scalar_i * P_i -> affine ints (None=identity).

    coords: affine (x, y) int pairs (subgroup-checked by the caller —
    crypto keys are validated at ingestion); scalars: ints < r.
    """
    n = len(coords)
    if n == 0:
        return None
    if n != len(scalars):
        raise ValueError("coords/scalars length mismatch")
    digits = scalars_to_bytes([s % F.R_ORDER for s in scalars], n)
    perm, ends = sort_windows(digits)
    pts = points_from_affine_ints(coords)
    n_rounds = max(1, int(np.ceil(np.log2(max(n, 2)))))
    bucket_blocks = []
    for g0 in range(0, NWIN, WINDOW_GROUP):
        g1 = min(g0 + WINDOW_GROUP, NWIN)
        gw = g1 - g0
        # gather each window's sorted lanes; segment id = window * 256 + digit
        idx = np.concatenate([np.asarray(perm[t], dtype=np.int64) for t in range(g0, g1)])
        rows = _gather(pts, xp.asarray(idx), xp)
        segs = np.concatenate(
            [
                (t - g0) * NBUCKETS
                + digits[np.asarray(perm[t], dtype=np.int64), t].astype(np.int64)
                for t in range(g0, g1)
            ]
        )
        rows = _segment_sums(rows, xp.asarray(segs), n_rounds, xp)
        # bucket heads: segment start offsets from the sorted-ends table
        heads = np.zeros((gw, NBUCKETS), dtype=np.int64)
        counts = np.zeros((gw, NBUCKETS), dtype=np.int64)
        for t in range(g0, g1):
            e = np.asarray(ends[t], dtype=np.int64)
            starts = np.concatenate([[0], e[:-1]])
            heads[t - g0] = (t - g0) * n + starts
            counts[t - g0] = e - starts
        # empty buckets have start == segment end (possibly == the row
        # count); clamp for the gather — they are masked to identity below
        heads = np.minimum(heads, gw * n - 1)
        gathered = _gather(rows, xp.asarray(heads.ravel()), xp)
        gathered = pselect(
            xp.asarray(counts.ravel() > 0), gathered, identity((gw * NBUCKETS,), xp), xp
        )
        bucket_blocks.append(
            tuple(c.reshape(F.NLIMBS, gw, NBUCKETS) for c in gathered)
        )
    buckets = tuple(
        xp.concatenate([b[c] for b in bucket_blocks], axis=1) for c in range(3)
    )
    if xp is np:
        return _host_tail(buckets)
    w_sums = _weighted_window_sums(buckets, xp)
    total = _combine_windows(w_sums, xp)
    return point_to_affine_int(total)


def _host_tail(buckets: Point) -> Optional[Tuple[int, int]]:
    """CPU-twin tail: the O(T * 256) weighted-bucket/window-combine work on
    a FIXED 8k-point set (vs the O(n) bucket accumulation above) runs as
    python-int Jacobian arithmetic — ~30x fewer interpreter ops than limb
    form at this batch size. The device path keeps the limb form
    (_weighted_window_sums/_combine_windows); both tails are pinned equal
    in tests/test_bls_kernels.py.

    The limb points are HOMOGENEOUS projective (RCB: x = X/Z); one batched
    Montgomery-trick inversion converts all nonzero-Z buckets to affine
    before the bls_ref Jacobian arithmetic takes over."""
    from tendermint_tpu.crypto import bls_ref as B

    t = buckets[0].shape[1]
    xs, ys, zs = (
        F.mont_to_ints(np.ascontiguousarray(c).reshape(F.NLIMBS, -1))
        for c in buckets
    )
    # batch inversion of the nonzero Zs (one pow for the whole tail)
    nz = [i for i, z in enumerate(zs) if z != 0]
    prefix = [1]
    for i in nz:
        prefix.append(prefix[-1] * zs[i] % F.P)
    inv_all = pow(prefix[-1], F.P - 2, F.P)
    zinv = {}
    for k in range(len(nz) - 1, -1, -1):
        i = nz[k]
        zinv[i] = inv_all * prefix[k] % F.P
        inv_all = inv_all * zs[i] % F.P
    total = B.G1_IDENTITY
    for wi in range(t - 1, -1, -1):
        if wi != t - 1:
            for _ in range(8):
                total = B._jac_double(total)
        running = B.G1_IDENTITY
        wsum = B.G1_IDENTITY
        for d in range(NBUCKETS - 1, 0, -1):
            j = wi * NBUCKETS + d
            if zs[j] != 0:
                zi = zinv[j]
                pt = (
                    B._G1Field(xs[j] * zi % F.P),
                    B._G1Field(ys[j] * zi % F.P),
                    B._G1Field(1),
                )
                running = B._jac_add(running, pt)
            wsum = B._jac_add(wsum, running)
        total = B._jac_add(total, wsum)
    aff = B._jac_to_affine(total)
    return None if aff is None else (aff[0].v, aff[1].v)


def g1_aggregate_bitmap(
    coords: Sequence[Tuple[int, int]],
    bitmap: Sequence[bool],
    xp=np,
) -> Optional[Tuple[int, int]]:
    """Aggregate-pubkey sum over a signer bitmap: apk = sum_{bitmap} P_i.

    The 0/1-scalar MSM degenerates to ONE masked halving-tree reduction
    (log2 n complete-add rounds) — the hot path of VerifyAggregateCommit."""
    n = len(coords)
    if n != len(bitmap):
        raise ValueError("coords/bitmap length mismatch")
    sel = [c for c, b in zip(coords, bitmap) if b]
    if not sel:
        return None
    m = 1 << max(1, int(np.ceil(np.log2(max(len(sel), 2)))))
    pts = points_from_affine_ints(sel)
    ident = identity((m - len(sel),), xp)
    pts = tuple(
        xp.concatenate([xp.asarray(c), i], axis=1) for c, i in zip(pts, ident)
    )
    while pts[0].shape[1] > 1:
        half = pts[0].shape[1] // 2
        lo = tuple(c[:, :half] for c in pts)
        hi = tuple(c[:, half:] for c in pts)
        pts = padd(lo, hi, xp)
    return point_to_affine_int(pts)


# --------------------------------------------------------------------------
# device dispatch (AOT-cached; BLS-prefixed artifact names)


def _bitmap_fold_jnp(X, Y, Z):
    """Halving-tree fold over the lane axis, jnp form (shapes shrink per
    level, fully unrolled at trace time)."""
    import jax.numpy as jnp

    pts = (X, Y, Z)
    while pts[0].shape[1] > 1:
        half = pts[0].shape[1] // 2
        pts = padd(
            tuple(c[:, :half] for c in pts),
            tuple(c[:, half:] for c in pts),
            jnp,
        )
    return pts


def g1_aggregate_bitmap_device(
    coords: Sequence[Tuple[int, int]], bitmap: Sequence[bool]
) -> Optional[Tuple[int, int]]:
    """Device form of g1_aggregate_bitmap: identity-padded to the
    power-of-two jit bucket and dispatched through the AOT artifact cache
    under BLS-OWN names (`bls_bitmap_fold_<bucket>`), machine-fingerprint
    keyed like every artifact (ops/aot_cache.py) so BLS executables never
    collide with the ed25519 RLC family's."""
    import jax

    from tendermint_tpu.ops import aot_cache

    sel = [c for c, b in zip(coords, bitmap) if b]
    if not sel:
        return None
    m = 1 << max(1, int(np.ceil(np.log2(max(len(sel), 2)))))
    pts = points_from_affine_ints(sel)
    ident = identity((m - len(sel),))
    args = tuple(
        np.concatenate([c, i], axis=1) for c, i in zip(pts, ident)
    )
    fn = jax.jit(_bitmap_fold_jnp)
    name = f"bls_bitmap_fold_{m}"
    out = aot_cache.call(name, fn, *args)
    return point_to_affine_int(tuple(np.asarray(c) for c in out))
