"""Field-arithmetic microbenchmarks on the TPU.

Decides the round-4 kernel direction with measurements, not guesses:
- int32 13-bit-limb mul (current fe25519) vs an f32 8-bit-limb prototype —
  v5e's VPU runs f32 FMA at full rate while 32-bit integer multiply is
  emulated; if the f32 conv wins, the whole MSM pipeline scales with it.
- chained (data-dependent) ops so XLA cannot CSE the loop away — the r3
  microbench that "proved" int mul was free measured a CSE'd graph.
- scan vs unrolled sequential point-doubling chains (the Horner combine's
  64 ms is ~2 ms/iteration of lax.scan overhead on tiny tensors).

Usage: python tools/micro_fe.py
"""

import os
import sys
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

import jax
import jax.numpy as jnp
import numpy as np

from tendermint_tpu.ops.aot_cache import configure_compile_cache

configure_compile_cache()

from tendermint_tpu.ops import fe25519 as fe


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def sync(x):
    """Device sync by a D2H fetch of one element."""
    leaf = jax.tree_util.tree_leaves(x)[0]
    np.asarray(jax.device_get(leaf.ravel()[0]))


def timeit(name, fn, *args, iters=5):
    out = fn(*args)
    sync(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    sync(out)
    per = (time.perf_counter() - t0) / iters
    log(f"  {name:40s} {per*1e3:9.3f} ms")
    return per


CHAIN = 8  # dependent ops per jit call; per-op cost = total / CHAIN


def main():
    log(f"backend: {jax.default_backend()}")
    rng = np.random.default_rng(0)
    shape = (32, 20480)  # windows x lanes, the tree's hot shape
    nl = fe.NLIMBS

    a32 = jnp.asarray(rng.integers(0, 1 << 13, (nl, *shape), dtype=np.int32))
    b32 = jnp.asarray(rng.integers(0, 1 << 13, (nl, *shape), dtype=np.int32))

    # RTT floor: the cost of sync() itself
    tiny = jnp.zeros((1,))
    t0 = time.perf_counter()
    for _ in range(5):
        sync(tiny)
    log(f"  sync RTT floor: {(time.perf_counter()-t0)/5*1e3:.1f} ms")

    # -- int32 chained mul (current implementation) ------------------------
    @jax.jit
    def chain_mul_i32(a, b):
        x = a
        for _ in range(CHAIN):
            x = fe.mul(x, b)
        return x

    per = timeit("int32 fe.mul chained", chain_mul_i32, a32, b32, iters=4)
    log(f"    -> {per/CHAIN*1e3:.2f} ms per mul @ {shape}")

    # -- f32 8-bit-limb prototype -----------------------------------------
    # 32 limbs x 8 bits; conv terms bounded by 32*255^2 < 2^21 (exact in
    # f32); wrap 2^256 = 38 mod p applied after an 8-bit carry pass.
    NL8 = 32

    def f32_carry(x):
        # one parallel carry pass: x -> digits in [0,256) + carries up
        c = jnp.floor(x * (1.0 / 256.0))
        lo = x - c * 256.0
        wrapped = jnp.concatenate([38.0 * c[NL8 - 1:], c[: NL8 - 1]], axis=0)
        return lo + wrapped

    def f32_mul(a, b):
        # schoolbook conv via shifted accumulation into 63 coefficients
        out = jnp.zeros((2 * NL8 - 1, *a.shape[1:]), dtype=jnp.float32)
        for i in range(NL8):
            out = out.at[i : i + NL8].add(a[i] * b)
        hi = out[NL8:]  # 31 coeffs, weight 2^(8(k+32)) = 38 * 2^(8k) mod p
        lo = out[:NL8]
        # hi < 2^21 but 38*hi > 2^24: split hi = 256*hc + h0 first so every
        # folded term stays exact in the f32 mantissa.
        hc = jnp.floor(hi * (1.0 / 256.0))
        h0 = hi - hc * 256.0
        x = lo
        x = x.at[: NL8 - 1].add(38.0 * h0)
        x = x.at[1:NL8].add(38.0 * hc)
        x = f32_carry(x)
        x = f32_carry(x)
        x = f32_carry(x)
        return x

    af = jnp.asarray(rng.integers(0, 256, (NL8, *shape)).astype(np.float32))
    bf = jnp.asarray(rng.integers(0, 256, (NL8, *shape)).astype(np.float32))

    @jax.jit
    def chain_mul_f32(a, b):
        x = a
        for _ in range(CHAIN):
            x = f32_mul(x, b)
        return x

    per = timeit("f32 8-bit-limb mul chained", chain_mul_f32, af, bf, iters=4)
    log(f"    -> {per/CHAIN*1e3:.2f} ms per mul @ {shape}")

    # correctness spot check of the f32 prototype
    def to_int_f32(limbs):
        arr = np.asarray(limbs, dtype=np.float64)
        return sum(int(round(arr[i].flat[0])) * (1 << (8 * i)) for i in range(NL8)) % fe.P

    xa = int.from_bytes(rng.bytes(31), "little")
    xb = int.from_bytes(rng.bytes(31), "little")
    la = jnp.asarray(np.array([(xa >> (8 * i)) & 0xFF for i in range(NL8)], dtype=np.float32)[:, None, None])
    lb = jnp.asarray(np.array([(xb >> (8 * i)) & 0xFF for i in range(NL8)], dtype=np.float32)[:, None, None])
    got = to_int_f32(f32_mul(la, lb))
    want = xa * xb % fe.P
    log(f"  f32 mul correctness: {'OK' if got == want else f'FAIL {got} != {want}'}")

    # -- int32 multiply vs add raw rate ------------------------------------
    @jax.jit
    def chain_raw_mul(a, b):
        x = a
        for _ in range(CHAIN * 4):
            x = (x * b) & 0x1FFF
        return x

    @jax.jit
    def chain_raw_fma_f32(a, b):
        x = a
        for _ in range(CHAIN * 4):
            x = x * b + a
        return x

    big_i = jnp.asarray(rng.integers(0, 1 << 13, (nl, *shape), dtype=np.int32))
    big_f = big_i.astype(jnp.float32)
    per_i = timeit("raw int32 mul+mask chain", chain_raw_mul, big_i, big_i, iters=4)
    per_f = timeit("raw f32 fma chain", chain_raw_fma_f32, big_f, big_f, iters=4)
    log(f"    -> int32 {per_i/(CHAIN*4)*1e3:.3f} ms/op vs f32 {per_f/(CHAIN*4)*1e3:.3f} ms/op")

    # -- scan vs unrolled tiny-tensor sequential chain ---------------------
    from tendermint_tpu.ops.msm_jax import SmallCtx, _pdbl, make_small_ctx
    from tendermint_tpu.ops.ed25519_jax import Point

    C = make_small_ctx()
    p0 = tuple(jnp.asarray(rng.integers(0, 1 << 13, (nl, 32), dtype=np.int32)) for _ in range(4))

    @jax.jit
    def dbl_scan(p):
        def body(st, _):
            return tuple(_pdbl(C, Point(*st))), None

        st, _ = jax.lax.scan(body, p, None, length=248)
        return st

    @jax.jit
    def dbl_unrolled(p):
        q = Point(*p)
        for _ in range(248):
            q = _pdbl(C, q)
        return tuple(q)

    timeit("248 doublings (20,32) via scan", dbl_scan, p0, iters=4)
    timeit("248 doublings (20,32) unrolled", dbl_unrolled, p0, iters=4)


if __name__ == "__main__":
    main()
