"""Stage-wise device profiling of the RLC/Pippenger MSM kernel on the TPU.

Times each pipeline stage of ops/msm_jax.py separately (decompress, lane
gather + pair-tree up-sweep, Fenwick node gather + prefix reduce, weighted
bucket sum, Horner window combine) plus the full cached kernel, with
device-resident inputs and multi-iteration async-dispatch timing (one sync
at the end) so the sync round trip is amortized out. Also dumps XLA's
cost_analysis for the full kernel to anchor a roofline estimate (PERF.md).

Stage compiles land in the compile cache (ops/aot_cache.configure_compile_cache).

Usage: python tools/profile_msm.py [NA] [ITERS]  (defaults 10240, 8)
"""

import json
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
from tendermint_tpu.ops import msm_jax as M
from tendermint_tpu.ops.ed25519_jax import Point, decompress, make_ctx


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def _sync(out):
    """Device sync by a D2H fetch of one element."""
    leaf = jax.tree_util.tree_leaves(out)[0]
    np.asarray(leaf)[(0,) * leaf.ndim]


def timeit(name, fn, *args, iters=8):
    """Compile+warm once, then slope-time: (t(iters) - t(1)) / (iters - 1)
    with a forced D2H sync per measurement — subtracts the sync constant.
    Prefer distinct-data pipelines (bench.py) for decisions."""
    t0 = time.perf_counter()
    out = fn(*args)
    _sync(out)
    compile_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    out = fn(*args)
    _sync(out)
    t_one = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    _sync(out)
    t_many = time.perf_counter() - t0
    per = max((t_many - t_one) / (iters - 1), 0.0)
    log(f"  {name:28s} {per*1e3:9.2f} ms/iter   (first call {compile_s:.1f}s)")
    return per, compile_s


def main():
    na = int(sys.argv[1]) if len(sys.argv) > 1 else 10240
    iters = int(sys.argv[2]) if len(sys.argv) > 2 else 8
    nr = na
    n = na + nr
    log(f"devices: {jax.devices()}  backend: {jax.default_backend()}")
    log(f"shape: NA={na} NR={nr} lanes={n} windows={M.NWIN}")

    rng = np.random.default_rng(0)
    # Scalars with realistic digit distributions (A lanes ~253-bit, R lanes
    # ~127-bit like real RLC coefficients) — the sort/Fenwick layout depends
    # on digit spread, the device work does not depend on values.
    scalars = [int.from_bytes(rng.bytes(32), "little") >> 3 for _ in range(na)] + [
        int.from_bytes(rng.bytes(16), "little") for _ in range(nr)
    ]
    digits = M.scalars_to_bytes(scalars, n)
    t0 = time.perf_counter()
    perm, ends = M.sort_windows(digits)
    log(f"host sort_windows: {(time.perf_counter()-t0)*1e3:.1f} ms")

    bx, by, bz, bt = M.basepoint_coords()
    a_coords = tuple(
        np.ascontiguousarray(np.broadcast_to(c[:, None], (fe.NLIMBS, na)))
        for c in (bx, by, bz, bt)
    )
    from tendermint_tpu.crypto.ed25519_ref import BASE, point_compress

    b_enc = np.frombuffer(point_compress(BASE), dtype=np.uint8)
    r_bytes_t = np.ascontiguousarray(np.tile(b_enc, (nr, 1)).T)

    dev = jax.devices()[0]
    put = lambda x: jax.device_put(x, dev)
    d_a = tuple(put(c) for c in a_coords)
    d_rb = put(r_bytes_t)
    d_perm = put(perm)
    d_ends = put(ends)
    d_nodes = put(np.asarray(M.fenwick_nodes_device(ends, n)))
    fctx = make_ctx((nr,))
    C = M.make_small_ctx()

    results = {}

    # --- full cached kernel (the production 10k path) ---------------------
    full = lambda *a: M._rlc_cached_jit(*a)
    per, comp = timeit(
        "full cached kernel", full, *d_a, d_rb, d_perm, d_ends, fctx, C, iters=iters
    )
    results["full_cached_ms"] = per * 1e3
    results["full_cached_compile_s"] = comp

    compiled = M._rlc_cached_jit.lower(*d_a, d_rb, d_perm, d_ends, fctx, C).compile()
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, list):
            ca = ca[0]
        results["cost_analysis"] = {
            k: v for k, v in ca.items()
            if k in ("flops", "bytes accessed", "transcendentals", "utilization")
            or "bytes accessed" in k
        }
        log(f"  cost_analysis: flops={ca.get('flops'):.3e} "
            f"bytes={ca.get('bytes accessed'):.3e}")
    except Exception as e:  # pragma: no cover
        log(f"  cost_analysis unavailable: {e}")
    try:
        mem = compiled.memory_analysis()
        results["temp_bytes"] = getattr(mem, "temp_size_in_bytes", None)
        log(f"  temp memory: {results['temp_bytes']/1e6:.0f} MB")
    except Exception:
        pass

    # --- stages -----------------------------------------------------------
    s0 = jax.jit(lambda rb, fc: decompress(fc, rb))
    per, comp = timeit("S0 decompress R", s0, d_rb, fctx, iters=iters)
    results["s0_decompress_ms"] = per * 1e3

    d_r_pts = tuple(s0(d_rb, fctx)[0])
    cat = jax.jit(
        lambda ac, rc: tuple(jnp.concatenate([a, b], -1) for a, b in zip(ac, rc))
    )
    d_pts = tuple(cat(d_a, d_r_pts))

    s1 = jax.jit(
        lambda pts, p: tuple(M._tree_levels(C, M._gather_lanes(Point(*pts), p)))
    )
    per, comp = timeit("S1 gather+tree up-sweep", s1, d_pts, d_perm, iters=iters)
    results["s1_tree_ms"] = per * 1e3

    d_tree = tuple(s1(d_pts, d_perm))
    s2 = jax.jit(
        lambda tr, ni: tuple(M._reduce_last_axis(C, M._gather_nodes(Point(*tr), ni)))
    )
    per, comp = timeit("S2 fenwick gather+reduce", s2, d_tree, d_nodes, iters=iters)
    results["s2_fenwick_ms"] = per * 1e3

    d_prefix = tuple(s2(d_tree, d_nodes))
    s3 = jax.jit(lambda pr: tuple(M._weighted_bucket_sum(C, Point(*pr))))
    per, comp = timeit("S3 weighted bucket sum", s3, d_prefix, iters=iters)
    results["s3_bucket_ms"] = per * 1e3

    d_wp = tuple(s3(d_prefix))
    s4 = jax.jit(lambda wp: tuple(M._combine_windows(C, Point(*wp))))
    per, comp = timeit("S4 horner combine", s4, d_wp, iters=iters)
    results["s4_horner_ms"] = per * 1e3

    # --- micro: field-mul throughput ceiling ------------------------------
    # One batched field multiply at tree width — an upper bound on how fast
    # point ops can go; ratio vs measured add cost shows codegen efficiency.
    big = jnp.asarray(rng.integers(0, 1 << 13, (fe.NLIMBS, 32, n), dtype=np.int32))
    fmul = jax.jit(lambda a, b: fe.mul(a, b))
    per, comp = timeit("micro fe.mul (32,N) lanes", fmul, big, big, iters=iters)
    results["fe_mul_32xN_ms"] = per * 1e3
    # one unified point add at the same width
    p_big = Point(big, big, big, big)
    padd = jax.jit(lambda p, q: tuple(M._padd(C, Point(*p), Point(*q))))
    per, comp = timeit("micro point add (32,N)", padd, tuple(p_big), tuple(p_big), iters=iters)
    results["padd_32xN_ms"] = per * 1e3

    stages = (
        results["s0_decompress_ms"] + results["s1_tree_ms"]
        + results["s2_fenwick_ms"] + results["s3_bucket_ms"]
        + results["s4_horner_ms"]
    )
    log(f"  stage sum {stages:.1f} ms vs full {results['full_cached_ms']:.1f} ms")
    print(json.dumps(results))


if __name__ == "__main__":
    main()
