"""Multi-chip sharded batch verification (the framework's scale-out axis).

Verification is embarrassingly parallel over the validator axis, so the
multi-chip design is: shard the trailing batch axis of every input tensor
across a `jax.sharding.Mesh`, run the single-device kernel per shard via
`shard_map`, and reduce cross-chip only for the O(1) aggregates (voting-power
tallies) with `psum` — which XLA lowers onto ICI.

Two mesh shapes are supported:
- 1D ("vals",): commit verification sharded across validators — replaces the
  reference's serial loop (reference: types/validator_set.go:680-702) at
  multi-chip scale.
- 2D ("blocks", "vals"): fast-sync historical replay sharded across blocks AND
  validators (reference: blockchain/v0/reactor.go VerifyCommitLight per block)
  — the batch axes of `verify_prepared` are arbitrary-rank, so a [32, NB, NV]
  tensor shards across both mesh axes with zero kernel changes.
"""

from __future__ import annotations

import time
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tendermint_tpu.libs import forensics as _forensics
from tendermint_tpu.ops import cache_hardening
from tendermint_tpu.ops.ed25519_jax import _verify_core, make_ctx, verify_prepared
from tendermint_tpu.parallel import health as _mesh_health
from tendermint_tpu.parallel import telemetry as _mesh_tm

# Round 4 bypassed the persistent compile cache for every sharded kernel
# (SIGSEGV on poisoned entries), which made each fresh dryrun/test process
# recompile for minutes. Root cause was jax's NON-ATOMIC cache entry write
# (truncated multi-hundred-MB entries after an OOM-kill mid-put); with
# atomic tmp+rename writes (ops/cache_hardening.py) the cache is safe to
# use again — warm sharded processes load their executables in seconds.
cache_hardening.harden()


class ShardFaultError(RuntimeError):
    """A failure of exactly ONE lane slice of a sharded dispatch, carrying
    its attribution: the shard index and the device string. Chaos injection
    (chaos/device.py) raises these from the shard-fault hook below; the
    health model (parallel/health.py) reads .device/.shard directly instead
    of probing the whole mesh."""

    def __init__(self, site: str, shard: int, device) -> None:
        super().__init__(f"shard fault at {site}: shard {shard} ({device})")
        self.site = site
        self.shard = int(shard)
        self.device = str(device)


_SHARD_FAULT_HOOK = None  # callable(site: str, devices: list[str]); may raise


def set_shard_fault_hook(fn) -> None:
    """Install (or clear, with None) the chaos shard-fault hook. It runs at
    every sharded submit site with the participating device strings, so a
    chaos schedule can kill exactly one lane slice mid-flush."""
    global _SHARD_FAULT_HOOK
    _SHARD_FAULT_HOOK = fn


def _shard_fault(site: str, devices) -> None:
    hook = _SHARD_FAULT_HOOK
    if hook is not None:
        hook(site, devices)


def _guarded(site: str, devices, fn, *args):
    """Run one sharded dispatch under the elastic-mesh contract: the chaos
    shard hook fires first (so an injected fault lands on exactly this
    dispatch), any raise is scored against the per-device health model
    (stamped ``_mesh_scored`` so callers further up never double-score),
    and a clean return clears the participants' failure streaks — with the
    call's wall feeding stall scoring."""
    t0 = time.perf_counter()
    try:
        _shard_fault(site, devices)
        out = fn(*args)
    except Exception as e:
        if not getattr(e, "_mesh_scored", False):
            _mesh_health.MESH_HEALTH.record_failure(devices, e)
            try:
                e._mesh_scored = True
            except Exception:
                pass
        raise
    _mesh_health.MESH_HEALTH.record_success(devices, time.perf_counter() - t0)
    return out


def make_mesh(devices=None, shape=None, axis_names=("vals",)) -> Mesh:
    """Build a device mesh. Default: all devices on one 'vals' axis.
    Also the mesh-telemetry anchor: every mesh built here lands in the
    `mesh` block of /debug/mesh (parallel/telemetry.py)."""
    import numpy as np

    devices = devices if devices is not None else jax.devices()
    arr = np.asarray(devices)
    if shape is not None:
        arr = arr.reshape(shape)
    mesh = Mesh(arr, axis_names)
    flat = list(arr.reshape(-1))
    _mesh_tm.record_mesh(
        axis_names, arr.shape, flat, getattr(flat[0], "platform", "unknown")
    )
    return mesh


def _aligned(mesh: Mesh, batch_rank: int):
    """Right-align mesh axes onto the trailing batch axes.

    Returns (leading_none_count, batch_spec_axes): a batch of rank R >= M
    (mesh rank) maps its LAST M axes onto the mesh axes and leaves the
    leading R-M axes unsharded — matching the documented semantics (the
    previous zip() left-aligned and silently truncated; advisor r2 finding).
    """
    mesh_rank = len(mesh.axis_names)
    if batch_rank < mesh_rank:
        raise ValueError(
            f"batch rank {batch_rank} < mesh rank {mesh_rank}: "
            "every mesh axis needs a batch axis to shard"
        )
    lead = batch_rank - mesh_rank
    return (None,) * lead + tuple(mesh.axis_names)


def _shard_batch_shape(mesh: Mesh, batch_shape) -> tuple:
    """Per-device shard of a right-aligned batch shape."""
    spec = _aligned(mesh, len(batch_shape))
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    return tuple(
        d // sizes[ax] if ax is not None else d for d, ax in zip(batch_shape, spec)
    )


def sharded_verify(mesh: Mesh):
    """jit'd verify_prepared with the batch axis sharded across the mesh.

    Inputs [32,B]/[253,B] (or [..., NB, NV] for 2D meshes); batch axes map to
    mesh axes right-aligned: the last input axis onto the last mesh axis, etc.
    (extra leading batch axes stay unsharded). Returns the bool mask with the
    same sharded layout.
    """
    # ctx is replicated: every chip gets the same materialized constants
    # sized for ITS shard, so the fast (real-buffer) path runs per shard.
    spec_ctx = jax.tree.map(lambda _: P(), make_ctx(()))
    _cache: dict = {}

    def _for_rank(batch_rank: int):
        fn = _cache.get(batch_rank)
        if fn is None:
            batch_axes = _aligned(mesh, batch_rank)
            spec_in = P(None, *batch_axes)
            spec_out = P(*batch_axes)

            @partial(
                jax.shard_map,
                mesh=mesh,
                in_specs=(spec_in, spec_in, spec_in, spec_in, spec_ctx),
                out_specs=spec_out,
                check_vma=False,
            )
            def _verify(a, r, s_bits, h_bits, ctx):
                return _verify_core(a, r, s_bits, h_bits, ctx)

            fn = _cache[batch_rank] = jax.jit(_verify)
        return fn

    devices = [str(d) for d in mesh.devices.flat]

    def run(a, r, s_bits, h_bits):
        import numpy as np

        shard_batch = _shard_batch_shape(mesh, a.shape[1:])
        rank = len(a.shape) - 1
        lanes = int(np.prod(shard_batch)) if shard_batch else 1
        # split submit (dispatch) from finish (sync) so a wedged mesh names
        # its phase: the heartbeat (libs/forensics.py) is readable from
        # outside even while this thread hangs in the device call
        _forensics.beat("mesh_persig_submit")
        t0 = time.perf_counter()
        out = _guarded(
            "mesh_persig_submit",
            devices,
            _for_rank(rank),
            a, r, s_bits, h_bits, make_ctx(shard_batch),
        )
        t1 = time.perf_counter()
        _forensics.beat("mesh_persig_finish")
        out = np.asarray(out)
        _mesh_tm.record_flush(
            "persig",
            ndev=int(mesh.devices.size),
            shard_lanes=lanes,
            submit_s=t1 - t0,
            finish_s=time.perf_counter() - t1,
            devices=devices,
        )
        return out

    return run


def sharded_commit_step(mesh: Mesh):
    """The full 'training step' analog: batched commit verification.

    Per-shard signature verification + cross-chip psum of the voting power
    carried by valid signatures; accepts iff valid power > 2/3 of total
    (reference: types/validator_set.go:662 VerifyCommit tally semantics).
    Returns (mask, ok) with mask sharded and ok replicated.
    """
    spec_ctx = jax.tree.map(lambda _: P(), make_ctx(()))
    _cache: dict = {}

    def _for_rank(batch_rank: int):
        fn = _cache.get(batch_rank)
        if fn is None:
            batch_axes = _aligned(mesh, batch_rank)
            spec_in = P(None, *batch_axes)
            spec_p = P(*batch_axes)

            @partial(
                jax.shard_map,
                mesh=mesh,
                in_specs=(spec_in, spec_in, spec_in, spec_in, spec_in, spec_ctx),
                out_specs=(spec_p, P(), P()),
                check_vma=False,
            )
            def _step(a, r, s_bits, h_bits, power_planes, ctx):
                mask = _verify_core(a, r, s_bits, h_bits, ctx)
                # Exact int64 tallies without x64: powers arrive as four
                # uint32 planes of 16 bits each (see split_powers). Each
                # plane sum is bounded by N*2^16, safe in uint32 for N up to
                # 2^15 validators per shard; psum across the mesh and
                # recombine host-side in Python ints (reference tally
                # semantics: types/validator_set.go:662 uses int64 power).
                valid_planes = jnp.where(mask[None], power_planes, 0)
                talled = jnp.sum(valid_planes, axis=tuple(range(1, valid_planes.ndim)))
                total = jnp.sum(power_planes, axis=tuple(range(1, power_planes.ndim)))
                for ax in mesh.axis_names:
                    talled = jax.lax.psum(talled, ax)
                    total = jax.lax.psum(total, ax)
                return mask, talled, total

            fn = _cache[batch_rank] = jax.jit(_step)
        return fn

    devices = [str(d) for d in mesh.devices.flat]

    def step(a, r, s_bits, h_bits, power_planes):
        import numpy as np

        shard_batch = _shard_batch_shape(mesh, a.shape[1:])
        rank = len(a.shape) - 1
        lanes = int(np.prod(shard_batch)) if shard_batch else 1
        _forensics.beat("mesh_commit_submit")
        t0 = time.perf_counter()
        mask, talled, total = _guarded(
            "mesh_commit_submit",
            devices,
            _for_rank(rank),
            a, r, s_bits, h_bits, power_planes, make_ctx(shard_batch),
        )
        t1 = time.perf_counter()

        def _join(planes) -> int:
            return sum(int(v) << (16 * k) for k, v in enumerate(np.asarray(planes)))

        _forensics.beat("mesh_commit_finish")
        ok = _join(talled) * 3 > _join(total) * 2
        _mesh_tm.record_flush(
            "commit_step",
            ndev=int(mesh.devices.size),
            shard_lanes=lanes,
            submit_s=t1 - t0,
            finish_s=time.perf_counter() - t1,
            devices=devices,
            ok=bool(ok),
        )
        return mask, ok

    return step


def sharded_rlc_check(mesh: Mesh):
    """The RLC/Pippenger fast path sharded across the mesh — the flagship
    kernel's scale-out story (validator-axis hot loop at pod scale,
    reference role: types/validator_set.go:680-702).

    Decomposition: the MSM is a sum over lanes, so each device runs the
    FULL Pippenger pipeline (sort-free: its host-prepped perm/fenwick
    indices cover only its lane shard) over 1/D of the lanes, producing one
    partial point; the D partial points (4x20 ints each — tiny) are
    all-gathered over ICI and tree-added on every chip; the identity check
    is replicated. Per-lane decompress-validity flags stay sharded. One
    all_gather of ~320 bytes is the ONLY cross-chip traffic.

    Returns run(pts_bytes[D,32,n], perm[D,T,n], ends[D,T,256]) ->
    (batch_ok bool replicated, lane_ok [D*n] flattened).
    """
    from tendermint_tpu.ops.ed25519_jax import decompress, identity
    from tendermint_tpu.ops.msm_jax import (
        _msm_total,
        _msm_total_fused,
        _padd,
        _pselect,
        fused_for_lanes,
        make_small_ctx,
        point_is_identity,
    )

    if len(mesh.axis_names) != 1:
        raise ValueError("sharded_rlc_check expects a 1D mesh")
    axis = mesh.axis_names[0]
    ndev = int(mesh.devices.size)
    spec_ctx_small = jax.tree.map(lambda _: P(), make_small_ctx())
    _cache: dict = {}

    def _for_lanes(n: int):
        # Each shard runs the FUSED VMEM-resident stage pipeline when its
        # lane count tiles a chunk (ops/pallas_msm.py) — the same schedule
        # the single-chip path runs, so multi-chip inherits every fused win.
        # Keyed on the routing decision too: a runtime disable_fused() must
        # not keep hitting a cached fused program.
        fused = fused_for_lanes(n)
        fn = _cache.get((n, fused))
        if fn is None:
            fctx = make_ctx((n,))
            spec_fctx = jax.tree.map(lambda _: P(), fctx)

            @partial(
                jax.shard_map,
                mesh=mesh,
                in_specs=(P(axis), P(axis), P(axis), spec_fctx, spec_ctx_small),
                out_specs=(P(), P(axis)),
                check_vma=False,
            )
            def _run(pts_bytes, perm, ends, fctx, C):
                from tendermint_tpu.ops.msm_jax import fenwick_nodes_device

                pts_bytes = pts_bytes[0]  # (32, n) local shard
                perm = perm[0]
                p, ok = decompress(fctx, pts_bytes)
                p = _pselect(ok, p, identity(fctx))
                if fused:
                    part = _msm_total_fused(C, p, perm, ends[0])
                else:
                    node_idx = fenwick_nodes_device(ends[0], n)
                    part = _msm_total(C, p, perm, node_idx)  # partial (20,)
                coords = jnp.stack(part)  # (4, 20)
                allc = jax.lax.all_gather(coords, axis)  # (D, 4, 20)
                from tendermint_tpu.ops.ed25519_jax import Point

                acc = Point(allc[0, 0], allc[0, 1], allc[0, 2], allc[0, 3])
                for d in range(1, ndev):
                    acc = _padd(
                        C, acc, Point(allc[d, 0], allc[d, 1], allc[d, 2], allc[d, 3])
                    )
                bok = point_is_identity(C, acc)
                return bok, ok[None]

            fn = _cache[(n, fused)] = jax.jit(
                lambda pb, pm, ni: _run(pb, pm, ni, make_ctx((n,)), make_small_ctx())
            )
        return fn

    devices = [str(d) for d in mesh.devices.flat]

    def run(pts_bytes, perm, ends):
        import numpy as np

        if pts_bytes.shape[0] != ndev:
            raise ValueError(f"leading axis {pts_bytes.shape[0]} != mesh size {ndev}")
        n_sh = pts_bytes.shape[2]
        _forensics.beat("mesh_rlc_submit")
        t0 = time.perf_counter()
        bok, ok = _guarded(
            "mesh_rlc_submit", devices, _for_lanes(n_sh), pts_bytes, perm, ends
        )
        t1 = time.perf_counter()
        _forensics.beat("mesh_rlc_finish")
        # where the lane-flag shards really live, not where the mesh was
        # built: a placement that collapsed onto one device shows here
        held = [str(s.device) for s in ok.addressable_shards]
        bok = np.asarray(bok)
        ok = np.asarray(ok)
        _mesh_tm.record_flush(
            "rlc",
            ndev=ndev,
            shard_lanes=n_sh,
            submit_s=t1 - t0,
            finish_s=time.perf_counter() - t1,
            # ONE all_gather of the (4, 20) int32 partial point per device
            all_gather_bytes=ndev * 4 * 20 * 4,
            devices=held,
            ok=bool(bok),
        )
        return bok, ok.reshape(-1)

    return run


def sharded_rlc_stream(mesh: Mesh):
    """Streamed-planner arm of sharded_rlc_check (crypto/batch.py ISSUE 13):
    an over-budget flush streams fixed-bucket chunks ACROSS the mesh. Per
    chunk, each device runs the full Pippenger pipeline over its lane shard
    and folds the partial point into a device-resident per-shard
    accumulator; after the LAST chunk, one all_gather + tree add + identity
    check delivers the combined verdict — cross-chip traffic stays ONE
    ~320-byte all_gather per flush, not per chunk, and per-chip memory stays
    constant at the chunk shard regardless of workload size.

    Returns (run_chunk, finish):
      run_chunk(pts (D, 32, n), perm (D, T, n), ends (D, T, 256), acc)
          -> (acc' (D, 4, 20) sharded device array, ok (D, n) unsynced)
        acc is None for the first chunk;
      finish(acc) -> batch_ok (unsynced device bool).
    """
    from tendermint_tpu.ops.ed25519_jax import Point, decompress, identity
    from tendermint_tpu.ops.msm_jax import (
        _msm_total,
        _msm_total_fused,
        _padd,
        _pselect,
        fused_for_lanes,
        make_small_ctx,
        point_is_identity,
    )
    from tendermint_tpu.ops.msm_jax import fenwick_nodes_device

    if len(mesh.axis_names) != 1:
        raise ValueError("sharded_rlc_stream expects a 1D mesh")
    axis = mesh.axis_names[0]
    ndev = int(mesh.devices.size)
    spec_ctx_small = jax.tree.map(lambda _: P(), make_small_ctx())
    _cache: dict = {}

    def _chunk_fn(n: int, with_acc: bool):
        fused = fused_for_lanes(n)
        key = (n, fused, with_acc)
        fn = _cache.get(key)
        if fn is not None:
            return fn
        fctx = make_ctx((n,))
        spec_fctx = jax.tree.map(lambda _: P(), fctx)
        in_specs = [P(axis), P(axis), P(axis)]
        if with_acc:
            in_specs.append(P(axis))
        in_specs += [spec_fctx, spec_ctx_small]

        @partial(
            jax.shard_map,
            mesh=mesh,
            in_specs=tuple(in_specs),
            out_specs=(P(axis), P(axis)),
            check_vma=False,
        )
        def _run(pts_bytes, perm, ends, *rest):
            if with_acc:
                acc, fctx_, C = rest
            else:
                fctx_, C = rest
            pts_local = pts_bytes[0]  # (32, n) local shard
            p, ok = decompress(fctx_, pts_local)
            p = _pselect(ok, p, identity(fctx_))
            if fused:
                part = _msm_total_fused(C, p, perm[0], ends[0])
            else:
                node_idx = fenwick_nodes_device(ends[0], n)
                part = _msm_total(C, p, perm[0], node_idx)
            coords = jnp.stack(part)  # (4, 20)
            if with_acc:
                a = acc[0]
                coords = jnp.stack(
                    _padd(
                        C,
                        Point(a[0], a[1], a[2], a[3]),
                        Point(coords[0], coords[1], coords[2], coords[3]),
                    )
                )
            return coords[None], ok[None]

        if with_acc:
            fn = jax.jit(
                lambda pb, pm, nd_, ac: _run(
                    pb, pm, nd_, ac, make_ctx((n,)), make_small_ctx()
                )
            )
        else:
            fn = jax.jit(
                lambda pb, pm, nd_: _run(pb, pm, nd_, make_ctx((n,)), make_small_ctx())
            )
        _cache[key] = fn
        return fn

    def _finish_fn():
        fn = _cache.get("finish")
        if fn is not None:
            return fn

        @partial(
            jax.shard_map,
            mesh=mesh,
            in_specs=(P(axis), spec_ctx_small),
            out_specs=P(),
            check_vma=False,
        )
        def _fin(acc, C):
            allc = jax.lax.all_gather(acc[0], axis)  # (D, 4, 20)
            total = Point(allc[0, 0], allc[0, 1], allc[0, 2], allc[0, 3])
            for d in range(1, ndev):
                total = _padd(
                    C,
                    total,
                    Point(allc[d, 0], allc[d, 1], allc[d, 2], allc[d, 3]),
                )
            return point_is_identity(C, total)

        fn = _cache["finish"] = jax.jit(lambda ac: _fin(ac, make_small_ctx()))
        return fn

    devices = [str(d) for d in mesh.devices.flat]

    def run_chunk(pts_bytes, perm, ends, acc):
        if pts_bytes.shape[0] != ndev:
            raise ValueError(
                f"leading axis {pts_bytes.shape[0]} != mesh size {ndev}"
            )
        n_sh = pts_bytes.shape[2]
        _forensics.beat("mesh_rlc_stream_submit")
        t0 = time.perf_counter()
        if acc is None:
            acc, ok = _guarded(
                "mesh_rlc_stream_submit",
                devices,
                _chunk_fn(n_sh, False),
                pts_bytes, perm, ends,
            )
        else:
            acc, ok = _guarded(
                "mesh_rlc_stream_submit",
                devices,
                _chunk_fn(n_sh, True),
                pts_bytes, perm, ends, acc,
            )
        _mesh_tm.record_flush(
            "rlc_stream_chunk",
            ndev=ndev,
            shard_lanes=n_sh,
            submit_s=time.perf_counter() - t0,
            finish_s=0.0,
            devices=devices,
        )
        return acc, ok

    def finish(acc):
        _forensics.beat("mesh_rlc_stream_finish")
        t0 = time.perf_counter()
        bok = _guarded("mesh_rlc_stream_finish", devices, _finish_fn(), acc)
        _mesh_tm.record_flush(
            "rlc_stream_finish",
            ndev=ndev,
            shard_lanes=0,
            submit_s=time.perf_counter() - t0,
            finish_s=0.0,
            # the flush's ONE all_gather: (4, 20) int32 per device
            all_gather_bytes=ndev * 4 * 20 * 4,
            devices=devices,
        )
        return bok

    return run_chunk, finish


def prepare_rlc_shards(pts_bytes, scalars, ndev: int):
    """Host prep for sharded_rlc_check: split lanes into ndev contiguous
    chunks, per-chunk window sort + bucket boundaries (ops/msm_jax.py
    sort_windows; fenwick indices derive on-device). pts_bytes (N, 32)
    uint8, N divisible by ndev."""
    import numpy as np

    from tendermint_tpu.ops.msm_jax import scalars_to_bytes, sort_windows

    n = pts_bytes.shape[0]
    if n % ndev:
        raise ValueError(f"lanes {n} not divisible by mesh size {ndev}")
    per = n // ndev
    t0 = time.perf_counter()
    digits = scalars_to_bytes(scalars, n)
    pts, perms, nodes = [], [], []
    for d in range(ndev):
        sl = slice(d * per, (d + 1) * per)
        perm, ends = sort_windows(digits[sl])
        pts.append(np.ascontiguousarray(pts_bytes[sl].T))
        perms.append(perm)
        nodes.append(ends)
    out = np.stack(pts), np.stack(perms), np.stack(nodes)
    _mesh_tm.record_prepare(ndev, per, time.perf_counter() - t0)
    return out


def split_powers(powers) -> "jnp.ndarray":
    """int64-range voting powers -> uint32[4, ...batch] planes of 16 bits
    each (exact for powers < 2^64; reference powers are int64)."""
    import numpy as np

    p = np.asarray(powers, dtype=np.uint64)
    planes = np.stack([(p >> np.uint64(16 * k)) & np.uint64(0xFFFF) for k in range(4)])
    return planes.astype(np.uint32)


def shard_batch_arrays(mesh: Mesh, *arrays):
    """Device-put host arrays with the trailing axes sharded over the mesh
    (right-aligned; each array keeps one leading non-batch axis unsharded)."""
    out = []
    for a in arrays:
        spec = P(None, *_aligned(mesh, a.ndim - 1))
        out.append(jax.device_put(a, NamedSharding(mesh, spec)))
    return tuple(out)


def aggregate_bitmap_sharded(coords, bitmap, n_shards: int | None = None):
    """Sharded BLS aggregate-pubkey fold (ISSUE 14): partition the signer
    coordinate list across shards, run the bitmap MSM fold per shard
    (ops/bls12_msm.g1_aggregate_bitmap — the same kernel schedule a mesh
    device would run per shard via shard_map), and combine the per-shard
    partial sums with ONE final O(n_shards) reduction — the exact shape of
    sharded_rlc_check: per-shard accumulation, one cross-shard combine.

    coords: [(x, y)] affine G1 ints; bitmap: per-index booleans. Returns
    affine (x, y) ints or None (empty selection). Host-combining via
    bls_ref keeps this correct on any backend; on a real mesh each shard's
    fold dispatches to its device and the combine stays O(devices)."""
    from tendermint_tpu.crypto import bls_ref
    from tendermint_tpu.ops import bls12_msm

    n = len(coords)
    if n != len(bitmap):
        raise ValueError("coords/bitmap length mismatch")
    if n_shards is None:
        try:
            n_shards = max(1, len(jax.devices()))
        except Exception:  # pragma: no cover - jax init failure
            n_shards = 1
    n_shards = max(1, min(n_shards, n or 1))
    per = (n + n_shards - 1) // n_shards
    acc = bls_ref.G1_IDENTITY
    for s in range(n_shards):
        sl = slice(s * per, min((s + 1) * per, n))
        if sl.start >= n:
            break
        part = bls12_msm.g1_aggregate_bitmap(coords[sl], bitmap[sl])
        if part is None:
            continue
        acc = bls_ref._jac_add(
            acc,
            (bls_ref._G1Field(part[0]), bls_ref._G1Field(part[1]), bls_ref._G1Field(1)),
        )
    aff = bls_ref._jac_to_affine(acc)
    return None if aff is None else (aff[0].v, aff[1].v)
