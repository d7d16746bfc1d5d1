"""Compile rehearsal: ask the TPU compiler for the programs chip_smoke.py's
path runs, at their real shapes, for a DESCRIBED v5e:2x2 — no chip attached.

A compile that passes here is not a chip run: nothing executes, so it says
nothing about results or times on the device. It shows what the chip's
compiler would refuse (Mosaic lowering, VMEM, HBM fit) and what one cold
program costs in trace+export and XLA-compile seconds on THIS host.

Each program goes the way ops/aot_cache.call sends it on the chip:
jax.export for platform "tpu", serialize, then compile jit(exp.call)
(export_s includes the lowering of the deserialized artifact's call).
`pallas_fe.enabled` and `msm_jax._scan_structures` ask
jax.default_backend() and would see the CPU here, so this script steers
them (the program gets no option for it).

Usage: JAX_PLATFORMS=cpu python tools/compile_rehearsal.py [name ...]
  names: smoke (default: every program chip_smoke.py's one-chip path runs),
         async (the submit/finish pair's whole-flush programs at 10k rows),
         sharded (the --chips 4 route's programs over a 4-device mesh),
         or single program names as printed.
One JSON line per program on stdout.
"""

import json
import os
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("TPU_LOG_DIR", "disabled")

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

import jax
import numpy as np
from jax import export as jexport
from jax.experimental import topologies
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

# compiles for a described device are written to the persistent cache but
# cannot be read back without the chip: keep the cache off here
jax.config.update("jax_enable_compilation_cache", False)

from tendermint_tpu.ops import aot_cache, msm_jax, pallas_fe
from tendermint_tpu.ops import ed25519_jax

pallas_fe.enabled = lambda: True  # the chip's branch, not this host's
msm_jax._scan_structures = lambda: False


def _abstract(tree, sharding):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(np.shape(x), x.dtype, sharding=sharding),
        tree,
    )


def _rlc_args(n):
    """(pts_bytes (32, N) u8, perm (32, N) u16, ends (32, 256) i32, fctx, C)
    as rlc_check_submit / rlc_partial_submit hand them to _dispatch."""
    return (
        np.zeros((32, n), np.uint8),
        np.zeros((msm_jax.NWIN, n), np.uint16),
        np.zeros((msm_jax.NWIN, msm_jax.NBUCKETS), np.int32),
        ed25519_jax.make_ctx((n,)),
        msm_jax.make_small_ctx(),
    )


def _cached_args(na, nr):
    n = na + nr
    a = tuple(np.zeros((20, na), np.int32) for _ in range(4))
    return a + (
        np.zeros((32, nr), np.uint8),
        np.zeros((msm_jax.NWIN, n), np.uint16),
        np.zeros((msm_jax.NWIN, msm_jax.NBUCKETS), np.int32),
        ed25519_jax.make_ctx((nr,)),
        msm_jax.make_small_ctx(),
    )


def _persig_args(b):
    from tendermint_tpu.crypto.batch import _signed_radix16

    z = np.zeros((b, 32), np.uint8)
    return (
        np.zeros((32, b), np.uint8),
        np.zeros((32, b), np.uint8),
        _signed_radix16(z),
        _signed_radix16(z),
        ed25519_jax.make_ctx((b,)),
    )


def _point():
    return np.zeros((4, 20), np.int32)


# name -> (jit_fn, args builder). Shapes: the 10,000-row valid flush is one
# chunk on the planner's one chunk bucket (12,288 rows = 24,576 lanes;
# `partial_fold` joins two chunks, so only a streamed flush over 12,287 rows
# runs it, and no group lists it); step 4's 640-row set lands on lane bucket
# 1,024 (2,048 lanes), its pubkey decode and per-signature leaf on 1,024 rows.
PROGRAMS = {
    "rlc_partial_f@24576": (msm_jax._rlc_partial_jit_fused, lambda: _rlc_args(24576)),
    "partial_fold": (
        msm_jax._partial_fold_jit,
        lambda: (_point(), _point(), msm_jax.make_small_ctx()),
    ),
    "partial_ident": (
        msm_jax._partial_identity_jit,
        lambda: (_point(), msm_jax.make_small_ctx()),
    ),
    "rlc_plain_f@2048": (msm_jax._rlc_jit_fused, lambda: _rlc_args(2048)),
    "rlc_cached_f@1024+1024": (
        msm_jax._rlc_cached_jit_fused,
        lambda: _cached_args(1024, 1024),
    ),
    "decompress@1024": (
        msm_jax._decompress_jit,
        lambda: (np.zeros((32, 1024), np.uint8), ed25519_jax.make_ctx((1024,))),
    ),
    "persig@1024": (ed25519_jax._verify_jit, lambda: _persig_args(1024)),
    # verify_commit_light outside an accumulate_flushes scope (rlc-async)
    "rlc_plain_f@20480": (msm_jax._rlc_jit_fused, lambda: _rlc_args(20480)),
    "rlc_cached_f@10240+10240": (
        msm_jax._rlc_cached_jit_fused,
        lambda: _cached_args(10240, 10240),
    ),
    "decompress@16384": (
        msm_jax._decompress_jit,
        lambda: (np.zeros((32, 16384), np.uint8), ed25519_jax.make_ctx((16384,))),
    ),
}
GROUPS = {
    "smoke": [
        "rlc_partial_f@24576", "partial_ident",
        "rlc_plain_f@2048", "rlc_cached_f@1024+1024", "decompress@1024",
        "persig@1024",
    ],
    "async": ["rlc_plain_f@20480", "rlc_cached_f@10240+10240", "decompress@16384"],
}


def _report(name, t_export, t_compile, compiled, n_bytes, **extra):
    mem = compiled.memory_analysis()
    print(
        json.dumps(
            {
                "program": name,
                "export_s": round(t_export, 1),
                "compile_s": round(t_compile, 1),
                "artifact_bytes": n_bytes,
                "tpu_custom_calls": compiled.as_text().count("tpu_custom_call"),
                "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
                "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
                **extra,
                "note": "compile for a described v5e:2x2, not a chip run",
            }
        ),
        flush=True,
    )


def rehearse(name, sharding):
    jit_fn, build = PROGRAMS[name]
    args = _abstract(build(), sharding)
    t0 = time.perf_counter()
    if jit_fn is msm_jax._decompress_jit:
        # msm_jax.decompress_rows calls its jit directly, not through the
        # AOT cache: plain lower + compile, as on the chip
        lowered, n_bytes = jit_fn.lower(*args), 0
    else:
        aot_cache._register_pytrees()
        exp = jexport.export(jit_fn, platforms=("tpu",))(*args)
        n_bytes = len(exp.serialize())
        lowered = jax.jit(exp.call).lower(*args)
    t1 = time.perf_counter()
    compiled = lowered.compile()
    _report(name, t1 - t0, time.perf_counter() - t1, compiled, n_bytes)


def _closure_var(fn, name):
    """A free variable of a closure: parallel/sharded.py keeps its jitted
    per-shape programs inside the runner closures."""
    return dict(
        zip(fn.__code__.co_freevars, (c.cell_contents for c in fn.__closure__))
    )[name]


def _timed_compile(name, jit_fn, args, count=None):
    """Plain lower + compile (the sharded programs skip the AOT cache);
    `count` names an HLO op whose occurrences the report should carry."""
    t0 = time.perf_counter()
    lowered = jit_fn.lower(*args)
    t1 = time.perf_counter()
    compiled = lowered.compile()
    extra = {count: compiled.as_text().count(f"{count}(")} if count else {}
    _report(name, t1 - t0, time.perf_counter() - t1, compiled, 0, **extra)


def rehearse_sharded(topo):
    """The --chips 4 route over a Mesh of the four described devices: the
    sharded RLC program at the two per-shard lane counts chip_smoke.py's
    four-chip phase meets (10,000 and 8,192 rows pad to 20,480 lanes = 5,120
    a shard, fused; the bisection's 1,024-row sub-range to 3,072 lanes = 768
    a shard, unfused) and the sharded per-signature leaf at 1,024 rows."""
    from tendermint_tpu.parallel import sharded

    devs = list(topo.devices)
    nd = len(devs)
    mesh = Mesh(np.asarray(devs), ("vals",))
    lanes = NamedSharding(mesh, P("vals"))
    for n_sh in (5120, 768):
        fn = _closure_var(sharded.sharded_rlc_check(mesh), "_for_lanes")(n_sh)
        args = (
            jax.ShapeDtypeStruct((nd, 32, n_sh), np.uint8, sharding=lanes),
            jax.ShapeDtypeStruct((nd, msm_jax.NWIN, n_sh), np.uint16, sharding=lanes),
            jax.ShapeDtypeStruct(
                (nd, msm_jax.NWIN, msm_jax.NBUCKETS), np.int32, sharding=lanes
            ),
        )
        _timed_compile(f"rlc_sharded@{nd}x{n_sh}", fn, args, count="all-gather")
    b = 1024
    cols = NamedSharding(mesh, P(None, "vals"))
    rep = NamedSharding(mesh, P())
    a, r, s_d, h_d, _ = _persig_args(b)
    fn = _closure_var(sharded.sharded_verify(mesh), "_for_rank")(1)
    args = _abstract((a, r, s_d, h_d), cols) + (
        _abstract(ed25519_jax.make_ctx((b // nd,)), rep),
    )
    _timed_compile(f"persig_sharded@{nd}x{b // nd}", fn, args)


def main(argv):
    names = argv or ["smoke"]
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one_chip = SingleDeviceSharding(topo.devices[0])
    for name in names:
        if name == "sharded":
            rehearse_sharded(topo)
            continue
        for prog in GROUPS.get(name, [name]):
            rehearse(prog, one_chip)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
