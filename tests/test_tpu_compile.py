"""Compile-only tests for the DESCRIBED chip: the Pallas kernels of the main
path, at production geometry (the 10,000-validator flush: 24,576 lanes = 192
rows of 128), handed to the TPU compiler for a v5e:2x2 that is not attached.

Nothing runs, so these say nothing about results or times on the device;
they catch what interpret mode cannot (a Mosaic lowering the chip refuses, a
kernel over its VMEM) on every later PR at no chip time. The whole-program
compiles are minutes each: tools/compile_rehearsal.py, not a test.

The topology is described inside a module-scoped fixture of THIS file, never
at import: only one process may load libtpu, the driver's workers each
import every test file, and only the worker that is handed this file may
take the library. Keep every such test in this one file, and compile in the
test's own process.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from tendermint_tpu.ops import pallas_fe, pallas_msm

NL, LANE = pallas_fe.NL, pallas_fe.LANE


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no libtpu here, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def persistent_cache_off():
    """A compile for a described device is written to the persistent cache
    but cannot be read back without the chip (the next run would warn and
    recompile): keep the cache off around these compiles."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _point(rows):
    return (4, NL, rows, LANE)


# (kernel builder, its arguments, input shapes). `__wrapped__` steps past the
# builders' lru_cache, which bakes in `interpret` from whatever the process's
# TMTPU_PALLAS said at first use.
KERNELS = {
    "padd(192,16)": (pallas_fe._padd_call, (192, 16), [_point(192)] * 2),
    "pdbl(192,16,1)": (pallas_fe._pdbl_call, (192, 16, 1), [_point(192)]),
    "fsq(192,16,16)": (pallas_fe._fsq_call, (192, 16, 16), [(NL, 192, LANE)]),
    "fenwick(12,64,8)": (pallas_msm._fenwick_call, (12, 64, 8), [(12,) + _point(64)]),
    # rows in, rows out (32 windows x 24,576 lanes of the large cells' chunk
    # bucket; x 3,072 lanes of the small cell's flush, the other chunk geometry)
    "uptree(786432,2048)": (pallas_msm._uptree_call, (786432, 2048), [(786432, pallas_msm.NW)]),
    "uptree(98304,1024)": (pallas_msm._uptree_call, (98304, 1024), [(98304, pallas_msm.NW)]),
    "bucket(64,32)": (pallas_msm._bucket_call, (64, 32), [_point(64)]),
}


@pytest.mark.parametrize("name", list(KERNELS))
def test_kernel_compiles_for_v5e(name, one_chip, persistent_cache_off, monkeypatch):
    monkeypatch.delenv("TMTPU_PALLAS", raising=False)  # never interpret mode
    build, params, shapes = KERNELS[name]
    call = build.__wrapped__(*params)
    args = [jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip) for s in shapes]
    compiled = jax.jit(call).lower(*args).compile()  # raises what the chip would
    assert "tpu_custom_call" in compiled.as_text()
