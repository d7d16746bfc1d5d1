#!/usr/bin/env python3
"""PR 32: the tree stage alone, parent's against the change's, in ONE process on the chip.

    python tools/proof/pr32/uptree_micro.py [_parent]

The parent's stage is `packed_to_rows(uptree(rows_to_packed(g_rows)))` (its kernel between two XLA
changes of layout), the change's is `uptree(g_rows)`; both take the row gather's (T*N, 80) table and
give the tree as rows. Prints whether the two results are equal word for word, and the wall time of
one call of each (and of the parent's kernel alone, on a packed table) at both chunk geometries.
The parent's `pallas_msm.py` is loaded under another module name; what it imports is unchanged here.
"""
import importlib.util
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))
sys.path.insert(0, ROOT)

import jax
import jax.numpy as jnp
import numpy as np

from tendermint_tpu.ops import pallas_msm as new_pm

parent = os.path.join(ROOT, sys.argv[1] if len(sys.argv) > 1 else "_parent")
spec = importlib.util.spec_from_file_location(
    "parent_pallas_msm", os.path.join(parent, "tendermint_tpu/ops/pallas_msm.py")
)
old_pm = importlib.util.module_from_spec(spec)
spec.loader.exec_module(old_pm)


def timed(fn, x, reps=20):
    t0 = time.perf_counter()
    jax.block_until_ready(fn(x))
    first = time.perf_counter() - t0
    jax.block_until_ready(fn(x))
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(x)
    jax.block_until_ready(out)
    return first, (time.perf_counter() - t0) / reps * 1e3


GEOMETRIES = ((24576, 2048), (3072, 1024))  # lanes of the two large cells and of the small one
WINDOWS = 32


def main(require_tpu=True):
    dev = jax.devices()[0]
    print(json.dumps({"platform": dev.platform, "device_kind": dev.device_kind}))
    assert dev.platform == "tpu" or not require_tpu
    for lanes, ch in GEOMETRIES:
        m = WINDOWS * lanes
        rows = jax.random.randint(jax.random.PRNGKey(lanes), (m, 80), 0, 1 << 13, jnp.int32)
        old_stage = jax.jit(lambda r: old_pm.packed_to_rows(old_pm.uptree(old_pm.rows_to_packed(r), ch)))
        old_kernel = jax.jit(lambda p: old_pm.uptree(p, ch))
        new_stage = jax.jit(lambda r: new_pm.uptree(r, ch))
        rec = {"lanes": lanes, "ch": ch, "rows": m}
        rec["old_first_s"], rec["old_stage_ms"] = timed(old_stage, rows)
        rec["new_first_s"], rec["new_stage_ms"] = timed(new_stage, rows)
        packed = jax.block_until_ready(jax.jit(old_pm.rows_to_packed)(rows))
        _, rec["old_kernel_alone_ms"] = timed(old_kernel, packed)
        a, b = old_stage(rows), new_stage(rows)
        rec["equal"] = bool(jnp.array_equal(a, b))
        rec["shape"] = list(b.shape)
        rec["mismatches"] = int(jnp.sum(a != b))
        del a, b, packed
        print(json.dumps(rec), flush=True)


if __name__ == "__main__":
    main()
