#!/usr/bin/env python3
"""On the chip: where does a checkout's set-up of a cell spend its lowering? Two warm-up calls through the
entry driver, in the checkout given, with JAX naming every function it traces, lowers and compiles:
    python tools/proof/pr31/lowering.py _parent commit-1024.verify-commit"""
import logging, os, sys, time
T0 = time.perf_counter()
root = os.path.abspath(sys.argv[1])
os.chdir(root)
sys.path[:0] = [root, os.path.join(root, "benchmark")]
import jax
jax.config.update("jax_log_compiles", True)
logging.basicConfig(level=logging.WARNING, stream=sys.stdout, format="%(message).300s")
import data, spec
from tendermint_tpu.ops.aot_cache import configure_compile_cache
print("checkout", root, "cache", configure_compile_cache(), flush=True)
seen = []
jax.monitoring.register_event_duration_secs_listener(
    lambda event, secs, **kw: secs > 0.3 and seen.append((round(time.perf_counter() - T0, 1), event.rsplit("/", 1)[-1], round(secs, 2), kw)))
cell = spec.Cell(spec.load_benchmark(root), sys.argv[2])
seed = 2147490501
vals = data.make_validators(seed, cell.config)
ring = data.make_ring(seed, cell.config, dict(cell.traffic, ring_commits=2), vals)
entry = cell.entry()
entry.configure(cell.traffic)
state = entry.build(cell.config, vals, ring)
for i in range(3):
    t = time.perf_counter()
    print("call", i, entry.call(state, i % 2), round(time.perf_counter() - t, 2), "s", flush=True)
for s in seen:
    print("EVENT", s)
print("total", round(time.perf_counter() - T0, 1), "s")
if hasattr(state, "scheduler"):
    state.scheduler.close()
