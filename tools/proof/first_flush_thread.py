#!/usr/bin/env python3
"""Does it matter on which thread the first flush of a process runs?
    python tools/proof/first_flush_thread.py main|thread
One 10,624-row verify_batch (the cell's own shapes), first on the main thread
or on a worker thread, with every JAX monitoring event and AOT event printed."""
import collections, json, os, sys, threading, time
T0 = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "benchmark")]
import jax, jax.monitoring, logging
logging.basicConfig(level=logging.WARNING, stream=sys.stderr)
for n in ("jax._src.compiler", "jax._src.compilation_cache", "jax._src.cache_key"):
    logging.getLogger(n).setLevel(logging.DEBUG)
events = []
jax.monitoring.register_event_duration_secs_listener(
    lambda ev, secs, **kw: events.append((round(time.perf_counter() - T0, 2), ev, round(secs, 3),
                                           threading.current_thread().name)))
counts = collections.Counter()
jax.monitoring.register_event_listener(lambda ev, **kw: counts.update([ev]))
import data, spec
from tendermint_tpu.ops.aot_cache import configure_compile_cache
from tendermint_tpu.crypto import batch
from tendermint_tpu.libs import trace
configure_compile_cache()
cell = spec.Cell(spec.load_benchmark(ROOT), "hub-175.catchup")
vals = data.make_validators(2147487301, cell.config)
ring = data.make_ring(2147487301, cell.config, dict(cell.traffic, ring_commits=1), vals)
_, pk, ms, sg = data.rows_of(cell.config, vals, ring[0])
out = {}
def flush(tag):
    t = time.perf_counter()
    ok = batch.verify_batch(pk, ms, sg)
    out[tag] = {"s": round(time.perf_counter() - t, 3), "valid": int(ok.sum())}
if sys.argv[1] == "main":
    flush("first"); flush("second")
else:
    for tag in ("first", "second"):
        th = threading.Thread(target=flush, args=(tag,), name="worker"); th.start(); th.join()
aot = [(e["name"], e["attrs"]["kernel"], round(e["attrs"]["seconds"], 3)) for e in trace.tracer.dump() if e["name"].startswith("aot.")]
print(json.dumps({"mode": sys.argv[1], "flushes": out, "aot": aot,
                  "events_over_half_s": [e for e in events if e[2] >= 0.5], "counts": dict(counts)}))
