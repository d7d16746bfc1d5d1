#!/usr/bin/env python3
"""On the chip: a few timed-size catch-up runs through the driver, then the
flight recorder's ring grouped by root: is one run one tree?"""
import collections, json, os, sys
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "benchmark")]
import data, spec
from tendermint_tpu.ops.aot_cache import configure_compile_cache
from tendermint_tpu.libs import trace
configure_compile_cache()
cell = spec.Cell(spec.load_benchmark(ROOT), "hub-175.catchup")
seed = 2147487301
vals = data.make_validators(seed, cell.config)
ring = data.make_ring(seed, cell.config, dict(cell.traffic, ring_commits=2), vals)
entry = cell.entry()
entry.configure(cell.traffic)
state = entry.build(cell.config, vals, ring)
for i in range(3):
    entry.call(state, i % 2)
trace.tracer.clear()
for i in range(4):
    assert entry.call(state, i % 2) == "accepted"
events = trace.tracer.dump()
roots = [e for e in events if e["name"] == "catchup.verify_run"]
by_root = collections.defaultdict(list)
for e in events:
    by_root[e["root"]].append(e)
out = {"events": len(events), "roots": len(roots),
       "events_outside_a_run": sum(len(v) for k, v in by_root.items()
                                   if k not in {r["span"] for r in roots})}
for r in roots:
    names = collections.Counter(e["name"] for e in by_root[r["span"]])
    out.setdefault("per_run", []).append({"attrs": r["attrs"], "dur_ms": r["dur_ms"],
                                          "events": sum(names.values()), "names": dict(names)})
last = by_root[roots[-1]["span"]]
out["last_run_ms"] = {e["name"] + (str(e["attrs"].get("chunk", "")) if "attrs" in e else ""): e.get("dur_ms")
                      for e in last if e.get("dur_ms") is not None}
state.scheduler.close()
print(json.dumps(out))
