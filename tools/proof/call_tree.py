#!/usr/bin/env python3
"""On the chip: a few timed-size calls of a cell through its entry driver, then
the flight recorder's ring grouped by root: how many events does one call
leave, and which?
    python tools/proof/call_tree.py commit-10k.verify-commit commit.verify"""
import collections, json, os, sys
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "benchmark")]
import data, spec
from tendermint_tpu.ops.aot_cache import configure_compile_cache
from tendermint_tpu.libs import trace
configure_compile_cache()
name, root_name = sys.argv[1], sys.argv[2]
cell = spec.Cell(spec.load_benchmark(ROOT), name)
seed = 2147489301
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
roots = [e for e in events if e["name"] == root_name]
by_root = collections.defaultdict(list)
for e in events:
    by_root[e["root"]].append(e)
out = {"cell": name, "events": len(events), "roots": len(roots),
       "events_outside_a_call": sum(len(v) for k, v in by_root.items()
                                    if k not in {r["span"] for r in roots})}
for r in roots:
    names = collections.Counter(e["name"] for e in by_root[r["span"]])
    out.setdefault("per_call", []).append({"attrs": r["attrs"], "dur_ms": r["dur_ms"],
                                           "events": sum(names.values()), "names": dict(names)})
last = by_root[roots[-1]["span"]]
out["last_call_ms"] = {e["name"] + (str(e["attrs"].get("chunk", "")) if "attrs" in e else ""): e.get("dur_ms")
                       for e in last if e.get("dur_ms") is not None}
out["last_flush"] = {k: v for k, v in trace.verify_stats()["last_flush"].items()
                     if k in ("path", "n", "chunks", "chunk_lanes", "padding_lanes", "jit_bucket",
                              "prep_overlap_ms", "prep_ms", "total_ms", "device_dispatches")}
if hasattr(state, "scheduler"):
    state.scheduler.close()
print(json.dumps(out))
