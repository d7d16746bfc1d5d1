#!/usr/bin/env python3
"""On the chip: what the verified-row memo costs and saves a call of
live-10k.vote-commit. One process, the cell's own driver and data; the same
steps with the memo at the mix's 65,536 rows (the commit answered from it) and
at 0 (the commit verified again on the device), in alternating blocks of calls.
    python tools/proof/pr36/memo_ab.py [calls a block] [blocks a side] [validators, for a walk on the CPU]"""
import gc, json, os, statistics, sys, time
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "benchmark")]
import data, spec
from tendermint_tpu.crypto import batch
from tendermint_tpu.ops.aot_cache import configure_compile_cache
configure_compile_cache()
per_block = int(sys.argv[1]) if len(sys.argv) > 1 else 24
blocks = int(sys.argv[2]) if len(sys.argv) > 2 else 3
cell = spec.Cell(spec.load_benchmark(ROOT), "live-10k.vote-commit")
seed = 2147495301
vals = data.make_validators(seed, cell.config, int(sys.argv[3]) if len(sys.argv) > 3 else None)
ring = data.make_ring(seed, cell.config, cell.traffic, vals)
entry = cell.entry()
entry.configure(cell.traffic)
state = entry.build(cell.config, vals, ring)
for i in range(3):
    assert entry.call(state, i) == "accepted"
gc.collect()
out = {"on": [], "off": []}
k = 3
for b in range(2 * blocks):
    side = "on" if b % 2 == 0 else "off"
    batch.configure_verified_memo(entry._memo_rows[0] if side == "on" else 0)
    for _ in range(per_block):
        t0 = time.perf_counter()
        verdict = entry.call(state, k % len(ring))
        wall = (time.perf_counter() - t0) * 1e3
        assert verdict == "accepted", verdict
        r = entry.flush_reading()
        out[side].append({"wall": wall, "flush_total": r["total_ms"], "memo_ms": r["memo_ms"] or 0.0,
                          "device_flushes": r["device_flushes"], "commit_path": r["commit_path"],
                          "add_ms": r["add_ms"]})
        k += 1
for side, calls in out.items():
    print(side, "calls", len(calls), {key: round(statistics.median(c[key] for c in calls), 3)
                                       for key in ("wall", "flush_total", "memo_ms", "add_ms")},
          "device_flushes", {c["device_flushes"] for c in calls}, "commit", {c["commit_path"] for c in calls},
          "wall p95", round(sorted(c["wall"] for c in calls)[int(len(calls) * 0.95)], 3))
os.makedirs(os.path.join(ROOT, "chiprun_out", "pr36"), exist_ok=True)
json.dump(out, open(os.path.join(ROOT, "chiprun_out", "pr36", "memo_ab.json"), "w"))
