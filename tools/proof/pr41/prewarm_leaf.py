#!/usr/bin/env python3
"""Does a node's first forged 10,000-signature commit compile anything after
`prewarm(10000)`? One fresh process, run from the root of a checkout:

    python tools/proof/pr41/prewarm_leaf.py [--seed N] [--no-prewarm] [--rehearse N]

Builds the validator set and a ring of 8 commits from the seed with the
benchmark's own generator (benchmark/data.py: the configuration
`commit-10k`, the mix `tampered` cut to 8 items, so item 7 carries one
flipped bit of R at row 5,000), then, as a node does: `crypto.batch.prewarm`
for the set's size (the node's background warm thread), one valid commit
through `ValidatorSet.verify_commit` (the node's first block), and the forged
commit. For each step it prints one JSON line: its wall, the AOT cache's
compile seconds (`compile_ms` of the flush record, `aot` events) and JAX's
backend compiles in it, and for the commits the verdict, the path, and the
recovery ladder's counts. `--no-prewarm` leaves the warm out (the forged
call then loads the ladder's leaf itself). `--rehearse N` runs N validators
on whatever JAX has (the host backend unless the environment says
otherwise), to walk the script without a chip."""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "benchmark")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=2147541001)
    ap.add_argument("--no-prewarm", action="store_true")
    ap.add_argument("--rehearse", type=int, default=0)
    args = ap.parse_args(argv)
    if args.rehearse:
        os.environ.setdefault("TMTPU_CRYPTO_BACKEND", "cpu")

    import jax
    import jax.monitoring

    import data
    import spec
    from tendermint_tpu.crypto import batch
    from tendermint_tpu.libs import trace
    from tendermint_tpu.ops.aot_cache import configure_compile_cache

    configure_compile_cache()
    compiles = [0]

    def on_duration(event, secs, **_kw):
        compiles[0] += event.endswith("backend_compile_duration")

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    cell = spec.Cell(spec.load_benchmark(ROOT), "commit-10k.tampered")
    entry = cell.entry()
    entry.configure(cell.traffic)
    mix = dict(cell.traffic, ring_commits=8)
    vals = data.make_validators(args.seed, cell.config, args.rehearse or None)
    ring = data.make_ring(args.seed, cell.config, mix, vals)
    state = entry.build(cell.config, vals, ring)
    n = len(vals.pubkeys)
    device = jax.devices()[0]
    print(json.dumps({"device": device.platform, "kind": device.device_kind, "validators": n,
                      "forged_item": 7, "seat": mix["tampered_seats"][0]}), flush=True)

    def step(name, fn):
        c0, a0, t0 = compiles[0], trace.compile_seconds_total(), time.perf_counter()
        out = fn()
        line = {"step": name, "wall_s": round(time.perf_counter() - t0, 3),
                "aot_compile_s": round(trace.compile_seconds_total() - a0, 3),
                "backend_compiles": compiles[0] - c0}
        if out is not None:
            reading = entry.flush_reading()
            last = trace.verify_stats()["last_flush"]
            line.update(verdict=out, compile_ms=reading["compile_ms"], path=reading["path"],
                        jax_path=reading["jax_path"], recovery_flushes=reading["recovery_flushes"],
                        recovery_checks=last.get("recovery_checks"),
                        recovery_leaves=last.get("recovery_leaves"),
                        recovery_rows_checked=last.get("recovery_rows_checked"))
        print(json.dumps(line), flush=True)
        return line

    if not args.no_prewarm:
        step("prewarm", lambda: batch.prewarm(n))
    step("first_commit", lambda: entry.call(state, 0))
    forged = step("forged_commit", lambda: entry.call(state, 7))
    step("forged_again", lambda: entry.call(state, 7))
    return 0 if forged["verdict"].startswith("wrong signature") else 1


if __name__ == "__main__":
    sys.exit(main())
