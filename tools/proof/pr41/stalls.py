#!/usr/bin/env python3
"""One run of benchmark/run.py in-process, from the root of a checkout, that
also keeps what the flight recorder saw in every slow call of the window:

    python tools/proof/pr41/stalls.py --workload W --seed N --seconds S --trace 0

Prints run.py's result line, then one line `CALLS {...}`: the window's
calls (count, and the clean and forged calls' median walls; a forged call is
one on a ring item at 7 mod 8, where the mix `tampered` seats them), each
call slower than three times its kind's median with the ring's spans that
started in it (the fifteen longest, and the collector's totals before and
after it), and every gap between two calls over 50 ms. A slow call's spans are taken from the ring
as soon as the call returns, so a stall anywhere in the window is seen, not
only in the calls the ring still holds at the end."""

from __future__ import annotations

import contextlib
import io
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "benchmark")]


def main() -> int:
    import run
    from tendermint_tpu.libs import trace

    slow, walls = [], []
    spec_cell = run.spec.Cell(run.spec.load_benchmark(ROOT), sys.argv[sys.argv.index("--workload") + 1])
    entry_mod = spec_cell.entry()
    inner = entry_mod.call
    seen = {"n": 0}

    def call(state, i):
        g0 = trace.gc_stats()
        t0 = time.perf_counter()
        verdict = inner(state, i)
        t1 = time.perf_counter()
        walls.append((i, t0, t1))
        # the first three laps set the medians; later a call over 3x its kind's
        kind = [w for j, a, b in walls if (j % 8 == 7) == (i % 8 == 7) for w in [b - a]]
        if len(kind) > 8 and (t1 - t0) > 3 * statistics.median(kind):
            lo, hi = int(t0 * 1e9), int(t1 * 1e9)
            evs = [e for e in trace.tracer.dump() if "t0_ns" in e and lo <= e["t0_ns"] <= hi]
            top = sorted(((e.get("dur_ms") or 0.0, e["name"]) for e in evs), reverse=True)[:15]
            slow.append({"item": i, "wall_ms": (t1 - t0) * 1e3, "n": seen["n"],
                         "spans": top, "gc_before": g0, "gc_after": trace.gc_stats()})
        seen["n"] += 1
        return verdict

    # run.py loads the entry driver by path again: hand it this module object
    run.spec.Cell.entry = lambda self: entry_mod
    entry_mod.call = call
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = run.main(sys.argv[1:])
    line = buf.getvalue().strip().splitlines()[-1]
    print(line, flush=True)
    result = json.loads(line)
    warm = int(spec_cell.traffic["warmup_calls"])
    window = walls[warm:warm + result["attempted"]]
    clean = [(b - a) * 1e3 for i, a, b in window if i % 8 != 7]
    forged = [(b - a) * 1e3 for i, a, b in window if i % 8 == 7]
    gaps = [{"after": k, "gap_ms": (window[k + 1][1] - window[k][2]) * 1e3}
            for k in range(len(window) - 1) if window[k + 1][1] - window[k][2] > 0.05]
    print("CALLS " + json.dumps({
        "rc": rc, "calls": len(window), "clean_ms_p50": statistics.median(clean) if clean else None,
        "forged_ms_p50": statistics.median(forged) if forged else None,
        "clean_ms_max": max(clean, default=None), "forged_ms_max": max(forged, default=None),
        "slow": slow, "gaps": gaps}), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
