#!/usr/bin/env python3
"""One run of benchmark/run.py in-process, in the checkout given, then what
the flight recorder's ring still holds of the window's calls after the judge:

    python tools/proof/pr38/ring_calls.py <checkout> --workload W --seed N --seconds S --trace 0|1 [...]

Prints run.py's result line, then one line `RING {...}`: the window's calls,
the whole calls the accepted readers keep (program_spans.whole_calls under
the mix's root; for a vote flush, vote_spans' rule), the calls the ring still
covers by time (call_spans.covered, without its floor of 30), the ring's
events a window call, and PR 38's six readers on the same ring. The checkout
(the parent's, with this PR's benchmark/ laid over it, or the change's) runs
its own program; call_spans and the readers come from this PR's benchmark/."""

import contextlib
import importlib.util
import io
import json
import os
import sys
import types

HERE = os.path.dirname(os.path.abspath(__file__))
MINE = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(HERE))), "benchmark")
NEW38 = ["gc.pause_ms", "call.unnamed_ms", "votes.pending_ms", "votes.memo_digest_ms",
         "votes.provenance_ms", "light.client_ms"]


def load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def main():
    checkout, argv = os.path.abspath(sys.argv[1]), sys.argv[2:]
    os.chdir(checkout)
    sys.path[:0] = [checkout, os.path.join(checkout, "benchmark")]
    import run  # the checkout's own harness and program

    got = {}
    window = run.run_window

    def kept(*a, **kw):
        out = window(*a, **kw)
        got["calls"] = out[0]
        return out

    run.run_window = kept
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = run.main(argv)
    line = buf.getvalue().strip().splitlines()[-1]
    print(line, flush=True)
    result = json.loads(line)
    import program_spans
    import spec

    from tendermint_tpu.libs import trace

    events = trace.tracer.dump()
    cell = spec.Cell(spec.load_benchmark(checkout), result["workload"])
    rows, mix = result["rows_per_call"], cell.traffic
    root = mix.get("root_span", program_spans.ROOT)
    if root == "votes.flush":
        by_root = {}
        for e in events:
            by_root.setdefault(e.get("root"), []).append(e)
        whole = sum(1 for rid, evs in by_root.items()
                    for r in evs if r.get("span") == rid and r["name"] == root
                    and (r.get("attrs") or {}).get("rows") == rows
                    and r["attrs"].get("committed") == rows and r["attrs"].get("failed") == 0
                    and any(e["name"] == "votes.gather" for e in evs))
    else:
        whole = len(program_spans.whole_calls(events, rows, root,
                                              mix.get("root_first_span", program_spans.FIRST_CHILD)))
    call_spans = load(os.path.join(MINE, "call_spans.py"), "call_spans")
    calls = got["calls"]
    ctx = types.SimpleNamespace(calls=calls, rows=rows, traffic=mix)
    floor, call_spans.MIN_CALLS = call_spans.MIN_CALLS, 0
    covered = len(call_spans.covered(ctx))
    call_spans.MIN_CALLS = floor
    sys.modules["call_spans"] = call_spans
    readings = {}
    for name in NEW38:
        ctx = types.SimpleNamespace(calls=calls, rows=rows, traffic=mix)
        readings[name] = load(os.path.join(MINE, "layer_metrics", name + ".py"),
                              "r_" + name.replace(".", "_")).read(ctx)
    names = {}
    for e in events:
        names[e["name"]] = names.get(e["name"], 0) + 1
    print("RING " + json.dumps({
        "rc": rc, "workload": result["workload"], "window_calls": len(calls),
        "whole_calls": whole, "covered_calls": covered, "ring_events": len(events),
        "gc_collect_events": names.get("gc.collect", 0), "readings": readings,
        "events_by_name": names}), flush=True)


if __name__ == "__main__":
    main()
