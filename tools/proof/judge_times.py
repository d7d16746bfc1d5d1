#!/usr/bin/env python3
"""What prove.py's summary leaves out, from the result lines it kept: per run
set-up, the window's medians, the comparison's seconds after the window
(`notes.judge_s`), the entry probes' paths, and what was not correct.
    python tools/proof/judge_times.py chiprun_out/hub-175.g.jsonl [...]"""
import json, sys

for path in sys.argv[1:]:
    for line in open(path):
        r = json.loads(line)
        res = r.get("result") or {}
        m = {k: v["value"] for k, v in res.get("metrics", {}).items()}
        notes = res.get("notes", {})
        bad = {k: v for k, v in res.get("checks", {}).items() if v[0] > v[1]} if res else {}
        print(json.dumps({
            "file": path.rsplit("/", 1)[-1], "seed": r.get("seed"), "trace": r.get("trace"),
            "rc": r.get("rc"), "wall_s": r.get("wall_s"), "correct": res.get("correct"),
            "failed": res.get("failed"), "setup_s": m.get("setup_s"),
            "verify_ms_p50": m.get("verify_ms_p50"), "verify_ms_p95": m.get("verify_ms_p95"),
            "sigs_per_s": m.get("sigs_per_s"), "judge_s": notes.get("judge_s"),
            "entry_probes": {k: (v.get("got"), v.get("path")) for k, v in
                             notes.get("entry_probes", {}).items()},
            "not_within_limit": bad, "warnings": notes.get("warnings"),
            "layers": {k: v for k, v in m.items() if "." in k or "roofline" in k} if r.get("trace") else None,
        }))
