#!/usr/bin/env python3
"""What the outputs of callA.sh / callB.sh say, side by side: per run the
end-to-end and per-layer readings, `correct`, the ring's counts; per cost
run the recorder's on-less-off."""
import json
import sys

for path in sys.argv[1:]:
    lines = [ln for ln in open(path).read().splitlines() if ln.strip()]
    if not lines:
        print(path, "EMPTY")
        continue
    if ".cost." in path:
        r = json.loads(lines[-1])
        print(path, "cost on-off p50 ms", [round(x, 3) for x in r["on_less_off_p50_ms"]],
              "median", round(r["median_on_less_off_ms"], 4), "off p50", round(r["median_off_p50_ms"], 3),
              "share", round(r["cost_share_of_p50"], 5),
              "calls", [w["calls"] for w in r["windows"]], "bad", sum(w["not_accepted"] for w in r["windows"]))
        continue
    ring = next((json.loads(ln[5:]) for ln in lines if ln.startswith("RING ")), {})
    res = next((json.loads(ln) for ln in reversed(lines) if ln.startswith("{")), {})
    m = {k: v["value"] for k, v in res.get("metrics", {}).items()}
    print(path, "correct", res.get("correct"), "samples", res.get("samples"), "failed", res.get("failed"),
          "setup", json.dumps(res.get("setup_split")), "late_s_max", res.get("late_s_max"))
    print("   metrics", json.dumps(m))
    print("   spans_p50", json.dumps(res.get("spans_p50")), "judge_s", res.get("notes", {}).get("judge_s"))
    print("   device", json.dumps(res.get("device")), "breakdown", json.dumps(res.get("breakdown", {}).get("idle_gaps")))
    print("   ring", json.dumps({k: v for k, v in ring.items() if k != "events_by_name"}))
    print("   events", json.dumps(ring.get("events_by_name")))
