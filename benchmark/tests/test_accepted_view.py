"""The six per-layer metrics that came with the recorder's spans of the
collector, the vote queue, the memo's digests, the scorer and the light
client around its run, as entries; and the eleven metrics that every accepted
cell reports, each with a `workloads` list of exactly the five accepted cells:
every accepted cell reports what it reported before, and a cell added later
reports none of the eleven unless it lists itself.

Every entry is looked up by name, and the digests are of the entries that
stand now (`accepted`: each list's head, since later entries are appended),
so the next cell's entries break none of it. Without the lists
(`without_lists`) the benchmark is the one before them, digest for digest;
without the six as well, the one before the six.

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
ACCEPTED_CELLS = ["commit-10k.verify-commit", "commit-1024.verify-commit", "hub-175.catchup",
                  LIGHT_CELL, LIVE_CELL]
# the eleven that once had no `workloads` list, so that every cell reported them
CELL_WIDE = ["entry.outside_flush_ms", "flush.wall_ms", "planner.padding_pct", "prep.ms_per_flush",
             "aot.first_call_s", "jit.cold_s", "msm.device_ms_per_flush", "msm_roofline",
             "device.idle_pct", "gc.pause_ms", "call.unnamed_ms"]
RECORDER_SIX = {
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
# sha256 of the benchmark (every key the lint reads, sorted) before the six and before the lists
BEFORE_THE_SIX = "5f2b6a3b4c6d4b914e42b106e0f4fd76202c9ade28fb213f288ed67a1327e34b"
BEFORE_THE_LISTS = "cc9b0264cebd10b3c7dad24e2d660d9e1f955b529d495dabb66a18a0fc12d58e"

# a bound loosened since those digests were taken: (then, now)
LOOSENED = {"sigs_per_s": (0.08, 0.15)}
# how many entries each list held when the eleven got their lists; later ones are appended
HEAD = {"configs": 5, "workloads": 5, "end_to_end": 4, "per_layer": 52}
FULL_BM = spec.load_benchmark(ROOT)


def without_the_six(bm: dict) -> dict:
    return dict(bm, per_layer=[m for m in bm["per_layer"] if m["name"] not in RECORDER_SIX])


def accepted(bm: dict) -> dict:
    """The benchmark's entries that stand now, without any appended later."""
    return dict(bm, **{k: bm[k][:n] for k, n in HEAD.items()})


def bounds_as_before(bm: dict) -> dict:
    """The benchmark with each loosened bound at what it was, so that the
    digests pin everything else; a bound that moved again fails here."""
    e2e = []
    for m in bm["end_to_end"]:
        if m["name"] in LOOSENED:
            then, now = LOOSENED[m["name"]]
            assert m["bound"] == now, m["name"]
            m = dict(m, bound=then)
        e2e.append(m)
    return dict(bm, end_to_end=e2e)


def without_lists(bm: dict) -> dict:
    """The benchmark with the eleven's `workloads` lists taken off again."""
    return dict(bm, per_layer=[{k: v for k, v in m.items() if k != "workloads"}
                               if m["name"] in CELL_WIDE else m for m in bm["per_layer"]])


def digest(bm: dict) -> str:
    keys = ("command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer")
    return hashlib.sha256(json.dumps({k: bm[k] for k in keys}, sort_keys=True).encode()).hexdigest()


def test_the_six_stand_as_they_came_and_nothing_else_moved():
    by = {m["name"]: m for m in FULL_BM["per_layer"]}
    for name, entry in RECORDER_SIX.items():
        listed = {"workloads": ACCEPTED_CELLS} if name in CELL_WIDE else {}
        assert by[name] == dict(entry, name=name, **listed), name
    assert digest(bounds_as_before(without_the_six(without_lists(accepted(FULL_BM))))) == \
        BEFORE_THE_SIX
    assert spec.lint(FULL_BM, ROOT, HERE) == []
    for name in RECORDER_SIX:
        assert os.path.isfile(spec.module_path(HERE, "layer_metrics", name))


def test_every_cell_reports_the_two_cell_wide_and_its_own():
    own = {LIVE_CELL: {"votes.pending_ms", "votes.memo_digest_ms", "votes.provenance_ms"},
           LIGHT_CELL: {"light.client_ms"}}
    for name in ACCEPTED_CELLS:
        cell = spec.Cell(FULL_BM, name)
        assert [m["name"] for m in cell.end_to_end] == ALL
        new = {m["name"] for m in cell.per_layer} & set(RECORDER_SIX)
        assert new == {"gc.pause_ms", "call.unnamed_ms"} | own.get(name, set()), name


def test_the_eleven_list_the_five_and_every_accepted_cell_reports_what_it_did():
    """The one edit of BENCHMARK.json that made room for a later cell: a
    `workloads` list on each of the eleven. Taken off again, the benchmark is
    the one before them, digest for digest; and with or without
    the lists `spec.metrics_of` gives every accepted cell the same metrics by
    name, in the same order."""
    by = {m["name"]: m for m in FULL_BM["per_layer"]}
    assert [w["name"] for w in accepted(FULL_BM)["workloads"]] == ACCEPTED_CELLS
    for name in CELL_WIDE:
        assert by[name]["workloads"] == ACCEPTED_CELLS, name
    before = without_lists(accepted(FULL_BM))
    assert digest(bounds_as_before(before)) == BEFORE_THE_LISTS
    for cell in ACCEPTED_CELLS:
        for kind in ("end_to_end", "per_layer"):
            now, then = ([m["name"] for m in spec.metrics_of(bm, kind, cell)]
                         for bm in (accepted(FULL_BM), before))
            assert now == then, (cell, kind)
    # a cell added later takes none of the eleven unless it lists itself
    later = dict(FULL_BM, workloads=FULL_BM["workloads"] + [dict(FULL_BM["workloads"][0],
                                                                name="later.cell")])
    assert not {m["name"] for m in spec.metrics_of(later, "per_layer", "later.cell")}
    assert {m["name"] for m in spec.metrics_of(before, "per_layer", "later.cell")} == set(CELL_WIDE)
