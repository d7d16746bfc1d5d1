"""The cell `commit-10k.tampered`: a forged 10,000-signature commit at the
protocol's ceiling, with its six per-layer metrics of the recovery ladder.
The cell is what its files say (the configuration `commit-10k-forged`, whose
numbers are `commit-10k`'s, with one Byzantine validator stated; the mix `tampered`: `verify-commit` with a ring of 16 of which items 7 and 15
are forged at row 5,000) and the lint passes it; the forged rows land where
the mix seats them; the readers give the medians a handmade ring and its
calls say, also over one or two forged calls (what a traced run's window
holds), and None on a ring without `recovery.*` spans (the program before
them) or with no forged call the ring covers; and the accepted cells report
exactly what they did.

Run: python -m pytest benchmark/tests -q   (or python benchmark/selftest.py)"""

from __future__ import annotations

import json
import os
import statistics
import sys
import types

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

import data  # noqa: E402
import program_spans  # noqa: E402
import reference  # noqa: E402
import spec  # noqa: E402
import test_accepted_view as view  # noqa: E402  (by the name both loaders give it)
import work  # noqa: E402

BM = view.FULL_BM
CELL = "commit-10k.tampered"
CONFIG = "commit-10k-forged"
LAYER = "recovery ladder"
SIX = {  # name: (unit, better, source, moves)
    "recovery.check_ms": ("ms", "lower", "program_span", "verify_ms_p95"),
    "recovery.leaf_ms": ("ms", "lower", "program_span", "verify_ms_p95"),
    "recovery.rows_checked": ("rows", "lower", "program_counter", "verify_ms_p95"),
    "recovery.flushes_per_call": ("flushes", "lower", "program_counter", "verify_ms_p95"),
    "recovery.device_ms": ("ms", "lower", "device_trace", "sigs_per_s"),
    "recovery_roofline": ("%", "higher", "device_trace", "sigs_per_s"),
}
OWN_KEYS = {"name": "tampered", "ring_commits": 16, "tampered_one_in": 8,
            "tampered_seats": [0.5], "warmup_calls": 16}
# the split rule at row 5,000 of 10,000: eight checks, then a leaf of 512 rows
CHECK_ROWS = [8192, 4096, 2048, 1024, 512, 1024, 2048, 1808]
MS = 10**6  # ns a millisecond


# -- the cell and its files


def test_tampered_cell_is_what_its_files_say():
    (w,) = [w for w in BM["workloads"] if w["name"] == CELL]
    assert (w["config"], w["traffic"], w["chips"]) == (CONFIG, "tampered", 1)
    cell = spec.Cell(BM, CELL)
    base = spec.load_json(spec.traffic_path(HERE, "verify-commit"))
    mix = cell.traffic
    assert {k: v for k, v in mix.items() if k in base and not k.startswith("why")} == dict(
        {k: v for k, v in base.items() if not k.startswith("why")}, **{
            k: v for k, v in OWN_KEYS.items() if k in base})
    assert {k: mix[k] for k in OWN_KEYS} == OWN_KEYS
    assert set(mix) - set(base) <= {"tampered_seats", "why_tampered", "why_trace_calls"}
    assert all(isinstance(mix[k], str) and mix[k] for k in mix if k.startswith("why"))
    # a configuration of its own, with its recovery contract
    assert cell.config == spec.load_json(os.path.join(HERE, "configs", CONFIG + ".json"))
    assert cell.config["expect_recovery"]["paths"] == ["rlc-bisect"]
    assert cell.config["expect_recovery"]["max_recovery_flushes"] >= len(CHECK_ROWS) + 1
    # four end-to-end metrics, and exactly the six per-layer ones
    assert [m["name"] for m in cell.end_to_end] == view.ALL
    assert [m["name"] for m in cell.per_layer] == list(SIX)
    by = {m["name"]: m for m in BM["per_layer"]}
    for name, (unit, better, source, moves) in SIX.items():
        assert by[name] == dict(name=name, unit=unit, better=better, source=source, layer=LAYER,
                                moves=moves, workloads=[CELL]), name
        assert os.path.isfile(spec.module_path(HERE, "layer_metrics", name))
    assert spec.lint(BM, ROOT, HERE) == []


def test_the_forged_configuration_is_commit_10k_with_one_byzantine_validator():
    """Every number and contract of `commit-10k`, so that the cell's clean
    calls are `commit-10k.verify-commit`'s call; its own source (the verdict
    on a forged commit), and a Byzantine validator stated."""
    entries = {c["name"]: c for c in BM["configs"]}
    mine, base = entries[CONFIG], entries["commit-10k"]
    assert (mine["file"], mine["reduced"]) == (f"benchmark/configs/{CONFIG}.json", [])
    assert mine["source"] != base["source"] and len(mine["source"]) <= 200
    for word in ("types/validation.go", "VerifyCommit", "wrong signature (#i)",
                 "MaxVotesCount = 10000"):
        assert word in mine["source"], word
    assert [c["name"] for c in BM["configs"]].index(CONFIG) >= len(view.ACCEPTED_CELLS)
    forged = spec.load_json(os.path.join(HERE, "configs", CONFIG + ".json"))
    commit_10k = spec.load_json(os.path.join(HERE, "configs", "commit-10k.json"))
    assert forged["name"] == CONFIG and forged["source"] == mine["source"]
    for key in ("chain_id", "validators", "key_type", "voting_power", "absent_share", "vote",
                "guarantees", "reject_via_entry", "reduced"):
        assert forged[key] == commit_10k[key], key
    for key in ("expect_flush", "expect_recovery"):
        assert {k: v for k, v in forged[key].items() if k != "why"} == \
            {k: v for k, v in commit_10k[key].items() if k != "why"}, key
    assert forged["byzantine_validators"] == 1
    assert "wrong signature (#i)" in forged["deployment"]


def test_accepted_cells_take_none_of_the_six():
    for name in view.ACCEPTED_CELLS:
        assert not {m["name"] for m in spec.Cell(BM, name).per_layer} & set(SIX), name
    assert [w["name"] for w in BM["workloads"]].index(CELL) >= len(view.ACCEPTED_CELLS)


@pytest.mark.parametrize("seed", [5, 2_147_483_911, 3_000_000_402])
def test_tampered_rows_sit_at_the_middle_under_every_seed(seed):
    """At a rehearsal's 64 validators: items 7 and 15, and no other, hold one
    bad row each, at row 32 (the seat 0.5), R flipped in item 7 and s in 15."""
    cell = spec.Cell(BM, CELL)
    vals = data.make_validators(seed, cell.config, 64)
    ring = data.make_ring(seed, cell.config, cell.traffic, vals)
    assert len(ring) == 16
    got = []
    for j, item in enumerate(ring):
        idx, pks, msgs, sigs = data.rows_of(cell.config, vals, item)
        bad = [i for i, ok in enumerate(reference.verify_rows(pks, msgs, sigs)) if not ok]
        if bad:
            (at,) = bad
            honest = vals.privs[idx[at]].sign(msgs[at])
            (byte,) = [k for k in range(64) if honest[k] != sigs[at][k]]
            got.append((j, at, {7: "R", 33: "s"}[byte]))
    assert got == [(7, 32, "R"), (15, 32, "s")]


# -- the readers on a handmade ring


def recorded(laps: int, ladder: bool = True):
    """`laps` laps of the ring, one call after another from 10 s on, each
    with its `commit.verify` root: a clean call 60 ms long, a forged one
    (items 7 and 15) 400 ms, walking the split rule's eight checks (forged
    call k: 30 + k ms each, the first, third and fourth failing) and the
    512-row leaf (20 + k ms) under a ladder; 32 events of set-up first,
    which the ring may have dropped. Gives the ring and the window's calls
    as the comparison leaves them."""
    events, calls, span = [], [], iter(range(1, 10**6))

    def ev(name, start_ns, dur_ms, parent=None, root=None, **attrs):
        sid = next(span)
        events.append({"name": name, "span": sid, "parent": parent,
                       "root": sid if root is None else root, "t0_ns": start_ns,
                       "dur_ms": dur_ms, "attrs": attrs})
        return sid

    for k in range(32):
        ev("aot.first_call", k * MS, 1.0)
    t, forged_k = 10 * 10**9, 0
    for i in range(16 * laps):
        forged = i % 16 in (7, 15)
        wall_ms = 400.0 if forged else 60.0
        root = ev("commit.verify", t, wall_ms, rows=10_000)
        if forged and ladder:
            at = t + 5 * MS
            lad = ev("recovery.ladder", at, 350.0, parent=root, root=root, rows=10_000,
                     checks=8, leaves=1, flushes=9, rows_checked=sum(CHECK_ROWS),
                     leaf_rows=512, backend="jax")
            for n, rows in enumerate(CHECK_ROWS):
                ev("recovery.check", at + (n + 1) * MS, 30.0 + forged_k, parent=lad, root=root,
                   rows=rows, ok=n not in (0, 2, 3))
            ev("recovery.leaf", at + 20 * MS, 20.0 + forged_k, parent=lad, root=root, rows=512)
            forged_k += 1
        calls.append({"ring": i % 16, "start": t / 1e9, "end": (t + int(wall_ms * MS)) / 1e9,
                      "forged": forged,
                      "flush": {"recovery_flushes": 9 if forged else None}})
        t += int(wall_ms * MS) + MS
    return events, calls


def read_six(monkeypatch, events, calls, device_s=None):
    monkeypatch.setattr(program_spans, "ring", lambda: events)
    cell = spec.Cell(BM, CELL)
    with open(os.path.join(HERE, "peaks.json")) as f:
        peaks = json.load(f)["TPU v5 lite"]
    trace = None if device_s is None else {"device_s_per_call": device_s}
    out = {}
    for name in SIX:
        ctx = types.SimpleNamespace(calls=calls, rows=10_000, traffic=cell.traffic, trace=trace,
                                    work=work, peaks=peaks)
        out[name] = cell.reader(name).read(ctx)
    return out, peaks


def test_the_six_read_a_recorded_ladder(monkeypatch):
    """Five laps: ten forged calls the ring covers, whose checks took
    8 x (30 + k) ms and whose leaf 20 + k ms; the traced slice's first eight
    calls, of which call 7 is forged (231.75 ms of device against 22.13 ms a
    clean call)."""
    events, calls = recorded(5)
    device_s = [0.02213] * 7 + [0.23175]
    got, peaks = read_six(monkeypatch, events, calls, device_s)
    ks = range(10)
    assert got["recovery.check_ms"] == pytest.approx(statistics.median(8 * (30 + k) for k in ks))
    assert got["recovery.leaf_ms"] == pytest.approx(statistics.median(20 + k for k in ks))
    assert got["recovery.rows_checked"] == 20_752
    assert got["recovery.flushes_per_call"] == 9
    assert got["recovery.device_ms"] == pytest.approx(231.75 - 22.13)
    least = work.least_seconds(20_752, peaks)[0] + work.least_seconds(512, peaks)[0]
    assert got["recovery_roofline"] == pytest.approx(100 * least * 1e3 / (231.75 - 22.13))
    assert 0.01 < got["recovery_roofline"] < 0.1


def test_the_span_readers_give_none_without_the_ladders_spans(monkeypatch):
    """The program before the ladder's spans: the four readers of the ring
    give nothing; what the calls and the slice say still reads."""
    events, calls = recorded(5, ladder=False)
    got, _ = read_six(monkeypatch, events, calls, [0.02213] * 7 + [0.23175])
    assert {k: v for k, v in got.items() if v is not None} == {
        "recovery.flushes_per_call": 9, "recovery.device_ms": pytest.approx(209.62)}


def test_the_span_readers_read_a_short_traced_window(monkeypatch):
    """A traced run's window, whose slice is reduced inside it: 18 calls,
    two of them forged (items 7 and 15), and no floor of calls to meet."""
    events, calls = recorded(2)
    calls = calls[:18]
    events = [e for e in events if e["t0_ns"] < calls[-1]["end"] * 1e9]
    got, _ = read_six(monkeypatch, events, calls, [0.02213] * 7 + [0.23175])
    assert got["recovery.check_ms"] == pytest.approx(statistics.median([8 * 30, 8 * 31]))
    assert got["recovery.leaf_ms"] == pytest.approx(20.5)
    assert got["recovery.rows_checked"] == 20_752
    assert got["recovery.flushes_per_call"] == 9
    assert got["recovery_roofline"] is not None


def test_the_span_readers_give_none_where_the_ring_has_dropped_the_forged_calls(monkeypatch):
    """Every forged call older than the ring's oldest events: nothing to
    read, though the calls still say what their flush readings said."""
    events, calls = recorded(2)
    cut = int(calls[16]["start"] * 1e9)
    events = [e for e in events if e["t0_ns"] >= cut]  # the ring now starts at call 16
    got, _ = read_six(monkeypatch, events, calls)
    assert got["recovery.check_ms"] is got["recovery.leaf_ms"] is None
    assert got["recovery.rows_checked"] is None
    assert got["recovery.flushes_per_call"] == 9
    assert got["recovery.device_ms"] is got["recovery_roofline"] is None  # no slice


def test_the_device_readers_give_none_where_the_slice_holds_no_forged_call(monkeypatch):
    events, calls = recorded(5)
    got, _ = read_six(monkeypatch, events, calls, [0.02213] * 7)
    assert got["recovery.device_ms"] is got["recovery_roofline"] is None
    assert got["recovery.rows_checked"] == 20_752


def test_a_cell_that_forges_nothing_reads_none(monkeypatch):
    events, calls = recorded(5, ladder=False)
    for c in calls:
        c["forged"] = False
    got, _ = read_six(monkeypatch, events, calls, [0.02213] * 8)
    assert set(got.values()) == {None}
