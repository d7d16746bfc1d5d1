"""The cell `hub-175.catchup` as files and entries: the lint passes with it,
the stated stake is what its file says of it, the shipped rule is the
fixture's rule, the driver `blocksync_run` keeps the protocol of README.md on
a rehearsal (12 validators on the program's host backend: under
`catchup_max_rows` a call waits out `catchup_max_wait` 0.25 s, which is the
lane's rule and no fault), with the program sound and with a guarantee
broken, and each of the cell's five readers gives a number on a recorded
ring and None on an empty one.

Run: python -m pytest benchmark/tests -q   (or python benchmark/selftest.py)"""

from __future__ import annotations

import itertools
import json
import os
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

import spec  # noqa: E402

CELL = "hub-175.catchup"
ACCEPTED = ["commit-10k.verify-commit", "commit-1024.verify-commit"]
NEW = ["catchup.gather_ms", "catchup.sign_bytes_ms", "catchup.tally_ms", "lane.wait_ms",
       "lane.flushes_per_run"]
# the flush of a run, split as PR 26's readers split a commit.verify call's
FLUSH = ["catchup.prep.hash_ms", "catchup.prep.scalars_ms", "catchup.prep.sort_ms",
         "catchup.prep.wait_ms", "catchup.prep.first_dispatch_ms", "catchup.flush.record_ms"]
COMMIT_READERS = ["entry.gather_ms", "entry.sign_bytes_ms", "flush.record_ms", "prep.first_dispatch_ms",
        "prep.hash_ms", "prep.scalars_ms", "prep.sort_ms", "prep.wait_ms"]
# every accepted cell reports these, and each lists the five accepted cells
CELL_WIDE = ["entry.outside_flush_ms", "flush.wall_ms", "planner.padding_pct", "prep.ms_per_flush",
             "aot.first_call_s", "jit.cold_s", "msm.device_ms_per_flush", "msm_roofline",
             "device.idle_pct", "gc.pause_ms", "call.unnamed_ms"]
BM = spec.load_benchmark(ROOT)
CONFIG = spec.load_json(os.path.join(HERE, "configs", "hub-175.json"))
TRAFFIC = spec.load_json(os.path.join(HERE, "traffic", "catchup.json"))


# -- the entries and the files


def test_the_lint_passes_with_the_new_entries():
    """Entries are looked up by name, so the next cell's entries move none."""
    assert spec.lint(BM, ROOT, HERE) == []
    entry = {c["name"]: c for c in BM["configs"]}["hub-175"]
    assert entry["reduced"] == [] and "docs/qa" in entry["source"] and "175" in entry["source"]
    cell = {w["name"]: w for w in BM["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("hub-175", "catchup", 1)


def test_the_cell_reports_the_new_metrics_and_the_eleven_cell_wide():
    cell = spec.Cell(BM, CELL)
    names = [m["name"] for m in cell.per_layer]
    assert [n for n in names if n in NEW + FLUSH] == NEW + FLUSH and not set(names) & set(COMMIT_READERS)
    assert sorted(n for n in names if n not in NEW + FLUSH) == sorted(CELL_WIDE)
    assert [m["name"] for m in cell.end_to_end] == [
        "sigs_per_s", "verify_ms_p50", "verify_ms_p95", "setup_s"]
    by = {m["name"]: m for m in BM["per_layer"]}
    for name in NEW + FLUSH:
        assert by[name]["workloads"] == [CELL]
    assert {by[n]["layer"] for n in NEW} == {"entry points", "scheduler"}
    assert by["lane.flushes_per_run"]["better"] == "lower"
    # a run's flush is split under the layers PR 26's readers of the same spans name
    for name in FLUSH:
        twin = by[name[len("catchup."):]]
        assert {k: by[name][k] for k in ("unit", "better", "source", "layer", "moves")} == {
            k: twin[k] for k in ("unit", "better", "source", "layer", "moves")}


@pytest.mark.parametrize("accepted", ACCEPTED)
def test_the_accepted_cells_report_what_they_did(accepted):
    """PR 26's eight readers now name the two cells they read in; an accepted
    cell reports the same metrics as before and none of the new ones."""
    by = {m["name"]: m for m in BM["per_layer"]}
    for name in COMMIT_READERS:
        assert by[name]["workloads"] == ACCEPTED
    names = {m["name"] for m in spec.Cell(BM, accepted).per_layer}
    assert set(COMMIT_READERS) <= names and not names & set(NEW + FLUSH)
    # the same in both (`prep.hidden_pct`, 10k only, is retired), and the eleven
    assert names == set(COMMIT_READERS) | set(CELL_WIDE)
    assert "prep.hidden_pct" not in by
    assert not os.path.exists(os.path.join(HERE, "layer_metrics", "prep.hidden_pct.py"))


def test_the_configuration_states_what_the_issue_names():
    assert CONFIG["validators"] == 175 and CONFIG["key_type"] == "ed25519"
    assert CONFIG["absent_share"] == 0.05 and CONFIG["reduced"] == []
    assert CONFIG["verdict_rule"] == "tally_valid_power_run"
    assert CONFIG["expect_flush"]["backend"] == "jax"
    assert CONFIG["expect_flush"]["paths"] == ["rlc-pipelined", "rlc"]
    assumed = " ".join(CONFIG["assumed"])
    for word in ("from memory", "voting_powers", "absent_share", "first and parts"):
        assert word in assumed, word
    assert any("VerifyCommitLight" in g for g in CONFIG["guarantees"])


def test_the_mix_states_what_the_issue_names():
    want = {"entry": "blocksync_run", "loop": "closed", "callers": 1, "ring_commits": 4,
            "commits_per_call": 64, "first_height": 1000, "tampered_one_in": 0,
            "verified_memo_rows": 0, "warmup_calls": 2, "probes": 8,
            "short_power_absent_share": 0.4, "invalid_power_probe": 1, "trace_calls": 8,
            "root_span": "catchup.verify_run", "root_first_span": "catchup.gather"}
    got = {k: v for k, v in TRAFFIC.items() if k != "name" and not k.startswith("why_")}
    assert got == want


def test_the_stake_is_the_stated_formula_and_shape():
    powers = CONFIG["voting_powers"]
    assert len(powers) == 175
    by_rank = [round(20_000_000 / rank ** 0.8) for rank in range(1, 176)]
    assert sorted(powers, reverse=True) == by_rank
    assert powers != by_rank and powers != by_rank[::-1]  # in no order, as a genesis file
    assert [powers[i] for i in range(175)] == [by_rank[67 * i % 175] for i in range(175)]
    total = sum(powers)
    assert total == 192_345_780
    held = [c / total for c in itertools.accumulate(by_rank)]
    assert round(100 * held[0], 1) == 10.4                 # the largest
    assert held[6] <= 1 / 3 < held[7]                      # eight signers hold over a third
    assert held[46] <= 2 / 3 < held[47]                    # 48 over two thirds
    absent = round(CONFIG["absent_share"] * 175)
    assert absent == 9 and (175 - absent) * TRAFFIC["commits_per_call"] == 10_624


def test_the_shipped_rule_is_the_fixtures_rule():
    shipped = spec.Cell(BM, CELL).rule()
    fixture = spec.load_module(os.path.join(
        HERE, "tests", "fixtures", "references", "tally_valid_power.py")).verdict
    rng = np.random.default_rng(29)
    powers = CONFIG["voting_powers"]
    total = sum(powers)
    said = set()
    for trial in range(200):
        blocks, signers = [], []
        for k in range(int(rng.integers(1, 9))):
            here = sorted(rng.choice(175, int(rng.integers(100, 176)), replace=False).tolist())
            blocks.append({"height": 1000 + k, "rows": len(here)})
            signers += here
        bad_share = [0.0, 0.02, 0.3, 0.6][trial % 4]
        mask = (rng.random(len(signers)) >= bad_share).tolist()
        got = shipped(mask, signers, powers, total, blocks)
        assert got == fixture(mask, signers, powers, total, blocks)
        said.add(got.split("#")[0])
    assert said == {"accepted", "refused at block "}
    src = open(os.path.join(HERE, "references", "tally_valid_power_run.py")).read()
    assert "import" not in src.split('"""')[2]  # the rule alone: nothing of the program


# -- the driver on a rehearsal


def run(control: str, seed: int, rows: int = 12, seconds: float = 1.0) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu", TMTPU_CRYPTO_BACKEND="cpu")
    cmd = [sys.executable, os.path.join(HERE, "run.py"), "--workload", CELL,
           "--seed", str(seed), "--seconds", str(seconds), "--trace", "0",
           "--rehearse", str(rows)]
    if control:
        cmd += ["--control", control]
    p = subprocess.run(cmd, cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-2000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["device"]["platform"] == "cpu" and out["metrics"] == {}
    assert list(out)[-1] == "checks"
    for name, (value, limit) in out["checks"].items():
        assert f"check {name} {value} limit {limit}" in p.stderr
    return out


def failing(out: dict) -> set:
    return {k for k, (v, limit) in out["checks"].items() if v > limit}


@pytest.mark.parametrize("seed", [29, 3_000_000_429])
def test_the_driver_keeps_the_protocol(seed):
    out = run("", seed)
    assert out["correct"] is True and not failing(out), out["checks"]
    assert out["attempted"] >= 2 and out["failed"] == 0
    assert out["rows_per_call"] == 64 * 11  # 64 commits, 1 of 12 absent in each
    assert out["notes"]["rows_compared"] == 4 * 64 * 11 and out["notes"]["probes"] == 8
    said = out["notes"]["entry_probes"]
    assert set(said) == {"short_power", "invalid_power"}
    for v in said.values():
        assert v["got"] == v["want"] and v["want"].startswith("refused at block #")
    # under catchup_max_rows a call waits out catchup_max_wait before its one flush
    assert out["rehearsal_readings"]["verify_ms_p50"]["value"] > 250
    assert out["spans_p50"]["outside_flush_ms"] > 250
    assert out["flush"]["backend"] == "cpu"


def test_the_control_on_the_drivers_own_path_is_not_correct():
    out = run("unsent_third", 30)
    assert out["correct"] is False
    assert out["checks"]["probes_accepted"][0] >= 2  # two whole strata lie in the last third
    assert {"probes_accepted"} <= failing(out) <= {"probes_accepted", "entry_verdict_mismatch"}
    assert out["checks"]["flush_off_path"][0] == 0 and out["failed"] == 0


def test_a_verifier_installed_under_the_lane_is_judged():
    """`light` (the reference stopped at 2/3 of each commit's power) under
    reactor, lane and tally: probes behind the 2/3 mark are accepted."""
    out = run("light", 31)
    assert out["correct"] is False and "probes_accepted" in failing(out)
    assert out["checks"]["flush_off_path"][0] == 0  # the stand-in ran under the lane


# -- the five readers, on a ring the program recorded

RECORD = r'''
import json, os, sys, types
sys.path[:0] = [os.getcwd(), os.path.join(os.getcwd(), "benchmark")]
os.environ["TMTPU_CRYPTO_BACKEND"] = "cpu"
import data, spec
from tendermint_tpu.libs import trace
bm = spec.load_benchmark()
cell = spec.Cell(bm, "hub-175.catchup")
traffic = dict(cell.traffic, commits_per_call=6, ring_commits=4)
vals = data.make_validators(29, cell.config, 12)
ring = data.make_ring(29, cell.config, traffic, vals)
entry = cell.entry()
entry.configure(traffic)
state = entry.build(cell.config, vals, ring)
state.scheduler.set_lane_wait("catchup", 0.0)
names = ["catchup.gather_ms", "catchup.sign_bytes_ms", "catchup.tally_ms", "lane.wait_ms",
         "lane.flushes_per_run"]
def read(rows):
    ctx = types.SimpleNamespace(rows=rows)
    return {n: cell.reader(n).read(ctx) for n in names}
out = {"empty": read(data.n_rows(ring[0]))}
# with 12 of the 175 stakes one absentee can hold over a third: such a run is
# refused, rightly, and the readers keep the accepted ones
good = [i for i in range(len(ring)) if entry.call(state, i) == "accepted"]
trace.tracer.clear()
for k in range(int(sys.argv[1])):
    assert entry.call(state, good[k % len(good)]) == "accepted"
    reading = entry.flush_reading()
out["reading"] = {k: reading[k] for k in ("flushes", "lane_flushes", "lane_fallbacks", "rows")}
out["recorded"] = read(data.n_rows(ring[0]))
out["other_size"] = read(7)
out["roots"] = sum(e["name"] == "catchup.verify_run" for e in trace.tracer.dump())
state.scheduler.close()
print(json.dumps(out))
'''


def record(calls: int) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "-c", RECORD, str(calls)], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-2000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_each_reader_reads_a_recorded_ring_and_nothing_from_an_empty_one():
    out = record(40)
    assert out["roots"] == 40
    assert out["empty"] == dict.fromkeys(NEW)
    assert out["other_size"] == dict.fromkeys(NEW)  # runs of another size are not the cell's
    got = out["recorded"]
    assert set(got) == set(NEW)
    for name in NEW[:4]:
        assert isinstance(got[name], float) and 0 < got[name] < 1000, (name, got[name])
    assert got["lane.flushes_per_run"] == 1
    assert out["reading"] == {"flushes": 1, "lane_flushes": 1, "lane_fallbacks": 0,
                              "rows": 6 * 11}


def _device_run_events(root: int, rows: int, t0: int) -> list:
    """One catch-up run as the device path records it (children before their
    root): the reactor's three spans, the lane's two, and under `lane.flush`
    the ordinary flush. Since PR 30 a run's flush is ONE chunk; this ring has
    the prep spans and the dispatch of two, as a streamed flush writes them,
    so that the readers' sums over a call's chunks are held to something."""
    def ev(name, start_ms, dur_ms, **attrs):
        return {"name": name, "span": root + len(out) + 1, "root": root, "attrs": attrs,
                "t0_ns": t0 + int(start_ms * 1e6), "dur_ms": dur_ms}

    out: list = []
    for args in [("catchup.gather", 0, 4.0), ("catchup.sign_bytes", 4, 9.0),
                 ("lane.wait", 13, 0.25), ("prep.hash", 14, 1.5), ("prep.scalars", 15.5, 0.25),
                 ("prep.sort", 15.75, 0.25), ("prep.chunk", 14, 2.5), ("flush.prep_wait", 14, 2.5),
                 ("dispatch", 17, 1.0), ("prep.hash", 17, 9.0), ("prep.scalars", 26, 1.5),
                 ("prep.sort", 27.5, 1.75), ("prep.chunk", 17, 13.0), ("flush.prep_wait", 18, 4.5),
                 ("dispatch", 31, 1.0), ("flush.record", 88, 0.125), ("verify_batch", 13.5, 75.0),
                 ("lane.flush", 13.25, 75.5), ("catchup.tally", 89, 1.0)]:
        out.append(ev(*args))
    out.append({"name": "catchup.verify_run", "span": root, "root": root, "t0_ns": t0,
                "dur_ms": 90.0, "attrs": {"rows": rows, "verdict": "accepted"}})
    return out


def test_the_flush_of_a_run_is_read_from_the_runs_tree(monkeypatch):
    """The six readers of a run's flush, on a ring as the device path writes
    it: sums over the run's chunks, the first dispatch from the start of the
    run's verify_batch, and None on an empty ring or runs of another size."""
    import types

    import program_spans

    cell = spec.Cell(BM, CELL)

    def read(events, rows=10624):
        monkeypatch.setattr(program_spans, "ring", lambda: events)
        ctx = types.SimpleNamespace(rows=rows)
        return {n: cell.reader(n).read(ctx) for n in FLUSH}

    events = [e for k in range(40) for e in _device_run_events(1000 * (k + 1), 10624, k * 10**8)]
    assert read([]) == dict.fromkeys(FLUSH)
    assert read(events, rows=7) == dict.fromkeys(FLUSH)
    assert read(events[: 12 * 20]) == dict.fromkeys(FLUSH)  # 12 runs: under 30
    assert read(events) == {
        "catchup.prep.hash_ms": 10.5, "catchup.prep.scalars_ms": 1.75,
        "catchup.prep.sort_ms": 2.0, "catchup.prep.wait_ms": 7.0,
        "catchup.prep.first_dispatch_ms": 4.5,  # 13.5 -> 17 + 1
        "catchup.flush.record_ms": 0.125}


PROGRAM_SPAN_READERS = ["prep.hash_ms", "prep.scalars_ms", "prep.sort_ms", "prep.wait_ms",
                        "prep.first_dispatch_ms", "flush.record_ms"]


@pytest.mark.parametrize("name", PROGRAM_SPAN_READERS)
def test_a_readers_root_is_the_cells(monkeypatch, name):
    """program_spans.py's readers pick a cell's calls by the root span its
    traffic file states: on a catch-up ring, under `catchup.json`'s root,
    each reads what its `catchup.*` twin reads; under the default root
    (`commit.verify`, a mix that states none) it finds no call."""
    import types

    import program_spans

    cell = spec.Cell(BM, CELL)
    events = [e for k in range(40) for e in _device_run_events(1000 * (k + 1), 10624, k * 10**8)]
    monkeypatch.setattr(program_spans, "ring", lambda: events)
    assert (TRAFFIC["root_span"], TRAFFIC["root_first_span"]) == ("catchup.verify_run",
                                                                  "catchup.gather")
    stated = cell.reader(name).read(types.SimpleNamespace(rows=10624, traffic=TRAFFIC))
    twin = cell.reader("catchup." + name).read(types.SimpleNamespace(rows=10624))
    assert stated == twin and isinstance(stated, float) and stated > 0
    unstated = spec.load_json(os.path.join(HERE, "traffic", "verify-commit.json"))
    assert "root_span" not in unstated
    assert cell.reader(name).read(types.SimpleNamespace(rows=10624, traffic=unstated)) is None
    assert cell.reader(name).read(types.SimpleNamespace(rows=10624)) is None  # no mix at all
    assert cell.reader(name).read(types.SimpleNamespace(rows=7, traffic=TRAFFIC)) is None


def test_under_thirty_whole_runs_the_metrics_are_left_out():
    out = record(12)
    assert out["roots"] == 12 and out["recorded"] == dict.fromkeys(NEW)


def test_a_program_whose_lane_verifies_a_run_twice_fails_cleanly_before_any_data(monkeypatch):
    """The parent of the PR that added the cell gives up on a ticket in
    flight after 30 s and verifies it again inline (a warning, so not
    correct): the driver asks the lane, and ends such a run at once, with no
    result. This program's lane waits, and the driver goes on."""
    sys.path.insert(0, ROOT)
    from tendermint_tpu.crypto.scheduler import VerifyScheduler

    entry = spec.Cell(BM, CELL).entry()
    assert entry._lane_verifies_twice() is False
    # the parent's rule: whoever misses the timeout verifies inline
    monkeypatch.setattr(VerifyScheduler, "_inline_on_host", lambda self, n: True)
    assert entry._lane_verifies_twice() is True
    with pytest.raises(SystemExit, match="cannot run hub-175.catchup soundly"):
        entry.configure(TRAFFIC)


def test_a_dispatch_thread_that_wakes_late_is_not_taken_for_a_lane_that_verifies_twice(monkeypatch):
    """On a loaded host the lane's dispatch thread may take the probe's ticket
    later than the probe's 0.05 s: the caller then takes it back off the queue
    and verifies it inline, once, which is the lane's rule for a QUEUED ticket.
    The driver's question is about a ticket in flight, so it asks again and
    answers from a try in which the timeout struck in flight; nothing of it
    reaches the run's count of warnings. (PR 32's tier-1 run failed here:
    the probe took the late thread for the fault and ended the child.)"""
    import logging

    sys.path.insert(0, ROOT)
    from tendermint_tpu.crypto.scheduler import VerifyScheduler

    entry = spec.Cell(BM, CELL).entry()
    plan, late = VerifyScheduler._plan_locked, []

    def wakes_late(self):
        if not late and any(st.queue for st in self._lanes.values()):
            late.append(True)
            return [], set(), False, 0.12  # looks again 0.12 s on
        return plan(self)

    said = []
    handler = logging.Handler(level=logging.WARNING)
    handler.emit = lambda record: said.append(record.getMessage())
    logging.getLogger("tendermint_tpu").addHandler(handler)
    monkeypatch.setattr(VerifyScheduler, "_plan_locked", wakes_late)
    try:
        assert entry._lane_verifies_twice() is False and late == [True]
        del late[:]
        # and the parent's rule is still found, also behind a late first try
        monkeypatch.setattr(VerifyScheduler, "_inline_on_host", lambda self, n: True)
        assert entry._lane_verifies_twice() is True and late == [True]
    finally:
        logging.getLogger("tendermint_tpu").removeHandler(handler)
    assert said == []
