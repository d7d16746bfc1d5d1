"""The cell `live-10k.vote-commit` (PR 36) as files and entries: the lint passes
with it appended last, the configuration and the mix state what ISSUE 36
names, the rule `vote_step` says what the driver has to say, the driver
`vote_commit` keeps the protocol of README.md on a rehearsal (24 validators on
the program's host backend) with the program sound and under each of its three
controls, and each of the cell's nine readers gives a number on a recorded ring
and None on an empty one. Entries are looked up by name: nothing here says
where in its list an entry stands, so the next cell's entries move none.

Run: python -m pytest benchmark/tests -q   (or python benchmark/selftest.py)"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

import spec  # noqa: E402
import test_light_seq  # noqa: E402  (by the name both loaders give it)

LIVE_CELL = "live-10k.vote-commit"
LIVE_SPANS = ["votes.gather_ms", "votes.sign_bytes_ms", "votes.count_ms", "votes.make_commit_ms",
              "votes.commit_verify_ms"]
LIVE_READINGS = ["votes.add_ms", "votes.memo_ms", "votes.memo_hit_pct", "votes.flushes_per_call"]
LIVE_NEW = ["votes.add_ms", "votes.gather_ms", "votes.sign_bytes_ms", "votes.count_ms",
            "votes.make_commit_ms", "votes.memo_ms", "votes.commit_verify_ms", "votes.memo_hit_pct",
            "votes.flushes_per_call"]
# the three that came with the recorder's new spans (their readers: test_trace_readers.py)
LIVE_OWN = LIVE_NEW + ["votes.pending_ms", "votes.memo_digest_ms", "votes.provenance_ms"]
LIVE_BM = spec.load_benchmark(ROOT)
LIVE_CONFIG = spec.load_json(os.path.join(HERE, "configs", "live-10k.json"))
VOTE_COMMIT_MIX = spec.load_json(os.path.join(HERE, "traffic", "vote-commit.json"))


def without_live(bm: dict) -> dict:
    """The benchmark without the live cell: its configuration, its cell, the
    metrics that list it alone, and its name in every other list."""
    return dict(bm, configs=[c for c in bm["configs"] if c["name"] != "live-10k"],
                workloads=[w for w in bm["workloads"] if w["name"] != LIVE_CELL],
                per_layer=[dict(m, workloads=[w for w in m["workloads"] if w != LIVE_CELL])
                           for m in bm["per_layer"] if m.get("workloads") != [LIVE_CELL]])


# -- the entries and the files


def named(entries: list) -> dict:
    return {e["name"]: e for e in entries}


def test_the_lint_passes_with_live10k_appended_and_nothing_else_moved():
    assert spec.lint(LIVE_BM, ROOT, HERE) == []
    assert spec.lint(without_live(LIVE_BM), ROOT, HERE) == []
    config = {c["name"]: c for c in LIVE_BM["configs"]}["live-10k"]
    cell = {w["name"]: w for w in LIVE_BM["workloads"]}[LIVE_CELL]
    assert (config["name"], config["file"], config["reduced"]) == (
        "live-10k", "benchmark/configs/live-10k.json", [])
    assert config["source"] == LIVE_CONFIG["source"] and len(config["source"]) <= 200
    for word in ("vote_set.go", "MaxVotesCount = 10000", "consensus/state.go", "addVote",
                 "state/validation.go", "validateBlock"):
        assert word in config["source"], word
    assert cell == dict(cell, name=LIVE_CELL, config="live-10k", traffic="vote-commit", chips=1)
    assert "every validator and full node" in cell["why"]
    assert [m["name"] for m in LIVE_BM["per_layer"] if m["workloads"] == [LIVE_CELL]] == LIVE_OWN
    # the accepted entries stand before them, in the order they had
    assert [c["name"] for c in LIVE_BM["configs"][:4]] == [
        "commit-10k", "commit-1024", "hub-175", "light-seq-100"]
    assert [w["name"] for w in LIVE_BM["workloads"][:4]] == [
        "commit-10k.verify-commit", "commit-1024.verify-commit", "hub-175.catchup",
        "light-seq-100.sequence"]


def test_the_live_cell_reports_its_nine_metrics_and_the_eleven_cell_wide():
    cell = spec.Cell(LIVE_BM, LIVE_CELL)
    by = named(LIVE_BM["per_layer"])
    assert {m["name"] for m in cell.per_layer} == set(LIVE_OWN) | set(test_light_seq.CELL_WIDE)
    assert {m["name"] for m in cell.end_to_end} == {"sigs_per_s", "verify_ms_p50", "verify_ms_p95",
                                                    "setup_s"}
    for name in LIVE_NEW:
        assert by[name]["workloads"] == [LIVE_CELL] and by[name]["moves"] == "verify_ms_p50"
    assert {n: by[n]["layer"] for n in LIVE_NEW} == {
        "votes.add_ms": "vote set", "votes.gather_ms": "vote set",
        "votes.sign_bytes_ms": "vote set", "votes.count_ms": "vote set",
        "votes.make_commit_ms": "vote set", "votes.memo_ms": "routing and planner",
        "votes.commit_verify_ms": "entry points", "votes.memo_hit_pct": "routing and planner",
        "votes.flushes_per_call": "scheduler"}
    assert by["votes.add_ms"]["source"] == "host_clock"
    assert {by[n]["source"] for n in LIVE_SPANS + ["votes.memo_ms"]} == {"program_span"}
    assert {by[n]["source"] for n in LIVE_NEW[-2:]} == {"program_counter"}
    assert (by["votes.memo_hit_pct"]["better"], by["votes.memo_hit_pct"]["unit"]) == ("higher", "%")
    assert all(by[n]["better"] == "lower" for n in LIVE_NEW if n != "votes.memo_hit_pct")
    for other in ("commit-10k.verify-commit", "hub-175.catchup", "light-seq-100.sequence"):
        assert not {m["name"] for m in spec.Cell(LIVE_BM, other).per_layer} & set(LIVE_OWN)


def test_the_live_configuration_states_what_the_issue_names():
    c = LIVE_CONFIG
    commit_10k = spec.load_json(os.path.join(HERE, "configs", "commit-10k.json"))
    assert (c["validators"], c["voting_power"], c["key_type"], c["absent_share"]) == (
        10000, 50000, "ed25519", 0.0)
    assert c["chain_id"] == commit_10k["chain_id"] and c["peers"] == 50
    assert c["verdict_rule"] == "vote_step" and c["reject_via_entry"] is False
    assert "dropped" in c["why_not_via_entry"] and "commit-10k.tampered" in c["why_not_via_entry"]
    expect = c["expect_flush"]
    assert (expect["backend"], expect["paths"]) == (commit_10k["expect_flush"]["backend"],
                                                    commit_10k["expect_flush"]["paths"])
    assert expect["commit_path"] == "memo"
    said = " ".join(c["guarantees"])
    for word in ("before the vote is counted, published or put into a commit", "named by its",
                 "neither the tally, the commit nor the verified-row memo", "one ed25519 verify per",
                 "more than 2/3", "one changed byte"):
        assert word in said, word
    assumed = " ".join(c["assumed"])
    for word in ("from memory", "timestamp", "distinct keys", "50 peers", "round robin",
                 "precommit step only", "flush tick", "no duplicates"):
        assert word in assumed, word
    assert c["reduced"] == [] and "voting_powers" not in c and "headers" not in c
    assert "MaxVotesCount = 10000" in c["source"] and "BASELINE.json configs[4]" in c["deployment"]


def test_the_vote_commit_mix_states_what_the_issue_names():
    want = {"entry": "vote_commit", "loop": "closed", "callers": 1, "ring_commits": 8,
            "commits_per_call": 1, "first_height": 5, "tampered_one_in": 0,
            "verified_memo_rows": 65536, "arrival_order": "seeded shuffle", "peers": 50,
            "flushes_per_call": 1, "warmup_calls": 3, "probes": 8,
            "short_power_absent_share": 0.4, "invalid_power_probe": True, "trace_calls": 8,
            "root_span": "votes.flush", "root_first_span": "votes.gather"}
    got = {k: v for k, v in VOTE_COMMIT_MIX.items() if k != "name" and not k.startswith("why_")}
    assert got == want
    # 8 items of 10,000 rows are more than the memo holds: an item's rows are gone by its next turn
    step = LIVE_CONFIG["validators"]
    assert want["ring_commits"] * step > want["verified_memo_rows"] > (want["ring_commits"] - 2) * step
    assert (want["ring_commits"] - 1) * step > want["verified_memo_rows"]
    assert "LRU" in VOTE_COMMIT_MIX["why_memo_on"]


def test_the_generated_steps_are_eight_heights_that_everyone_signs():
    import data

    vals = data.make_validators(36, LIVE_CONFIG, 24)
    ring = data.make_ring(36, LIVE_CONFIG, VOTE_COMMIT_MIX, vals)
    assert [c.height for c in ring] == list(range(5, 13))
    assert all(len(c.present()) == 24 and not c.tampered and c.header is None for c in ring)
    assert len({c.block_hash for c in ring}) == 8 and set(vals.powers) == {50000}
    probes = dict(data.entry_probes(36, LIVE_CONFIG, VOTE_COMMIT_MIX, ring, vals))
    assert set(probes) == {"short_power", "invalid_power"}
    assert len(probes["short_power"].present()) == 14       # 10 of 24 never voted
    assert probes["invalid_power"].tampered == tuple(range(8))  # 16 of 24 is not over 2/3


# -- the rule


def test_the_rule_vote_step_tallies_the_valid_votes_and_names_what_it_saw():
    rule = spec.Cell(LIVE_BM, LIVE_CELL).rule()
    powers, block = [5] * 9, [{"height": 5, "rows": 9}]
    everyone = list(range(9))
    assert rule([True] * 9, everyone, powers, 45, block) == "accepted"
    assert rule([True] * 8 + [False], everyone, powers, 45, block) == "accepted"  # one wrong alone
    assert rule([True] * 7, everyone[:7], powers, 45, block) == "accepted"        # 35 > 30
    assert rule([True] * 6, everyone[:6], powers, 45, block) == (
        "no commit: valid power for the block 30 of 45, over 30 needed; wrong signatures: none")
    mask = [False, False, True, False, True, True, True, False, False]
    assert rule(mask, everyone, powers, 45, block) == (
        "no commit: valid power for the block 20 of 45, over 30 needed; "
        "wrong signatures: #0-1, #3, #7-8")
    # by power, not by head count; and in whatever order the votes arrived
    skewed = [30, 5, 5, 5]
    assert rule([True, False, False, False], [0, 1, 2, 3], skewed, 45, block) == (
        "no commit: valid power for the block 30 of 45, over 30 needed; wrong signatures: #1-3")
    assert rule([False, True, True], [3, 0, 1], skewed, 45, block) == "accepted"
    src = open(os.path.join(HERE, "references", "vote_step.py")).read()
    assert "import" not in src.split('"""')[2]  # the rule alone: nothing of the program


def test_the_driver_writes_indices_as_the_rule_does():
    rule = spec.load_module(spec.module_path(HERE, "references", "vote_step"))
    entry = spec.Cell(LIVE_BM, LIVE_CELL).entry()
    rng = np.random.default_rng(36)
    for trial in range(200):
        picked = rng.choice(40, int(rng.integers(0, 30)), replace=False).tolist()
        assert entry._ranges(picked) == rule._ranges(picked)
    assert entry._ranges([]) == "" and entry._ranges([3, 1, 2, 9]) == "#1-3, #9"


# -- the driver on a rehearsal


def run_live(control: str, seed: int, rows: int = 24, seconds: float = 1.0) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu", TMTPU_CRYPTO_BACKEND="cpu")
    cmd = [sys.executable, os.path.join(HERE, "run.py"), "--workload", LIVE_CELL,
           "--seed", str(seed), "--seconds", str(seconds), "--trace", "0",
           "--rehearse", str(rows)]
    if control:
        cmd += ["--control", control]
    p = subprocess.run(cmd, cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["device"]["platform"] == "cpu" and out["metrics"] == {}
    assert list(out)[-1] == "checks"
    for name, (value, limit) in out["checks"].items():
        assert f"check {name} {value} limit {limit}" in p.stderr
    return out


def failing_live(out: dict) -> set:
    return {k for k, (v, limit) in out["checks"].items() if v > limit}


SHORT = "no commit: valid power for the block 700000 of 1200000, over 800000 needed; " \
        "wrong signatures: none"
INVALID = "no commit: valid power for the block 800000 of 1200000, over 800000 needed; " \
          "wrong signatures: #0-7"


@pytest.mark.parametrize("seed", [36, 3_000_000_436])
def test_the_vote_driver_keeps_the_protocol(seed):
    out = run_live("", seed)
    assert out["correct"] is True and not failing_live(out), out["checks"]
    assert all(v == 0 for v, _ in out["checks"].values())
    assert out["attempted"] >= 9 and out["failed"] == 0  # more than a lap of the ring
    assert out["rows_per_call"] == 24
    assert out["notes"]["rows_compared"] == 8 * 24 and out["notes"]["probes"] == 8
    said = out["notes"]["entry_probes"]
    assert {k: (v["want"], v["got"]) for k, v in said.items()} == {
        "short_power": (SHORT, SHORT), "invalid_power": (INVALID, INVALID)}
    assert out["flush"]["backend"] == "cpu"  # the votes' flush, not the commit's answer


def test_the_control_on_the_vote_drivers_own_path_is_not_correct():
    out = run_live("unsent_third", 37)
    assert out["correct"] is False
    assert out["checks"]["probes_accepted"][0] >= 2  # two whole strata lie in the last third
    assert {"probes_accepted"} <= failing_live(out) <= {"probes_accepted",
                                                        "entry_verdict_mismatch"}
    assert out["checks"]["flush_off_path"][0] == 0 and out["failed"] == 0


def test_a_memo_that_answers_everything_counts_the_wrong_votes():
    out = run_live("memo_answers_all", 38)
    assert out["correct"] is False
    assert {"flush_off_path", "entry_verdict_mismatch"} <= failing_live(out)
    assert out["checks"]["flush_off_path"][0] == out["attempted"] == out["failed"]
    assert "0 of them verified" in out["notes"]["first_off_path"]
    said = out["notes"]["entry_probes"]
    assert said["invalid_power"]["got"] == "accepted" != said["invalid_power"]["want"] == INVALID
    assert said["short_power"]["got"] == SHORT  # a tally needs no signature to refuse


def test_votes_counted_before_the_flush_put_the_wrong_ones_into_the_commit():
    out = run_live("counted_unverified", 39)
    assert out["correct"] is False
    assert {"flush_off_path", "entry_verdict_mismatch"} <= failing_live(out)
    assert out["checks"]["flush_off_path"][0] == out["attempted"] == out["failed"]
    assert out["notes"]["first_off_path"].startswith("0 VoteSet.flush, 0 flushes")
    said = out["notes"]["entry_probes"]
    assert said["invalid_power"]["got"] == "commit refused: wrong signature (#0)"
    assert said["invalid_power"]["want"] == INVALID and said["short_power"]["got"] == SHORT
    assert out["checks"]["verdict_mismatch"][0] == 0  # the window's votes are all valid


def test_the_reference_under_verify_batch_is_sound_and_keeps_the_memo_s_answer():
    """`--control sound`: controls.py's reference in the place of the program's
    executors, under verify_batch: vote set, lane, memo and records stay the
    program's, so the run is correct and the commit still comes from memory."""
    out = run_live("sound", 40)
    assert out["correct"] is True and out["failed"] == 0
    assert {k: v["got"] for k, v in out["notes"]["entry_probes"].items()} == {
        "short_power": SHORT, "invalid_power": INVALID}


# -- the driver's own judgement of a call


def test_the_driver_fails_a_call_whose_flushes_ran_elsewhere():
    entry = spec.Cell(LIVE_BM, LIVE_CELL).entry()
    expect = dict(LIVE_CONFIG["expect_flush"])
    good = {"backend": "jax", "path": "rlc-pipelined", "jax_path": "rlc-pipelined", "fused": True,
            "rlc_fallback": False, "recovery_flushes": None, "rows": 10000, "memo_hits": 0,
            "flushes": 2, "device_flushes": 1, "vote_flushes": 1, "lane_flushes": 1,
            "lane_closed": False, "pending_left": False, "failed": 0, "commit_path": "memo",
            "commit_rows": 10000, "commit_memo_hits": 10000}
    assert entry.flush_fault(good, expect, 10000) is None
    assert entry.flush_fault(dict(good, memo_hits=None), expect, 10000) is None  # PR 35's records

    def fault(**changed):
        return entry.flush_fault(dict(good, **changed), expect, 10000) or ""

    assert "hit 1 rows of the memo" in fault(memo_hits=1)
    assert "not ONE on the votes lane" in fault(lane_flushes=0)
    assert "not ONE on the votes lane" in fault(vote_flushes=2, lane_flushes=2)
    assert "left pending" in fault(pending_left=True)
    assert "3 votes failed" in fault(failed=3)
    assert "2 of them verified" in fault(device_flushes=2)   # the commit was verified again
    assert "0 of them verified" in fault(device_flushes=0)   # the votes came from memory
    assert "path 'rlc-pipelined', not 'memo'" in fault(commit_path="rlc-pipelined")
    assert "hit 9999 of the memo" in fault(commit_memo_hits=9999)
    assert fault(path="rlc-pipelined-recovery") == "path 'rlc-pipelined-recovery'"
    assert fault(rows=9999) == "9999 rows flushed" and fault(fused=False) == "not fused"
    # a rehearsal's expect_flush states no commit_path: the memo's all the same
    rehearsal = {"backend": "cpu", "paths": ["cpu"]}
    cpu = dict(good, backend="cpu", path="cpu", jax_path="", fused=None)
    assert entry.flush_fault(cpu, rehearsal, 10000) is None
    assert "not 'memo'" in entry.flush_fault(dict(cpu, commit_path="cpu"), rehearsal, 10000)


# -- the nine readers

RECORD_LIVE = r'''
import json, os, sys, types
sys.path[:0] = [os.getcwd(), os.path.join(os.getcwd(), "benchmark")]
os.environ["TMTPU_CRYPTO_BACKEND"] = "cpu"
import data, spec
from tendermint_tpu.libs import trace
bm = spec.load_benchmark()
cell = spec.Cell(bm, "live-10k.vote-commit")
n = 24
vals = data.make_validators(36, cell.config, n)
ring = data.make_ring(36, cell.config, cell.traffic, vals)
entry = cell.entry()
entry.configure(cell.traffic)
state = entry.build(cell.config, vals, ring)
names = [m["name"] for m in bm["per_layer"] if m.get("workloads") == [cell.name]]
calls = []
def read(rows):
    ctx = types.SimpleNamespace(rows=rows, traffic=cell.traffic, calls=calls)
    return {k: cell.reader(k).read(ctx) for k in names}
out = {"empty": read(n)}
trace.tracer.clear()
for k in range(int(sys.argv[1])):
    assert entry.call(state, k % len(ring)) == "accepted"
    calls.append({"flush": entry.flush_reading()})
out["faults"] = [entry.flush_fault(c["flush"], {"backend": "cpu", "paths": ["cpu"]}, n)
                 for c in calls]
keys = ("flushes", "device_flushes", "rows", "rows_valid", "path", "memo_hits", "commit_path",
        "commit_rows", "commit_memo_hits", "vote_flushes", "lane_flushes", "failed")
out["reading"] = {k: calls[-1]["flush"][k] for k in keys}
out["recorded"] = read(n)
out["other_size"] = read(7)
events = trace.tracer.dump()
out["roots"] = {name: sum(e["name"] == name for e in events)
                for name in ("votes.flush", "votes.make_commit", "commit.verify")}
out["events_a_call"] = len(events) / int(sys.argv[1])
print(json.dumps(out))
'''


def record_live(calls: int) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "-c", RECORD_LIVE, str(calls)], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-2000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_each_vote_reader_reads_a_recorded_ring_and_nothing_from_an_empty_one():
    out = record_live(36)
    assert out["roots"] == {"votes.flush": 36, "votes.make_commit": 36, "commit.verify": 36}
    assert out["events_a_call"] <= 40
    assert out["empty"] == dict.fromkeys(LIVE_OWN)
    # four and a half laps over the ring, and after the first call no vote flush hit the memo
    assert out["faults"] == [None] * 36
    assert out["reading"] == {"flushes": 2, "device_flushes": 1, "rows": 24, "rows_valid": 24,
                              "path": "cpu", "memo_hits": 0, "commit_path": "memo",
                              "commit_rows": 24, "commit_memo_hits": 24, "vote_flushes": 1,
                              "lane_flushes": 1, "failed": 0}
    got = out["recorded"]
    assert set(got) == set(LIVE_OWN)
    # the queue's wait is read off the ring by the flush's root; the digests and the
    # scorer are put down to calls by time, and these calls carry no clock readings
    assert isinstance(got["votes.pending_ms"], float) and 0 < got["votes.pending_ms"] < 1000
    assert got["votes.memo_digest_ms"] is None and got["votes.provenance_ms"] is None
    for name in LIVE_SPANS + ["votes.add_ms", "votes.memo_ms"]:
        assert isinstance(got[name], float) and 0 < got[name] < 1000, (name, got[name])
    for name in LIVE_SPANS:
        assert out["other_size"][name] is None  # spans of another size are not the cell's
    assert got["votes.memo_hit_pct"] == 100.0 and got["votes.flushes_per_call"] == 1


def test_under_thirty_steps_in_the_ring_the_vote_span_metrics_are_left_out():
    out = record_live(12)
    assert out["roots"]["votes.flush"] == 12
    assert {n: out["recorded"][n] for n in LIVE_SPANS} == dict.fromkeys(LIVE_SPANS)
    assert out["recorded"]["votes.flushes_per_call"] == 1  # the readings need no ring


def step_events(k: int, rows: int, committed=None, failed: int = 0, whole: bool = True) -> list:
    """A call's three roots as the device path writes them: children before their root."""
    t0, base = k * 10**9, 100 * (k + 1)
    flush = [("votes.gather", 9.0), ("votes.sign_bytes", 5.0), ("lane.flush", 45.0),
             ("votes.count", 14.0)][0 if whole else 1:]
    out = [{"name": name, "span": base + 1 + j, "parent": base, "root": base, "t0_ns": t0,
            "dur_ms": dur, "attrs": {}} for j, (name, dur) in enumerate(flush)]
    out.append({"name": "votes.flush", "span": base, "parent": None, "root": base, "t0_ns": t0,
                "dur_ms": 75.0, "attrs": {"height": 5 + k % 8, "round": 0, "type": "precommit",
                                          "rows": rows, "failed": failed,
                                          "committed": rows if committed is None else committed}})
    out.append({"name": "votes.make_commit", "span": base + 10, "parent": None, "root": base + 10,
                "t0_ns": t0, "dur_ms": 11.0, "attrs": {"height": 5 + k % 8, "rows": rows}})
    out.append({"name": "commit.verify", "span": base + 20, "parent": None, "root": base + 20,
                "t0_ns": t0, "dur_ms": 33.0,
                "attrs": {"entry": "verify_commit", "rows": rows, "verdict": "accepted"}})
    return out


def test_the_vote_span_readers_on_handmade_rings(monkeypatch):
    import program_spans

    cell = spec.Cell(LIVE_BM, LIVE_CELL)

    def read(events, rows=10000):
        monkeypatch.setattr(program_spans, "ring", lambda: events)
        ctx = types.SimpleNamespace(rows=rows, traffic=VOTE_COMMIT_MIX, calls=[])
        return [cell.reader(n).read(ctx) for n in LIVE_SPANS]

    ring = [e for k in range(40) for e in step_events(k, 10000)]
    assert read(ring) == [9.0, 5.0, 14.0, 11.0, 33.0]
    assert read(ring, rows=6000) == [None] * 5
    assert read([]) == [None] * 5
    assert read([e for k in range(29) for e in step_events(k, 10000)]) == [None] * 5
    # the parent of the PR that added the spans: commit.verify alone
    assert read([e for e in ring if e["name"] == "commit.verify"]) == [None] * 4 + [33.0]
    # the probes' steps (short of power: all counted but 6,000 rows; wrong votes: 3,334 failed)
    # and a flush whose first child has rolled out of the ring are not the cell's calls
    probes = step_events(50, 6000) + step_events(51, 10000, committed=6666, failed=3334)
    assert read(ring + probes) == [9.0, 5.0, 14.0, 11.0, 33.0]
    torn = [e for k in range(40) for e in step_events(k, 10000, whole=k >= 20)]
    assert read(torn)[:3] == [None] * 3 and read(torn)[3:] == [11.0, 33.0]
    refused = [dict(e, attrs=dict(e["attrs"], verdict="CommitVerifyError"))
               if e["name"] == "commit.verify" else e for e in ring]
    assert read(refused) == [9.0, 5.0, 14.0, 11.0, None]


def test_the_vote_reading_readers_on_handmade_readings():
    cell = spec.Cell(LIVE_BM, LIVE_CELL)

    def read(flushes):
        ctx = types.SimpleNamespace(calls=[{"flush": f} for f in flushes], rows=10000)
        return [cell.reader(n).read(ctx) for n in LIVE_READINGS]

    call = {"add_ms": 17.0, "memo_ms": 43.5, "commit_rows": 10000, "commit_memo_hits": 10000,
            "device_flushes": 1}
    assert read([call] * 5) == [17.0, 43.5, 100.0, 1]
    assert read([call, dict(call, add_ms=19.0, memo_ms=50.0), call]) == [17.0, 43.5, 100.0, 1]
    again = dict(call, commit_memo_hits=None, commit_rows=10000, device_flushes=2)
    assert read([again] * 3) == [17.0, 43.5, 0.0, 2]  # the commit was verified again
    # PR 35's program: records without memo_ms; a call that made no second verify_batch
    parent = dict(call, memo_ms=None)
    assert read([parent] * 3) == [17.0, None, 100.0, 1]
    assert read([dict(call, commit_rows=None, commit_memo_hits=None)])[2] is None
    assert read([]) == [None] * 4
