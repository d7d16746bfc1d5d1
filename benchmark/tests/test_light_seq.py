"""The cell `light-seq-100.sequence` as files and entries: the lint passes with
it, the chain is what its files say (3 x 333 = 999 headers above a trusted
root at height 1, 100 rows a header), the shipped rule is the fixture's rule,
the driver `light_sequence` keeps the protocol of README.md on a rehearsal (4
validators on the program's host backend), with the program sound, with the
control and with each of the four planted faults a chain cell can have, and
each of the cell's nine readers gives a number on a recorded ring and None
on an empty one.

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

LIGHT_CELL = "light-seq-100.sequence"
LIGHT_SPANS = ["light.header_checks_ms", "light.gather_ms", "light.sign_bytes_ms",
               "light.tally_ms", "light.store_ms"]
# the cell's eight span and counter readers and `light.set_leaf_hit_pct` (test_light_set_leaf.py)
LIGHT_NEW = LIGHT_SPANS + ["light.flushes_per_call", "light.chunks_per_flush",
                           "light.prep_hidden_pct", "light.set_leaf_hit_pct"]
# and the one that reads the calls' clock readings (call_spans.py; test_trace_readers.py)
LIGHT_OWN = LIGHT_NEW + ["light.client_ms"]  # every metric that lists the light cell alone
# every accepted cell reports these, and each lists the five accepted cells
CELL_WIDE = ["entry.outside_flush_ms", "flush.wall_ms", "planner.padding_pct", "prep.ms_per_flush",
             "aot.first_call_s", "jit.cold_s", "msm.device_ms_per_flush", "msm_roofline",
             "device.idle_pct", "gc.pause_ms", "call.unnamed_ms"]
LIGHT_BM = spec.load_benchmark(ROOT)
LIGHT_CONFIG = spec.load_json(os.path.join(HERE, "configs", "light-seq-100.json"))
SEQUENCE_MIX = spec.load_json(os.path.join(HERE, "traffic", "sequence.json"))


# -- the entries and the files


def by_name(entries: list) -> dict:
    return {e["name"]: e for e in entries}


def test_the_lint_passes_with_the_light_cell_and_the_accepted_entries_stand():
    """Entries are looked up by name: nothing here says where in its list an
    entry stands or how many there are, so the next cell breaks none of it."""
    assert spec.lint(LIGHT_BM, ROOT, HERE) == []
    configs, cells = by_name(LIGHT_BM["configs"]), by_name(LIGHT_BM["workloads"])
    assert {"commit-10k", "commit-1024", "hub-175"} <= set(configs)
    assert {"commit-10k.verify-commit", "commit-1024.verify-commit", "hub-175.catchup"} <= set(cells)
    entry = configs["light-seq-100"]
    assert entry["reduced"] == ["headers_per_call"]
    assert entry["file"] == "benchmark/configs/light-seq-100.json"
    for word in ("client_benchmark_test.go", "BenchmarkSequence",
                 "genMockNode(chainID, 1000, 100, 1, bTime)", "SequentialVerification"):
        assert word in entry["source"], word
    cell = cells[LIGHT_CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("light-seq-100", "sequence", 1)
    assert [w["name"] for w in LIGHT_BM["workloads"] if w["config"] == "light-seq-100"] == [LIGHT_CELL]


def test_the_light_cell_reports_its_own_metrics_and_the_eleven_cell_wide():
    cell = spec.Cell(LIGHT_BM, LIGHT_CELL)
    by = by_name(LIGHT_BM["per_layer"])
    assert set(LIGHT_OWN) | set(CELL_WIDE) <= {m["name"] for m in cell.per_layer}
    assert {"sigs_per_s", "verify_ms_p50", "verify_ms_p95", "setup_s"} <= {
        m["name"] for m in cell.end_to_end}
    for name in LIGHT_NEW:
        assert by[name]["workloads"] == [LIGHT_CELL] and by[name]["moves"] == "verify_ms_p50"
    assert {by[n]["source"] for n in LIGHT_SPANS} == {"program_span"}
    assert {by[n]["source"] for n in LIGHT_NEW[5:]} == {"program_counter"}
    assert by["light.prep_hidden_pct"]["better"] == "higher"
    assert by["light.prep_hidden_pct"]["unit"] == "%"
    # no accepted cell reports a new one
    for other in ("commit-10k.verify-commit", "hub-175.catchup"):
        assert not {m["name"] for m in spec.Cell(LIGHT_BM, other).per_layer} & set(LIGHT_OWN)


def test_the_light_configuration_states_what_the_issue_names():
    c = LIGHT_CONFIG
    assert (c["validators"], c["key_type"], c["absent_share"]) == (100, "ed25519", 0.0)
    assert c["headers"] is True and c["validator_changes_per_height"] == 1
    assert isinstance(c["voting_power"], int) and "voting_powers" not in c
    assert "fresh_voting_powers" not in c  # a fresh key takes the power of the key it replaces
    assert c["verdict_rule"] == "sequential_run" and c["reject_via_entry"] is False
    assert c["expect_flush"]["backend"] == "jax" and c["expect_flush"]["paths"] == ["rlc-streamed"]
    assert any("VerifyCommitLight" in g and "unseen" in g for g in c["guarantees"])
    assert any("own height" in g for g in c["guarantees"])
    assumed = " ".join(c["assumed"])
    for word in ("from memory", "voting_power", "timestamp", "chain_id", "expired"):
        assert word in assumed, word
    (cut,) = c["reduced"]
    assert cut.startswith("headers_per_call") and "333 of the source's 999 headers" in cut
    assert "BenchmarkSequence" in c["source"] and "1,099 distinct keys" in c["source"]


def test_the_light_mix_states_what_the_issue_names():
    want = {"entry": "light_sequence", "loop": "closed", "callers": 1, "ring_commits": 3,
            "commits_per_call": 333, "first_height": 2, "tampered_one_in": 0,
            "verified_memo_rows": 0, "warmup_calls": 2, "probes": 8,
            "short_power_absent_share": 0.4, "invalid_power_probe": 1, "trace_calls": 4,
            "root_span": "light.verify_run", "root_first_span": "light.fetch"}
    got = {k: v for k, v in SEQUENCE_MIX.items() if k != "name" and not k.startswith("why_")}
    assert got == want
    # the source's chain: 999 headers above a trusted root at height 1, the last at 1000
    last = want["first_height"] + want["ring_commits"] * want["commits_per_call"] - 1
    assert want["ring_commits"] * want["commits_per_call"] == 999 and last == 1000
    assert want["commits_per_call"] * LIGHT_CONFIG["validators"] == 33_300


def test_the_generated_chain_is_the_sources_at_a_rehearsals_size():
    """The ring at 4 validators: 3 items of 333 headers, ONE chain from the
    root at height 1 to height 1000, every one signs, one key replaced a
    height (1,000 sets, 4 + 999 keys), equal powers throughout."""
    import data

    vals = data.make_validators(34, LIGHT_CONFIG, 4)
    ring = data.make_ring(34, LIGHT_CONFIG, SEQUENCE_MIX, vals)
    commits = [c for item in ring for c in item]
    assert [len(item) for item in ring] == [333] * 3
    assert [c.height for c in commits] == list(range(2, 1001))
    root = ring[0][0].prev
    assert root.height == 1 and root.vals is vals
    assert [c.prev for c in commits] == [root] + commits[:-1]
    assert all(len(c.present()) == 4 for c in commits) and data.n_rows(ring[0]) == 333 * 4
    sets = [root.vals] + [c.vals for c in commits]
    assert len({tuple(v.pubkeys) for v in sets}) == 1000
    assert len({pk for v in sets for pk in v.pubkeys}) == 4 + 999
    assert {tuple(v.powers) for v in sets} == {(LIGHT_CONFIG["voting_power"],) * 4}
    assert all(b["link_ok"] for item in ring for b in data.blocks_of(item))
    probes = dict(data.entry_probes(34, LIGHT_CONFIG, SEQUENCE_MIX, ring, vals))
    assert set(probes) == {"short_power", "invalid_power", "broken_link"}
    # no header has expired at the configuration's now, none is from the future
    now = data.BASE_TIME_NS + LIGHT_CONFIG["now_after_base_time_s"] * 10**9
    times = [c.header["time_ns"] for c in [root] + commits]
    assert max(times) < now < min(times) + LIGHT_CONFIG["trusting_period_s"] * 10**9


def test_the_shipped_light_rule_is_the_fixtures_rule():
    shipped = spec.Cell(LIGHT_BM, LIGHT_CELL).rule()
    fixture = spec.load_module(os.path.join(
        HERE, "tests", "fixtures", "references", "adjacent_run.py")).verdict
    rng = np.random.default_rng(34)
    said = set()
    for trial in range(300):
        blocks, signers = [], []
        for k in range(int(rng.integers(1, 9))):
            powers = rng.integers(1, 50, 12).tolist()
            here = sorted(rng.choice(12, int(rng.integers(6, 13)), replace=False).tolist())
            blocks.append({"height": 2 + k, "rows": len(here), "powers": powers,
                           "total_power": sum(powers), "link_ok": bool(rng.random() > 0.1)})
            signers += here
        mask = (rng.random(len(signers)) >= [0.0, 0.05, 0.3, 0.6][trial % 4]).tolist()
        got = shipped(mask, signers, [1] * 12, 12, blocks)
        assert got == fixture(mask, signers, [1] * 12, 12, blocks)
        said.add(got.split("#")[0])
    assert said == {"accepted", "broken link at block ", "not enough power at block "}
    src = open(os.path.join(HERE, "references", "sequential_run.py")).read()
    assert "import" not in src.split('"""')[2]  # the rule alone: nothing of the program


# -- the driver on a rehearsal


def run_light(control: str, seed: int, rows: int = 4, seconds: float = 1.0) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu", TMTPU_CRYPTO_BACKEND="cpu")
    cmd = [sys.executable, os.path.join(HERE, "run.py"), "--workload", LIGHT_CELL,
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


def failing_light(out: dict) -> set:
    return {k for k, (v, limit) in out["checks"].items() if v > limit}


@pytest.mark.parametrize("seed", [34, 3_000_000_434])
def test_the_light_driver_keeps_the_protocol(seed):
    out = run_light("", seed)
    assert out["correct"] is True and not failing_light(out), out["checks"]
    assert all(v == 0 for v, _ in out["checks"].values())
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert out["rows_per_call"] == 333 * 4
    assert out["notes"]["rows_compared"] == 3 * 333 * 4 and out["notes"]["probes"] == 8
    said = out["notes"]["entry_probes"]
    assert set(said) == {"short_power", "invalid_power", "broken_link"}
    for label, v in said.items():
        assert v["got"] == v["want"], (label, v)
        words = "broken link at block #" if label == "broken_link" else "not enough power at block #"
        assert v["want"].startswith(words)
    assert out["flush"]["backend"] == "cpu"


def test_the_control_on_the_light_drivers_own_path_is_not_correct():
    out = run_light("unsent_third", 35)
    assert out["correct"] is False
    assert out["checks"]["probes_accepted"][0] >= 2  # two whole strata lie in the last third
    assert {"probes_accepted"} <= failing_light(out) <= {"probes_accepted",
                                                         "entry_verdict_mismatch"}
    assert out["checks"]["flush_off_path"][0] == 0 and out["failed"] == 0


def test_every_header_against_the_roots_set_fails_every_call():
    out = run_light("roots_set", 36)
    assert out["correct"] is False
    assert {"verdict_mismatch", "rows_valid_short"} <= failing_light(out)
    assert out["checks"]["verdict_mismatch"][0] == out["attempted"] == out["failed"]


def test_links_not_checked_accepts_the_broken_link():
    out = run_light("links_unchecked", 37)
    assert out["correct"] is False and failing_light(out) == {"entry_verdict_mismatch"}
    said = out["notes"]["entry_probes"]
    assert said["broken_link"]["got"] == "accepted" != said["broken_link"]["want"]
    assert all(said[k]["got"] == said[k]["want"] for k in ("short_power", "invalid_power"))
    assert out["failed"] == 0  # the window's own runs are linked


def test_only_the_runs_first_header_verified_accepts_the_probes():
    out = run_light("first_header_only", 38)
    assert out["correct"] is False
    # the first header is a 333rd of the rows: of the 8 strata it holds part of one
    assert out["checks"]["probes_accepted"][0] >= 7
    assert {"probes_accepted"} <= failing_light(out) <= {"probes_accepted",
                                                         "entry_verdict_mismatch"}
    assert out["notes"]["entry_probes"]["broken_link"]["got"].startswith("broken link")
    assert out["failed"] == 0


def test_powers_of_the_wrong_height_refuse_the_sound_chain():
    out = run_light("roots_powers", 39)
    assert out["correct"] is False
    assert {"verdict_mismatch"} <= failing_light(out) <= {"verdict_mismatch",
                                                          "entry_verdict_mismatch"}
    assert out["checks"]["verdict_mismatch"][0] == out["attempted"] == out["failed"]
    # two of the root's four keys are gone two heights on: no more than 2/3 of its power is left
    assert out["checks"]["rows_valid_short"][0] == 0  # every row was verified, and valid


SEVERAL_FLUSHES = r'''
import os, sys
sys.path[:0] = [os.getcwd(), os.path.join(os.getcwd(), "benchmark")]
os.environ["TMTPU_CRYPTO_BACKEND"] = "cpu"
import data, spec
from tendermint_tpu.light import client
cell = spec.Cell(spec.load_benchmark(), "light-seq-100.sequence")
traffic = dict(cell.traffic, commits_per_call=6)
vals = data.make_validators(34, cell.config, 4)
ring = data.make_ring(34, cell.config, traffic, vals)
entry = cell.entry()
entry.configure(traffic)
state = entry.build(cell.config, vals, ring)
client.verify_run_rows = lambda: int(sys.argv[1])
print(entry.call(state, 0), entry.call(state, 1))
'''


@pytest.mark.parametrize("bound, flushes", [(8, 3), (4, 6), (24, 1)])
def test_a_client_that_makes_several_flushes_a_call_ends_at_its_first_call(bound, flushes):
    """The parent of the PR that added the cell verifies a header a flush, on
    the host: the driver ends such a run at the first warm-up call, with no
    result, on the count of flushes it saw (here: the run bound lowered, so a
    call of 6 headers of 4 rows spans several runs)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "-c", SEVERAL_FLUSHES, str(bound)], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    if flushes == 1:
        assert p.returncode == 0 and p.stdout.split() == ["accepted", "accepted"], p.stderr[-2000:]
    else:
        assert p.returncode != 0 and p.stdout == ""
        assert f"the first call made {flushes} flushes, not ONE" in p.stderr


# -- the eight readers

RECORD_LIGHT = r'''
import json, os, sys, types
sys.path[:0] = [os.getcwd(), os.path.join(os.getcwd(), "benchmark")]
os.environ["TMTPU_CRYPTO_BACKEND"] = "cpu"
import data, spec
from tendermint_tpu.libs import trace
bm = spec.load_benchmark()
cell = spec.Cell(bm, "light-seq-100.sequence")
traffic = dict(cell.traffic, commits_per_call=6)
vals = data.make_validators(34, cell.config, 4)
ring = data.make_ring(34, cell.config, traffic, vals)
entry = cell.entry()
entry.configure(traffic)
state = entry.build(cell.config, vals, ring)
names = [m["name"] for m in bm["per_layer"] if m.get("workloads") == [cell.name]]
calls = []
def read(rows, mix=traffic):
    ctx = types.SimpleNamespace(rows=rows, traffic=mix, calls=calls)
    return {n: cell.reader(n).read(ctx) for n in names}
out = {"empty": read(data.n_rows(ring[0]))}
trace.tracer.clear()
for k in range(int(sys.argv[1])):
    assert entry.call(state, k % 3) == "accepted"
    calls.append({"flush": entry.flush_reading()})
out["reading"] = {k: calls[-1]["flush"][k] for k in ("flushes", "rows", "rows_valid", "path")}
out["recorded"] = read(data.n_rows(ring[0]))
out["other_size"] = read(7)
out["no_root_stated"] = read(data.n_rows(ring[0]), {})
events = trace.tracer.dump()
out["roots"] = sum(e["name"] == "light.verify_run" for e in events)
out["events_a_call"] = len(events) / int(sys.argv[1])
print(json.dumps(out))
'''


def record_light(calls: int) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "-c", RECORD_LIGHT, str(calls)], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-2000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_each_light_reader_reads_a_recorded_ring_and_nothing_from_an_empty_one():
    out = record_light(36)
    assert out["roots"] == 36 and out["events_a_call"] <= 60
    assert out["empty"] == dict.fromkeys(LIGHT_OWN)
    got = out["recorded"]
    assert set(got) == set(LIGHT_OWN)
    assert got["light.client_ms"] is None  # calls without their clock readings
    for name in LIGHT_SPANS:
        assert isinstance(got[name], float) and 0 < got[name] < 1000, (name, got[name])
        assert out["other_size"][name] is None      # runs of another size are not the cell's
        assert out["no_root_stated"][name] is None  # a reader's root is the cell's mix's
    assert got["light.flushes_per_call"] == 1 and out["other_size"]["light.flushes_per_call"] is None
    assert out["reading"] == {"flushes": 1, "rows": 24, "rows_valid": 24, "path": "cpu"}
    # the host path writes no chunks and hides no prep: nothing to read, no number
    assert got["light.chunks_per_flush"] is None and got["light.prep_hidden_pct"] is None


def test_under_thirty_whole_light_calls_the_span_metrics_are_left_out():
    out = record_light(12)
    assert out["roots"] == 12
    assert {n: out["recorded"][n] for n in LIGHT_NEW[:6]} == dict.fromkeys(LIGHT_NEW[:6])


def test_the_streamed_flushs_counters_are_read_from_the_flush_record():
    """`light.chunks_per_flush` and `light.prep_hidden_pct` on readings as a
    streamed flush of three chunks leaves them (and on a one-chunk flush,
    whose prep nothing can hide)."""
    cell = spec.Cell(LIGHT_BM, LIGHT_CELL)

    def read(flushes):
        ctx = types.SimpleNamespace(calls=[{"flush": f} for f in flushes], rows=33300)
        return [cell.reader(n).read(ctx) for n in ("light.chunks_per_flush",
                                                   "light.prep_hidden_pct")]

    streamed = {"chunks": 3, "prep_ms": 40.0, "prep_overlap_ms": 25.0}
    assert read([streamed] * 5) == [3, 62.5]
    assert read([streamed, dict(streamed, prep_overlap_ms=30.0), streamed]) == [3, 62.5]
    assert read([{"chunks": 1, "prep_ms": 13.0, "prep_overlap_ms": 0.0}]) == [1, 0.0]
    assert read([{"chunks": None, "prep_ms": None, "prep_overlap_ms": None}]) == [None, None]
    assert read([]) == [None, None]


def test_a_light_readers_root_is_the_cells(monkeypatch):
    """The five span readers on a ring as the device path writes a run (one
    span a stage, children before their root): each reads its span under the
    root the mix states, and nothing under another cell's."""
    import program_spans

    def run_events(root: int, rows: int, t0: int) -> list:
        out = []
        at = 0.0
        for name, dur in [("light.fetch", 0.5), ("light.header_checks", 120.0),
                          ("light.gather", 12.0), ("light.sign_bytes", 48.0),
                          ("verify_batch", 95.0), ("light.tally", 3.0), ("light.store", 80.0)]:
            out.append({"name": name, "span": root + len(out) + 1, "root": root, "attrs": {},
                        "t0_ns": t0 + int(at * 1e6), "dur_ms": dur})
            at += dur
        out.append({"name": "light.verify_run", "span": root, "root": root, "t0_ns": t0,
                    "dur_ms": at, "attrs": {"rows": rows, "verdict": "accepted", "flushes": 1}})
        return out

    cell = spec.Cell(LIGHT_BM, LIGHT_CELL)
    events = [e for k in range(40) for e in run_events(1000 * (k + 1), 33300, k * 10**9)]
    monkeypatch.setattr(program_spans, "ring", lambda: events)

    def read(mix, rows=33300):
        ctx = types.SimpleNamespace(rows=rows, traffic=mix, calls=[])
        return [cell.reader(n).read(ctx) for n in LIGHT_SPANS + ["light.flushes_per_call"]]

    assert read(SEQUENCE_MIX) == [120.0, 12.0, 48.0, 3.0, 80.0, 1]
    catchup = spec.load_json(os.path.join(HERE, "traffic", "catchup.json"))
    assert read(catchup)[:5] == [None] * 5 and read(SEQUENCE_MIX, rows=7) == [None] * 6
    # and an accepted reader of the same ring, once a benchmark PR lists the cell under it
    ctx = types.SimpleNamespace(rows=33300, traffic=SEQUENCE_MIX)
    assert cell.reader("flush.record_ms").read(ctx) is None  # this ring holds no flush.record
