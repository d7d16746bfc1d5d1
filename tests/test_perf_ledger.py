"""Tier-1 guard for the perf-trajectory ledger (tools/perf_ledger.py).

Two jobs: (1) the ledger must parse every SHAPE of round artifact the driver
has ever written — a parseless wrapper, a `value: -1` device-init stall, a
healthy round, a skipped and an rc-124 multichip round — without error, and
flag the lost datapoints instead of silently skipping them; (2) `--check`
must exit nonzero on a simulated headline regression, in the spirit of
tests/test_hotpath_guard.py. The rounds are synthetic, written into
tmp_path: the repo root keeps no old round records."""

import glob
import json
import os

import pytest

from tendermint_tpu.tools import perf_ledger as PL

_CMD = "if [ -f bench.py ]; then python bench.py; else exit 0; fi"


def _wrapper(d, n, parsed, tail=""):
    (d / f"BENCH_r{n:02d}.json").write_text(
        json.dumps({"n": n, "cmd": _CMD, "rc": 0, "tail": tail, "parsed": parsed})
    )


def _multichip(d, n, rc=0, ok=False, skipped=False, tail=""):
    (d / f"MULTICHIP_r{n:02d}.json").write_text(
        json.dumps(
            {"n_devices": 8, "rc": rc, "ok": ok, "skipped": skipped, "tail": tail}
        )
    )


@pytest.fixture
def rounds(tmp_path):
    """One synthetic round per case the ledger must survive."""
    _wrapper(tmp_path, 1, None)  # wrapper with parsed: null
    _wrapper(
        tmp_path, 4,
        {"metric": "verify_commit_10k_latency", "value": 273.205, "unit": "ms",
         "vs_baseline": 4.23, "extra": {}},
        tail="[vote_storm] serial ...",
    )  # a healthy round
    _wrapper(
        tmp_path, 5,
        {"metric": "verify_commit_latency", "value": -1, "unit": "ms",
         "vs_baseline": 0, "extra": {}},
        tail="[init] device initialization stalled",
    )  # value -1: the device-init stall that cost a whole round
    _multichip(tmp_path, 1, skipped=True, tail="__GRAFT_DRYRUN_SKIP__\n")
    _multichip(tmp_path, 3, ok=True, tail="dryrun_multichip OK: all 32 sigs verified")
    _multichip(tmp_path, 4, rc=124)  # timeout
    return str(tmp_path)


def test_parses_every_round_artifact_shape(rounds):
    """Every BENCH_r*/MULTICHIP_r* file yields a ledger row
    (parse_bench/parse_multichip never raise by design — a malformed file
    becomes a flagged lost row)."""
    ledger = PL.load_ledger(rounds)
    on_disk = {
        os.path.basename(p)
        for pat in ("BENCH_r*.json", "MULTICHIP_r*.json")
        for p in glob.glob(os.path.join(rounds, pat))
    }
    assert len(on_disk) == 6
    rows = {r["file"] for r in ledger["bench"] + ledger["multichip"]}
    assert rows == on_disk
    for r in ledger["bench"] + ledger["multichip"]:
        assert isinstance(r["round"], int), r["file"]


def test_known_lost_datapoints_are_flagged(rounds):
    ledger = PL.load_ledger(rounds)
    lost = set(ledger["lost_datapoints"])
    # r01: wrapper with parsed: null (no parseable bench JSON)
    assert "BENCH_r01.json" in lost
    # r05: value -1 — the device-init stall that cost the whole round
    assert "BENCH_r05.json" in lost
    by_file = {r["file"]: r for r in ledger["bench"]}
    assert "no parseable" in by_file["BENCH_r01.json"]["lost_reason"]
    assert "-1" in by_file["BENCH_r05.json"]["lost_reason"]
    # healthy rounds are NOT flagged
    assert "BENCH_r04.json" not in lost


def test_multichip_diagnoses(rounds):
    ledger = PL.load_ledger(rounds)
    by_file = {r["file"]: r for r in ledger["multichip"]}
    assert by_file["MULTICHIP_r01.json"]["diagnosis"] == "skipped"
    assert "timeout" in by_file["MULTICHIP_r04.json"]["diagnosis"]  # rc-124
    assert by_file["MULTICHIP_r04.json"]["lost"]


def test_renders_full_trajectory(rounds, tmp_path, capsys):
    rc = PL.main([
        "--root", rounds,
        "--json", str(tmp_path / "ledger.json"),
        "--markdown", str(tmp_path / "ledger.md"),
    ])
    capsys.readouterr()
    assert rc == 0
    md = (tmp_path / "ledger.md").read_text()
    assert "| r01 |" in md and "LOST" in md
    assert "## Multichip rounds" in md
    doc = json.loads((tmp_path / "ledger.json").read_text())
    assert doc["lost_datapoints"] and doc["bench"] and doc["multichip"]


def _write_round(d, n, value, metric="verify_commit_10k_latency", rc=0,
                 degraded=None):
    parsed = {
        "metric": metric, "value": value, "unit": "ms", "vs_baseline": 2.0,
        "extra": {"host": {"machine_fingerprint": "test-host", "jax": "0.9"}},
    }
    if degraded:
        parsed["degraded"] = degraded
    (d / f"BENCH_r{n:02d}.json").write_text(
        json.dumps({"n": n, "rc": rc, "tail": "", "parsed": parsed})
    )


def test_check_exits_nonzero_on_simulated_headline_regression(tmp_path, capsys):
    _write_round(tmp_path, 1, 100.0)
    _write_round(tmp_path, 2, 200.0)  # 2x the best round: way past 25%
    rc = PL.main(["--root", str(tmp_path), "--check"])
    out = capsys.readouterr()
    assert rc == 2
    assert "REGRESSION" in out.err and "REGRESSIONS" in out.out
    # a loose enough tolerance passes the same data
    assert PL.main(["--root", str(tmp_path), "--check", "--tolerance", "1.5"]) == 0
    capsys.readouterr()


def test_check_ignores_lost_and_degraded_rounds(tmp_path, capsys):
    """A lost (value -1) or cpu-fallback round must not count as 'the newest
    headline' — the guard compares healthy device datapoints only."""
    _write_round(tmp_path, 1, 100.0)
    _write_round(tmp_path, 2, 110.0)
    _write_round(tmp_path, 3, -1)  # lost
    _write_round(tmp_path, 4, 900.0, degraded="cpu-fallback")
    rc = PL.main(["--root", str(tmp_path), "--check"])
    capsys.readouterr()
    assert rc == 0  # newest healthy (r02, 110ms) is within budget of r01
    ledger = PL.load_ledger(str(tmp_path))
    assert "BENCH_r03.json" in ledger["lost_datapoints"]


def test_host_stamp_lands_in_rows(tmp_path):
    _write_round(tmp_path, 7, 50.0)
    row = PL.load_ledger(str(tmp_path))["bench"][0]
    assert row["fingerprint"] == "test-host"
    assert row["versions"]["jax"] == "0.9"


def test_empty_root_errors(tmp_path, capsys):
    assert PL.main(["--root", str(tmp_path)]) == 1
    assert "no BENCH_r*" in capsys.readouterr().err


def test_salvaged_value_from_nonzero_rc(tmp_path):
    """A bench that printed its JSON and then exited nonzero keeps its value
    but flags the round (never silently trusted, never silently dropped)."""
    _write_round(tmp_path, 1, 75.0, rc=1)
    row = PL.load_ledger(str(tmp_path))["bench"][0]
    assert not row["lost"] and row["value"] == 75.0
    assert "rc=1" in row["lost_reason"]


def test_artifact_without_round_suffix_renders_not_crashes(tmp_path, capsys):
    """BENCH_rerun.json matches the glob but not the _r<NN> pattern: the
    ledger must label it by filename and keep going, not TypeError on
    formatting a None round (the contract is flag, never die)."""
    _write_round(tmp_path, 1, 100.0)
    (tmp_path / "BENCH_rerun.json").write_text(
        (tmp_path / "BENCH_r01.json").read_text()
    )
    (tmp_path / "MULTICHIP_rX.json").write_text(
        json.dumps({"n": 8, "rc": 0, "tail": ""})
    )
    ledger = PL.load_ledger(str(tmp_path))
    assert [r["round"] for r in ledger["bench"]] == [1, None]
    md = PL.render_markdown(ledger)
    assert "BENCH_rerun" in md and "MULTICHIP_rX" in md
    assert PL.main(["--root", str(tmp_path), "--check"]) == 0
    capsys.readouterr()
