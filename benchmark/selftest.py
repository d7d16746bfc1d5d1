#!/usr/bin/env python3
"""Checks of the benchmark itself, on the CPU, in a few minutes. Its tests/
are also collected into the repo's tier-1 (tests/test_benchmark_selftests.py).
    python benchmark/selftest.py

1. BENCHMARK.json is within the contract's limits and every file it names
   by name exists (spec.lint).
2. The percentile and rate arithmetic: a planted stall moves the rate and
   the 95th percentile and leaves the median.
3. work.py counts from the row count alone.
4. The trace reduction, on handmade intervals and on the recorded slices of
   chip runs under testdata/.
5. Off a TPU the command fails with no result line; so it does where the
   program is missing.
6. A later PR adds a cell as files and entries only: a throwaway cell of
   another shape than the accepted ones (a call of 6 commits, a skewed stake
   with absent signers, a verdict rule and an entry driver of its own, from
   tests/fixtures/, and a per-layer metric) in a copy of this directory, run
   (rehearsed on the host) without a line of the harness changed.
7. tests/: `correct` fails the control and the planted faults, of the
   accepted cells (test_correct.py, test_hub175.py), of the windowed fixture
   (test_room.py) and of the chain fixture (test_chain.py: a header and a
   validator set of its own per height, the `broken_link` probe), whose
   generator is also held against the program's own hashes and light client.
"""

from __future__ import annotations

import glob
import inspect
import json
import os
import shutil
import subprocess
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

import spec  # noqa: E402
import stats  # noqa: E402
import tracing  # noqa: E402
import work  # noqa: E402


def check(cond, what):
    if not cond:
        raise AssertionError(what)
    print("ok  ", what)


def test_lint():
    bm = spec.load_benchmark(ROOT)
    check(spec.lint(bm, ROOT, HERE) == [], "BENCHMARK.json passes the lint")
    broken = json.loads(json.dumps(bm))
    broken["per_layer"][0]["moves"] = "no_such_metric"
    broken["end_to_end"][0]["unit"] = "sigs per second"
    faults = spec.lint(broken, ROOT, HERE)
    check(len(faults) >= 2, f"the lint finds planted faults ({len(faults)})")
    check(os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024, "under 64 KiB")


def test_arithmetic():
    check(stats.percentile([1, 2, 3, 4, 5], 50) == 3 and stats.percentile([0, 10], 95) == 9.5,
          "percentile interpolates between closest ranks")
    steady = [(i * 0.1, i * 0.1 + 0.1, True) for i in range(200)]
    stalled, t = [], 0.0
    for i in range(200):
        wall = 1.0 if i in range(90, 102) else 0.1  # 12 of 200 calls stall
        stalled.append((t, t + wall, True))
        t += wall
    def credited(calls, rows):
        return [(s, e, rows * ok) for s, e, ok in calls]

    a, b = (stats.window_metrics(credited(c, 1000)) for c in (steady, stalled))
    check(abs(a["sigs_per_s"] - 10000) < 1e-6, "the rate is work over the whole window")
    check(b["sigs_per_s"] < 0.7 * a["sigs_per_s"], "a stall lowers the rate")
    check(b["verify_ms_p95"] > 5 * a["verify_ms_p95"], "a stall raises the 95th percentile")
    check(abs(b["verify_ms_p50"] - a["verify_ms_p50"]) < 1e-6, "and leaves the median")
    wrong = [(s, e, i % 2 == 0) for i, (s, e, _) in enumerate(steady)]
    check(abs(stats.window_metrics(credited(wrong, 1000))["sigs_per_s"] - 5000) < 1e-6,
          "a call that came back wrong adds no signatures and keeps its time")
    unequal = [(s, e, 276 if i % 2 else 230) for i, (s, e, _) in enumerate(steady)]
    check(abs(stats.window_metrics(unequal)["sigs_per_s"] - 2530) < 1e-6,
          "a call is credited the signatures of its own item")
    check(abs(stats.spread([1, 2, 3, 4, 5, 6]) - 3.5 / 3.5) < 1e-9,
          "spread is the quartile distance by statistics.quantiles over the median")


def test_work():
    for fn in (work.flush_bytes, work.flush_field_muls):
        check(list(inspect.signature(fn).parameters) == ["rows"],
              f"work.{fn.__name__} takes the row count alone")
    check(list(inspect.signature(work.least_seconds).parameters) == ["rows", "peaks"],
          "work.least_seconds takes rows and the chip's peaks")
    src = inspect.getsource(work)
    check("import" not in src.replace("from __future__ import annotations", ""),
          "work.py imports nothing: no bucket or layout of the program can enter")
    check(work.flush_field_muls(10000) < 10 * work.flush_field_muls(1000)
          and work.flush_bytes(10000) == 10 * work.flush_bytes(1000),
          "bytes grow with the rows, multiplications a little slower (wider windows)")
    with open(os.path.join(HERE, "peaks.json")) as f:
        peaks = json.load(f)
    for kind, p in peaks.items():
        check({"hbm_bytes_per_s", "int8_ops_per_s", "source"} <= set(p), f"peaks of {kind} name their source")
        secs, bound = work.least_seconds(10000, p)
        check(0 < secs < 1e-3, f"least time of a 10,000-row flush on {kind}: {secs:.3e} s ({bound})")


def test_reduction():
    dev = [("/device:TPU:0", [(100, 200, "k1"), (150, 300, "k2"), (500, 600, "k1")])]
    spans = [(0, 1000, "slice"), (50, 700, "call"), (80, 650, "flush")]
    r = tracing.reduce(dev, spans)
    check(r["busy_s"] == 300e-9 and r["window_s"] == 1000e-9, "busy is the union, not the sum")
    check(dict(map(tuple, r["device_ops"]))["k1"] == 200e-9, "seconds by operation")
    gaps = dict(map(tuple, r["idle_gaps"]))
    check(abs(sum(gaps.values()) - 700e-9) < 1e-15, "the gaps fill the rest of the window")
    check(gaps["flush: between device operations (dispatch, next chunk's prep)"] == 200e-9
          and gaps["entry: sign bytes and row gathering, before the flush"] == 30e-9,
          "gaps are named by what the host was doing")
    check(r["device_s_per_call"] == [300e-9], "device time of a call")
    def plane(name: bytes, rest: bytes = b"") -> bytes:  # XSpace.planes = 1; XPlane.id = 1, name = 2
        body = b"\x08\x07\x12" + bytes([len(name)]) + name + rest
        return b"\x0a" + bytes([len(body)]) + body

    kept = plane(b"/device:TPU:0", b"\x1a\x02\x08\x01") + b"\x22\x03abc"  # and XSpace.errors = 4
    check(tracing.without_hlo(plane(b"/host:metadata", b"\x22\x01x") + kept) == kept,
          "a kept slice loses its /host:metadata plane (the programs' HLO) and nothing else")
    recorded = sorted(glob.glob(os.path.join(HERE, "testdata", "*.xplane.pb.gz")))
    check(recorded, "a recorded slice of a chip run is kept under testdata/")
    for path in recorded:
        with open(path[: -len(".xplane.pb.gz")] + ".expect.json") as f:
            want = json.load(f)["expect"]
        got = tracing.reduce(*tracing.load_xplane(path))
        name = os.path.basename(path)
        for k, v in want.items():
            check(json.dumps(got[k]) == json.dumps(v), f"{name}: {k} = {v}")
        check(0 < got["busy_s"] <= got["window_s"], f"{name}: 0 < busy <= window")
        check(abs(sum(s for _, s in got["idle_gaps"]) + got["busy_s"] - got["window_s"]) < 1e-9,
              f"{name}: busy and the named gaps fill the slice")


def _run(cmd, env=None, cwd=ROOT):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env or {}))
    return subprocess.run(cmd, cwd=cwd, env=env, capture_output=True, text=True, timeout=600)


def test_fails_off_the_chip():
    p = _run([sys.executable, os.path.join(HERE, "run.py"), "--workload",
              "commit-1024.verify-commit", "--seed", "1", "--seconds", "1", "--trace", "0"])
    check(p.returncode != 0 and p.stdout.strip() == "" and "not a TPU" in p.stderr,
          "without a TPU the command fails and prints no result")


THROWAWAY_READER = '''"""Throwaway reader: the latest a call was started after it was due."""


def read(ctx):
    return max(c["late_s"] for c in ctx.calls) * 1e3
'''
WINDOWED_CELL = "skewed-48.window-6"


CHAIN_CELL = "chain-16.sequence-12"


def add_fixture_cell(tmp: str, config: str, traffic: str):
    """What a later PR does, in a copy of this directory under `tmp`: the
    files of tests/fixtures/ laid into configs/, traffic/, references/ and
    entries/, one reader, and four entries in BENCHMARK.json for the cell
    `<config>.<traffic>`. Returns the copy's BENCHMARK.json and its
    benchmark/ directory."""
    here = os.path.join(tmp, "benchmark")
    shutil.copytree(HERE, here, ignore=shutil.ignore_patterns("__pycache__", "testdata"))
    shutil.copytree(os.path.join(HERE, "tests", "fixtures"), here, dirs_exist_ok=True,
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(here, "layer_metrics", "late.max_ms.py"), "w") as f:
        f.write(THROWAWAY_READER)
    cell = f"{config}.{traffic}"
    bm = spec.load_benchmark(ROOT)
    bm["configs"].append({"name": config, "source": "selftest", "reduced": [],
                          "file": f"benchmark/configs/{config}.json", "why": "throwaway"})
    bm["workloads"].append({"name": cell, "config": config, "traffic": traffic, "chips": 1,
                            "why": "throwaway"})
    bm["per_layer"].append({"name": "late.max_ms", "unit": "ms", "better": "lower",
                            "source": "host_clock", "layer": "entry points",
                            "moves": "verify_ms_p50", "workloads": [cell]})
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(bm, f)
    return bm, here


def add_windowed_cell(tmp: str):
    """The windowed, skewed fixture (PR 28) as the cell WINDOWED_CELL."""
    return add_fixture_cell(tmp, *WINDOWED_CELL.split("."))


def add_chain_cell(tmp: str):
    """The fixture chain (a header and a validator set of its own per height)
    as the cell CHAIN_CELL."""
    return add_fixture_cell(tmp, *CHAIN_CELL.split("."))


def run_fixture_cell(tmp: str, here: str, cell: str, rows: int, seed: int, control: str = "",
                     seconds: float = 1.0):
    cmd = [sys.executable, os.path.join(here, "run.py"), "--workload", cell, "--seed", str(seed),
           "--seconds", str(seconds), "--trace", "0", "--rehearse", str(rows)]
    return _run(cmd + (["--control", control] if control else []),
                env={"PYTHONPATH": ROOT}, cwd=tmp)


def run_windowed_cell(tmp: str, here: str, seed: int, control: str = "", seconds: float = 1.0):
    return run_fixture_cell(tmp, here, WINDOWED_CELL, 48, seed, control, seconds)


def test_add_a_cell_as_data():
    with tempfile.TemporaryDirectory() as tmp:
        # what a later PR adds: five files and four entries
        bm, here = add_windowed_cell(tmp)
        check(spec.lint(bm, tmp, here) == [], "the copy with the added cell passes the lint")
        changed = [f for f in sorted(os.listdir(HERE)) if f.endswith(".py")
                   and open(os.path.join(HERE, f)).read() != open(os.path.join(here, f)).read()]
        check(changed == [], "and the added files replace no file of the harness")
        p = _run([sys.executable, os.path.join(here, "run.py"), "--workload",
                  "commit-1024.verify-commit", "--seed", "1", "--seconds", "1", "--trace", "0",
                  "--rehearse", "48"], cwd=tmp)
        check(p.returncode != 0 and p.stdout.strip() == "",
              "with only BENCHMARK.json and benchmark/ the command fails, no result")
        p = run_windowed_cell(tmp, here, seed=2500000000)
        check(p.returncode == 0, "the added cell runs (rehearsed): " + p.stderr[-300:])
        out = json.loads(p.stdout.strip().splitlines()[-1])
        check(out["correct"] is True and out["failed"] == 0 and out["attempted"] > 0,
              "a call of 6 commits under a skewed stake is judged by the cell's own rule: correct")
        check(out["rows_per_call"] == 6 * 46,
              "a call is credited the rows of its own item: 6 commits of 46 signers")
        said = out["notes"]["entry_probes"]
        check(sorted(said) == ["invalid_power", "short_power"]
              and all(v["want"] == v["got"] != "accepted" for v in said.values()),
              f"both entry probes are refused in the rule's words ({said['short_power']['want']})")
        cell = spec.Cell(bm, WINDOWED_CELL, tmp, here)
        check([m["name"] for m in cell.per_layer if "workloads" in m] == ["late.max_ms"],
              "the cell reports the added per-layer metric and none that names other cells")
        ctx = type("Ctx", (), {"calls": [{"late_s": 0.002}, {"late_s": 0.0005}]})()
        check(cell.reader("late.max_ms").read(ctx) == 2.0, "and its reader is found by name")
        # the mix's other paths, on the accepted driver: an open loop, tampered items, absence
        cfg = spec.load_json(os.path.join(here, "configs", "commit-1024.json"))
        cfg.update(name="tiny-48", validators=48, voting_power=7, absent_share=0.25,
                   chain_id="throwaway", source="selftest")
        with open(os.path.join(here, "configs", "tiny-48.json"), "w") as f:
            json.dump(cfg, f)
        mix = spec.load_json(os.path.join(here, "traffic", "verify-commit.json"))
        mix.update(name="open-tampered", loop="open", rate_per_s=25, tampered_one_in=2)
        with open(os.path.join(here, "traffic", "open-tampered.json"), "w") as f:
            json.dump(mix, f)
        bm["configs"].append({"name": "tiny-48", "source": "selftest", "reduced": [],
                              "file": "benchmark/configs/tiny-48.json", "why": "throwaway"})
        bm["workloads"].append({"name": "tiny-48.open-tampered", "config": "tiny-48",
                                "traffic": "open-tampered", "chips": 1, "why": "throwaway"})
        with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
            json.dump(bm, f)
        check(spec.lint(bm, tmp, here) == [], "a second added cell passes the lint")
        p = _run([sys.executable, os.path.join(here, "run.py"), "--workload",
                  "tiny-48.open-tampered", "--seed", "2500000000", "--seconds", "1.5",
                  "--trace", "0", "--rehearse", "48"], env={"PYTHONPATH": ROOT}, cwd=tmp)
        check(p.returncode == 0, "an open loop of tampered commits runs (rehearsed): " + p.stderr[-300:])
        out = json.loads(p.stdout.strip().splitlines()[-1])
        check(out["correct"] is True and out["failed"] == 0,
              "its tampered commits are refused as the reference refuses them")
        check(30 <= out["attempted"] <= 40, f"its open loop kept its rate ({out['attempted']} calls)")


def test_correct_fails_what_is_wrong():
    p = _run([sys.executable, "-m", "pytest", os.path.join(HERE, "tests"), "-q",
              "-p", "no:cacheprovider"])
    check(p.returncode == 0, "tests/: " + p.stdout.strip().splitlines()[-1])


def main() -> int:
    for t in (test_lint, test_arithmetic, test_work, test_reduction, test_fails_off_the_chip,
              test_add_a_cell_as_data, test_correct_fails_what_is_wrong):
        print(f"-- {t.__name__}")
        t()
    print("selftest: all passed")
    return 0


if __name__ == "__main__":
    sys.exit(main())
