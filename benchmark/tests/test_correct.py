"""`correct` has been shown to fail. Each test drives a whole run of run.py
(data from a seed, warm-up, window, the comparison with the plain reference)
at a size a test run can hold, on the program's host backend, skipping only
the look for a chip, with a stand-in for the program's verifier underneath
the entry:

- the reference itself: correct;
- the control (controls.light: VerifyCommitLight's rule where VerifyCommit
  is stated, the last third of the rows taken unseen): not correct;
- half of the batch left out: not correct;
- one answer altered where it is produced: not correct;

and with the program itself underneath, its own path broken:

- the control on the program's path (the entry driver's unsent_third: each
  combined check gets a valid row in the place of every row of the last
  third): not correct;
- a combined check that answers None to everything: not correct, and not
  taken for refusing the probes.

Run: python -m pytest benchmark/tests -q   (or python benchmark/selftest.py)"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
CELL = "commit-1024.verify-commit"


def run(control: str, seed: int, rows: int = 96) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu", TMTPU_CRYPTO_BACKEND="cpu")
    cmd = [sys.executable, os.path.join(HERE, "run.py"), "--workload", CELL,
           "--seed", str(seed), "--seconds", "0.5", "--trace", "0",
           "--rehearse", str(rows)]
    if control:
        cmd += ["--control", control]
    p = subprocess.run(cmd, cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-2000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["device"]["platform"] == "cpu" and out["metrics"] == {}
    assert list(out)[-1] == "checks"  # the numbers compared come last in the line
    for name, (value, limit) in out["checks"].items():
        assert f"check {name} {value} limit {limit}" in p.stderr
    return out


def failing(out: dict) -> set:
    return {k for k, (v, limit) in out["checks"].items() if v > limit}


@pytest.mark.parametrize("control", ["", "sound"])
def test_what_is_right_is_correct(control):
    out = run(control, seed=2_200_000_001)
    assert out["correct"] is True and not failing(out), out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0


@pytest.mark.parametrize("control", ["light", "unsent_third"])
@pytest.mark.parametrize("seed", [5, 2_147_483_900, 3_000_000_019])
def test_the_control_is_not_correct(control, seed):
    out = run(control, seed)
    assert out["correct"] is False
    # the last third of the rows holds two whole strata of the 8 probes
    assert out["checks"]["probes_accepted"][0] >= 2
    # and the commit tampered through the entry, where its row was drawn there
    assert {"probes_accepted"} <= failing(out) <= {"probes_accepted", "entry_verdict_mismatch"}
    if control == "unsent_third":  # the program itself ran, where it should
        assert out["checks"]["flush_off_path"][0] == 0 and out["failed"] == 0


def test_a_check_that_declines_everything_refuses_nothing():
    out = run("declines", seed=9)
    assert out["correct"] is False
    assert out["checks"]["clean_declined"][0] == 4  # every ring commit, untampered
    assert "clean_declined" in failing(out)


def test_half_of_the_batch_left_out_is_not_correct():
    out = run("half", seed=7)
    assert out["correct"] is False
    assert out["checks"]["probes_accepted"][0] == 4
    assert {"probes_accepted"} <= failing(out) <= {"probes_accepted", "entry_verdict_mismatch"}


def test_an_altered_answer_is_not_correct():
    out = run("altered", seed=8)
    assert out["correct"] is False
    assert {"verdict_mismatch", "mask_mismatch", "entry_verdict_mismatch"} <= failing(out)
    assert out["failed"] == out["attempted"]  # wrong verdicts are failed operations
