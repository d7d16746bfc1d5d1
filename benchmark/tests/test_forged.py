"""Room for a cell of forged commits, proven with no such cell. The mix
may seat each forged row (`tampered_seats`: a share of the item's rows, the
same row under every seed); a configuration may state the recovery ladder's
contract (`expect_recovery`), to which every forged call of the window is held
under `flush_off_path`. The fixture `forge-128.forged-8` (128 validators of
equal power, a ring of 8 commits, items 3 and 7 forged at seats 0.25 and
0.9999, the accepted driver `verify_commit`) is laid into a copy of this
directory and rehearsed, sound and with its recovery taken off the ladder.

Run: python -m pytest benchmark/tests -q   (or python benchmark/selftest.py)"""

from __future__ import annotations

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

import data  # noqa: E402
import reference  # noqa: E402
import run as harness  # noqa: E402
import selftest  # noqa: E402
import spec  # noqa: E402

FIXTURES = os.path.join(HERE, "tests", "fixtures")
FORGE = spec.load_json(os.path.join(FIXTURES, "configs", "forge-128.json"))
FORGED_MIX = spec.load_json(os.path.join(FIXTURES, "traffic", "forged-8.json"))
SKEWED = spec.load_json(os.path.join(FIXTURES, "configs", "skewed-48.json"))
WINDOW = spec.load_json(os.path.join(FIXTURES, "traffic", "window-6.json"))
SEEDS = [5, 2_147_483_911, 3_000_000_402, 2**32 + 7]
CHIP_CONTRACT = {"backend": "jax", "paths": ["rlc-bisect"], "max_recovery_flushes": 11}


# -- the seat: one row under every seed


def forged_rows(config: dict, traffic: dict, seed: int) -> list:
    """(ring index, position of the bad row among the item's rows, which half
    of the signature was flipped) of each forged item, checked against the
    reference: exactly one row of the item fails, and re-signed it differs
    from the forged signature in one byte."""
    vals = data.make_validators(seed, config)
    ring = data.make_ring(seed, config, traffic, vals)
    out = []
    for j, item in enumerate(ring):
        if not any(c.tampered for c in data.commits_of(item)):
            continue
        idx, pks, msgs, sigs = data.rows_of(config, vals, item)
        bad = [i for i, ok in enumerate(reference.verify_rows(pks, msgs, sigs)) if not ok]
        assert len(bad) == 1, (j, bad)
        (at,) = bad
        honest = vals.privs[idx[at]].sign(msgs[at])
        (byte,) = [k for k in range(64) if honest[k] != sigs[at][k]]
        out.append((j, at, {7: "R", 33: "s"}[byte]))
    return out


@pytest.mark.parametrize("share", [0.0, 0.25, 0.5, 0.9999])
def test_a_seated_forged_row_lands_at_the_same_row_under_every_seed(share):
    """One commit an item, and an item of six commits with absentees drawn
    afresh in every commit: the row's position among the item's rows is
    floor(share * rows) under every seed, and R and s alternate."""
    for config, mix, rows in ((dict(FORGE, validators=40), FORGED_MIX, 40),
                              (SKEWED, dict(WINDOW, tampered_one_in=2), 276)):
        seated = dict(mix, tampered_seats=[share])
        got = {seed: forged_rows(config, seated, seed) for seed in SEEDS}
        want = [(j, int(share * rows), "Rs"[k % 2]) for k, j in enumerate(
            range(mix["tampered_one_in"] - 1, mix["ring_commits"], mix["tampered_one_in"]))]
        assert all(v == want for v in got.values()), (share, got)
    # equal power and nobody absent: the same validator, too
    vals = data.make_validators(SEEDS[0], dict(FORGE, validators=40))
    ring = data.make_ring(SEEDS[0], dict(FORGE, validators=40),
                          dict(FORGED_MIX, tampered_seats=[share]), vals)
    assert [c.tampered for c in ring if c.tampered] == [(int(share * 40),)] * 2


def test_seats_are_cycled_over_the_forged_items_and_drawn_rows_are_not_seated():
    vals = data.make_validators(7, FORGE)
    got = forged_rows(FORGE, dict(FORGED_MIX, ring_commits=16, tampered_one_in=4,
                                  tampered_seats=[0.0, 0.5, 0.9999]), 7)
    assert got == [(3, 0, "R"), (7, 64, "s"), (11, 127, "R"), (15, 0, "s")]
    drawn = {forged_rows(FORGE, dict(FORGED_MIX, tampered_seats=None), seed)[0][1]
             for seed in SEEDS}
    assert len(drawn) > 1  # without seats the row is the seed's draw, as it was
    assert len(vals.pubkeys) == 128


# -- the lint


def forged_lint(tmp_path, config=None, traffic=None) -> list:
    bm, here = selftest.add_forged_cell(str(tmp_path))
    for kind, name, change in (("configs", "forge-128", config), ("traffic", "forged-8", traffic)):
        path = os.path.join(here, kind, name + ".json")
        body = spec.load_json(path)
        for k, v in (change or {}).items():
            if isinstance(v, dict) and isinstance(body.get(k), dict):
                body[k] = {kk: vv for kk, vv in dict(body[k], **v).items() if vv is not None}
            elif v is None:
                body.pop(k)
            else:
                body[k] = v
        with open(path, "w") as f:
            json.dump(body, f)
    return spec.lint(bm, str(tmp_path), here)


@pytest.mark.parametrize("change,says", [
    ({"traffic": {"warmup_calls": 7}}, "warmup_calls 7 under ring_commits 8"),
    ({"traffic": {"tampered_seats": [0.5, 1.0]}}, "is not a list of shares in [0, 1)"),
    ({"traffic": {"tampered_seats": [-0.25]}}, "is not a list of shares in [0, 1)"),
    ({"traffic": {"tampered_seats": []}}, "is not a list of shares in [0, 1)"),
    ({"config": {"expect_recovery": {"paths": None}}}, "expect_recovery has no paths"),
    ({"config": {"expect_recovery": {"paths": []}}}, "expect_recovery has no paths"),
])
def test_the_lint_refuses_a_forged_mix_or_contract(tmp_path, change, says):
    faults = forged_lint(tmp_path, **change)
    assert len(faults) == 1 and says in faults[0], faults


def test_the_lint_passes_the_forged_fixture_and_a_mix_that_forges_nothing(tmp_path):
    assert forged_lint(tmp_path / "as_it_is") == []
    # a short warm-up is the mix's own business where nothing is forged
    assert forged_lint(tmp_path / "forging_nothing",
                       traffic={"tampered_one_in": 0, "warmup_calls": 3}) == []


# -- the fixture through run.py, sound and with its recovery off the ladder


@pytest.fixture(scope="module")
def forged_copy(tmp_path_factory):
    tmp = str(tmp_path_factory.mktemp("forged"))
    bm, here = selftest.add_forged_cell(tmp)
    assert spec.lint(bm, tmp, here) == []
    return tmp, here


def run_forged(forged_copy, seed: int, control: str = "") -> dict:
    p = selftest.run_forged_cell(*forged_copy, seed=seed, control=control)
    assert p.returncode == 0, p.stderr[-2000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["device"]["platform"] == "cpu" and out["metrics"] == {}
    assert list(out)[-1] == "checks"
    for name, (value, limit) in out["checks"].items():
        assert f"check {name} {value} limit {limit}" in p.stderr
    return out


@pytest.mark.parametrize("seed", [40, 3_000_000_440])
def test_the_forged_cell_rehearsed_sound_is_correct(forged_copy, seed):
    out = run_forged(forged_copy, seed)
    assert out["correct"] is True and all(v == 0 for v, _ in out["checks"].values()), out["checks"]
    assert out["failed"] == 0 and out["rows_per_call"] == 128
    notes = out["notes"]
    # a quarter of the ring is forged; on the host backend the ladder is 2 or 3
    # flushes, and none where the flipped R is no point (its row drops out of
    # the combined check): item 7's bit of s always reaches the sum
    assert 0 < notes["forged_calls"] <= out["attempted"] // 4 + 1
    assert notes["recovery_flushes_p50"] <= notes["recovery_flushes_max"] in (2, 3)


def test_a_forged_call_answered_off_the_ladder_is_not_correct(forged_copy):
    """The planted fault `recovery_off_ladder`: a straight per-signature pass on
    the host names the right index, so only the recovery contract sees it."""
    out = run_forged(forged_copy, 41, "recovery_off_ladder")
    assert out["correct"] is False
    assert {k for k, (v, limit) in out["checks"].items() if v > limit} == {"flush_off_path"}
    notes = out["notes"]
    # every call on item 7 (a bit of s: its combined check fails) at least
    assert notes["forged_calls"] >= out["checks"]["flush_off_path"][0] == out["failed"]
    assert out["failed"] >= notes["forged_calls"] // 2 >= 1
    assert notes["first_off_path"] == "no recovery ladder walked"
    assert notes["recovery_flushes_max"] == 0


# -- the contract on handmade readings


def test_the_recovery_contract_on_handmade_readings():
    entry = spec.load_module(spec.module_path(HERE, "entries", "verify_commit"))
    ladder = {"backend": "jax", "path": "rlc-bisect", "jax_path": "rlc-bisect", "fused": True,
              "rlc_fallback": True, "recovery_flushes": 9, "rows": 10000, "compile_ms": 0.0}

    def fault(**changed):
        return entry.recovery_fault(dict(ladder, **changed), CHIP_CONTRACT, 10000)

    assert fault() is None and fault(recovery_flushes=11, compile_ms=None) is None
    # `_bisect_recover_host` in the place of the device's ladder
    assert fault(backend="cpu", path="cpu", jax_path="rlc-pipelined",
                 recovery_flushes=3) == "recovery on backend 'cpu'"
    # a straight per-signature pass over all the rows (TMTPU_BISECT=0 on the chip)
    assert fault(path="persig", jax_path="persig", recovery_flushes=1) == "recovery on path 'persig'"
    assert fault(jax_path="rlc-pipelined") == "recovery jax path 'rlc-pipelined' under 'rlc-bisect'"
    assert fault(recovery_flushes=None) == fault(recovery_flushes=0) == "no recovery ladder walked"
    # the host's combined check, which dropped a row of an invalid encoding, answered alone
    host = dict(ladder, backend="cpu", path="cpu", rlc_fallback=False, recovery_flushes=None)
    rehearsal = dict(CHIP_CONTRACT, backend="cpu", paths=["cpu"])
    assert entry.recovery_fault(dict(host, host_rlc=True), rehearsal, 10000) is None
    assert entry.recovery_fault(host, rehearsal, 10000) == "no recovery ladder walked"
    assert fault(recovery_flushes=12) == "12 recovery flushes"
    assert fault(compile_ms=208000.0) == "compiled 208000 ms in the recovery"
    assert fault(rows=5000) == "5000 rows flushed"
    # run.py: no contract stated, nothing held; a driver that cannot judge one, a fault
    assert harness.recovery_fault(entry, dict(ladder, path="cpu"), None, 10000) is None
    assert harness.recovery_fault(object(), ladder, CHIP_CONTRACT, 10000) == \
        "the entry driver has no recovery_fault"
    assert harness.recovery_fault(entry, ladder, CHIP_CONTRACT, 10000) is None
