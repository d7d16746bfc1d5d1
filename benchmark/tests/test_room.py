"""The room a windowed, skewed cell needs, proven with no such cell: an item
of several commits, a stake that the configuration states, a verdict rule
found by name. The fixture under fixtures/ (48 validators of skewed power, 5%
absent, calls of 6 commits, the rule `tally_valid_power`, a driver built on
reference.verify_rows alone) is laid into a copy of this directory as a later
PR would add it and run through run.py --rehearse, sound and with each fault
such a cell can have. And what must not have moved: the data of the accepted
configurations comes out of a seed byte for byte as before items existed, and
every mix without `tampered_seats` (the fixtures', and mixes that forge) as
before a mix could seat its forged rows.

Run: python -m pytest benchmark/tests -q   (or python benchmark/selftest.py)"""

from __future__ import annotations

import hashlib
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

import controls  # noqa: E402
import data  # noqa: E402
import reference  # noqa: E402
import selftest  # noqa: E402
import spec  # noqa: E402
import stats  # noqa: E402

FIXTURES = os.path.join(HERE, "tests", "fixtures")
SKEWED = spec.load_json(os.path.join(FIXTURES, "configs", "skewed-48.json"))
WINDOW = spec.load_json(os.path.join(FIXTURES, "traffic", "window-6.json"))
RULE = spec.load_module(os.path.join(FIXTURES, "references", "tally_valid_power.py")).verdict


# -- the fixture through run.py, sound and broken


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    tmp = str(tmp_path_factory.mktemp("room"))
    bm, here = selftest.add_windowed_cell(tmp)
    assert spec.lint(bm, tmp, here) == []
    return tmp, here


def run(copy, seed: int, control: str = "") -> dict:
    p = selftest.run_windowed_cell(*copy, seed=seed, control=control, seconds=0.5)
    assert p.returncode == 0, p.stderr[-2000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["device"]["platform"] == "cpu" and out["metrics"] == {}
    assert list(out)[-1] == "checks"
    for name, (value, limit) in out["checks"].items():
        assert f"check {name} {value} limit {limit}" in p.stderr
    return out


def failing(out: dict) -> set:
    return {k for k, (v, limit) in out["checks"].items() if v > limit}


@pytest.mark.parametrize("seed", [5, 2_147_483_911, 3_000_000_402])
def test_the_windowed_cell_sound_is_correct(copy, seed):
    out = run(copy, seed)
    assert out["correct"] is True and not failing(out), out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["rows_per_call"] == 6 * 46  # 6 commits, 2 of 48 absent in each
    # the rate is the rows of the calls' own items over the window
    rate = out["rehearsal_readings"]["sigs_per_s"]["value"]
    assert rate == pytest.approx(out["attempted"] * 276 / out["window_s"])
    assert out["notes"]["rows_compared"] == 4 * 276
    said = out["notes"]["entry_probes"]
    assert set(said) == {"short_power", "invalid_power"}
    for v in said.values():
        assert v["got"] == v["want"] and v["want"].startswith("refused at block #")


@pytest.mark.parametrize("seed", [6, 2_147_483_912, 3_000_000_403])
def test_only_the_first_block_checked_is_not_correct(copy, seed):
    out = run(copy, seed, "first_block_only")
    assert out["correct"] is False
    # the first block is a sixth of the rows: it holds one stratum of the 8 whole
    assert out["checks"]["probes_accepted"][0] >= 6
    assert {"probes_accepted"} <= failing(out) <= {"probes_accepted", "entry_verdict_mismatch"}


@pytest.mark.parametrize("seed", [7, 2_147_483_913, 3_000_000_404])
def test_power_tallied_by_head_count_is_not_correct(copy, seed):
    out = run(copy, seed, "head_count")
    assert out["correct"] is False
    assert failing(out) == {"entry_verdict_mismatch"}
    said = out["notes"]["entry_probes"]
    # four of 48 signers hold over a third of the power: by count they are few
    assert said["invalid_power"]["got"] == "accepted" != said["invalid_power"]["want"]
    assert said["short_power"]["got"] == said["short_power"]["want"]
    assert out["failed"] == 0  # the window's own runs are accepted either way


@pytest.mark.parametrize("seed", [8, 2_147_483_914, 3_000_000_405])
def test_the_last_third_unseen_is_not_correct(copy, seed):
    out = run(copy, seed, "unseen_last_third")
    assert out["correct"] is False
    assert out["checks"]["probes_accepted"][0] >= 2
    assert {"probes_accepted"} <= failing(out) <= {"probes_accepted", "entry_verdict_mismatch"}


def test_an_answer_altered_in_the_last_block_is_not_correct(copy):
    out = run(copy, 9, "altered_last_block")
    assert out["correct"] is False
    assert {"verdict_mismatch", "mask_mismatch"} <= failing(out)
    assert out["checks"]["mask_mismatch"][0] == 4 * 46  # the last block of each ring item
    assert out["failed"] == out["attempted"]


def test_the_control_by_power_is_not_correct(copy):
    out = run(copy, 10, "light")
    assert out["correct"] is False
    assert "probes_accepted" in failing(out)


# -- the arithmetic of a call of several commits


def test_a_call_is_credited_the_rows_of_its_own_item():
    rows = [276, 270, 264, 276]  # items of unequal row counts, 6 commits each
    calls = [(i * 0.5, i * 0.5 + 0.5, rows[i % 4]) for i in range(8)]
    assert stats.window_metrics(calls)["sigs_per_s"] == pytest.approx(2 * sum(rows) / 4.0)
    wrong = [(s, e, 0 if i == 1 else r) for i, (s, e, r) in enumerate(calls)]
    got = stats.window_metrics(wrong)
    assert got["sigs_per_s"] == pytest.approx((2 * sum(rows) - 270) / 4.0)
    assert got["verify_ms_p50"] == pytest.approx(500.0) and got["calls"] == 8


def test_an_item_is_a_run_of_commits_at_consecutive_heights():
    vals = data.make_validators(11, SKEWED)
    ring = data.make_ring(11, SKEWED, WINDOW, vals)
    assert len(ring) == 4 and all(isinstance(item, list) and len(item) == 6 for item in ring)
    assert [c.height for item in ring for c in item] == list(range(11, 11 + 24))
    assert len({c.block_hash for item in ring for c in item}) == 24
    assert len({tuple(c.flags) for c in ring[0]}) > 1  # each block its own draw of absentees
    assert [data.n_rows(item) for item in ring] == [276] * 4
    assert data.blocks_of(ring[1]) == [{"height": 17 + b, "rows": 46} for b in range(6)]
    idx, pks, msgs, sigs = data.rows_of(SKEWED, vals, ring[1])
    assert len(idx) == len(pks) == len(msgs) == len(sigs) == 276
    assert idx[:46] == ring[1][0].present() and idx[230:] == ring[1][5].present()
    assert all(reference.verify_rows(pks, msgs, sigs))
    # one commit a call: the commit itself, as before items
    one = data.make_ring(11, SKEWED, dict(WINDOW, commits_per_call=1), vals)
    assert all(isinstance(c, data.CommitData) for c in one)
    assert data.commits_of(one[0]) == [one[0]] and data.n_rows(one[0]) == 46


def test_the_probes_strata_span_the_whole_run():
    vals = data.make_validators(12, SKEWED)
    ring = data.make_ring(12, SKEWED, WINDOW, vals)
    probes = data.probes(12, 8, ring)
    assert [pos * 8 // 276 for _, pos, _ in probes] == list(range(8))
    assert sum(1 for _, pos, _ in probes if pos >= 46) >= 6  # past the run's first block


# -- a stake that the configuration states


def test_the_set_is_ordered_by_power_then_address():
    vals = data.make_validators(13, SKEWED)
    assert sorted(vals.powers) == sorted(SKEWED["voting_powers"])
    keys = [(-p, reference.address(pk)) for p, pk in zip(vals.powers, vals.pubkeys)]
    assert keys == sorted(keys) and vals.powers[0] == 1000 and vals.total_power == 6405
    assert all(p.public_key().public_bytes_raw() == pk for p, pk in zip(vals.privs, vals.pubkeys))
    first = data.make_validators(13, SKEWED, 12)  # a rehearsal takes the list's first 12
    assert sorted(first.powers) == sorted(SKEWED["voting_powers"][:12])
    equal = data.make_validators(13, {"validators": 12, "voting_power": 7})
    assert equal.powers == [7] * 12
    assert equal.pubkeys == sorted(equal.pubkeys, key=reference.address)


@pytest.mark.parametrize("seed", range(20, 28))
def test_every_entry_probe_is_refused_by_the_cells_own_rule(seed):
    vals = data.make_validators(seed, SKEWED)
    ring = data.make_ring(seed, SKEWED, WINDOW, vals)
    got = dict(data.entry_probes(seed, SKEWED, WINDOW, ring, vals))
    assert set(got) == {"short_power", "invalid_power"}
    for label, item in got.items():
        idx, pks, msgs, sigs = data.rows_of(SKEWED, vals, item)
        mask = reference.verify_rows(pks, msgs, sigs)
        said = RULE(mask, idx, vals.powers, vals.total_power, data.blocks_of(item))
        assert said.startswith("refused at block #"), (label, said)
        altered = [b for b, (c, was) in enumerate(zip(item, ring[0 if label == "short_power" else 2]))
                   if c is not was]
        assert altered == [int(said.rsplit("#", 1)[1])]  # ONE block of the item, the one named
    short = got["short_power"]
    gone = [48 - len(c.present()) for c in short]
    assert max(gone) >= 2 + 19 and sorted(gone)[:5] == [2] * 5  # 40% by count, then by power
    assert all(mask_ok for mask_ok in reference.verify_rows(*data.rows_of(SKEWED, vals, short)[1:]))
    bad = got["invalid_power"]
    flipped = [c.tampered for c in bad if c.tampered]
    assert len(flipped) == 1 and 1 <= len(flipped[0]) <= 6  # the largest signers, not a third by count
    assert [48 - len(c.present()) for c in bad] == [2] * 6  # nobody absent who was not


def test_with_equal_power_the_short_probe_is_the_draw_by_count():
    config = {"validators": 40, "voting_power": 3, "chain_id": "t", "absent_share": 0.0}
    traffic = {"ring_commits": 2, "short_power_absent_share": 0.4}
    vals = data.make_validators(14, config)
    ring = data.make_ring(14, config, traffic, vals)
    ((label, c),) = data.entry_probes(14, config, traffic, ring, vals)
    assert label == "short_power" and len(c.present()) == 24  # 60% left: the power step never ran


def test_the_control_stops_where_two_thirds_of_the_power_has_been_seen(monkeypatch):
    vals = data.make_validators(15, SKEWED)
    ring = data.make_ring(15, SKEWED, WINDOW, vals)
    _, pks, msgs, sigs = data.rows_of(SKEWED, vals, ring[0])
    seen = []

    def spy(p, m, s, _inner=reference.verify_rows):
        seen.append(len(p))
        return _inner(p, m, s)

    monkeypatch.setattr(reference, "verify_rows", spy)
    mask = controls.light(vals, pks, msgs, sigs)
    assert len(mask) == 276 and all(mask)
    # in each of the 6 blocks: rows until over 2/3 of 6,405 has been seen, far under 2/3 of 46
    assert seen and 6 * 8 <= seen[0] <= 6 * 22
    checked = 0
    for c in ring[0]:
        tallied = 0
        for i in c.present():
            checked += tallied * 3 <= vals.total_power * 2
            tallied += vals.powers[i]
    assert seen[0] == checked
    # with equal power it is the cut by count that it was: 2n/3 + 1 rows of one commit
    config = {"validators": 30, "voting_power": 5, "chain_id": "t"}
    vals = data.make_validators(16, config)
    (c,) = data.make_ring(16, config, {"ring_commits": 1}, vals)
    _, pks, msgs, sigs = data.rows_of(config, vals, c)
    sigs[25] = data.flip_bit(sigs[25], "s")
    sigs[20] = data.flip_bit(sigs[20], "R")
    assert [i for i, ok in enumerate(controls.light(vals, pks, msgs, sigs)) if not ok] == [20]


# -- the rule, found by name


def test_a_configuration_without_a_rule_is_held_to_verify_commit(copy):
    tmp, here = copy
    bm = spec.load_benchmark(tmp)
    plain = spec.Cell(bm, "commit-1024.verify-commit", tmp, here).rule()
    one = [{"height": 5, "rows": 3}]
    assert plain([True, True, True], [0, 1, 2], [1, 1, 1], 3, one) == "accepted"
    assert plain([True, False, True], [0, 1, 2], [1, 1, 1], 3, one) == "wrong signature (#1)"
    assert plain([True, True], [0, 2], [1, 1, 1], 3, one) == "not enough voting power"
    with pytest.raises(ValueError, match="verdict_rule"):
        plain([True] * 6, [0, 1, 2] * 2, [1, 1, 1], 3, one * 2)
    named = spec.Cell(bm, selftest.WINDOWED_CELL, tmp, here).rule()
    two = [{"height": 5, "rows": 3}, {"height": 6, "rows": 2}]
    powers = [5, 2, 2]  # total 9: over 2/3 needs more than 6
    assert named([True] * 5, [0, 1, 2, 0, 1], powers, 9, two) == "accepted"
    assert named([True, False, True, True, True], [0, 1, 2, 0, 1], powers, 9, two) == "accepted"
    assert named([True] * 3 + [False, True], [0, 1, 2, 0, 1], powers, 9, two) == "refused at block #1"
    assert named([False, True, True, True, True], [0, 1, 2, 0, 1], powers, 9, two) == "refused at block #0"


def lint_of(tmp_path, config=None, traffic=None, drop_rule=False):
    bm, here = selftest.add_windowed_cell(str(tmp_path))
    for kind, name, change in (("configs", "skewed-48", config), ("traffic", "window-6", traffic)):
        path = os.path.join(here, kind, name + ".json")
        body = spec.load_json(path)
        for k, v in (change or {}).items():
            body.pop(k) if v is None else body.__setitem__(k, v)
        with open(path, "w") as f:
            json.dump(body, f)
    if drop_rule:
        os.remove(os.path.join(here, "references", "tally_valid_power.py"))
    return spec.lint(bm, str(tmp_path), here)


@pytest.mark.parametrize("change,says", [
    ({"config": {"voting_powers": [1] * 47}}, "47 voting_powers for 48 validators"),
    ({"drop_rule": True}, "no rule file references/tally_valid_power.py"),
    ({"traffic": {"commits_per_call": 0}}, "commits_per_call 0"),
    ({"traffic": {"commits_per_call": 2.5}}, "commits_per_call 2.5"),
    ({"config": {"verdict_rule": None}}, "needs the configuration to name its verdict_rule"),
])
def test_the_lint_refuses(tmp_path, change, says):
    faults = lint_of(tmp_path, **change)
    assert len(faults) == 1 and says in faults[0], faults


def test_the_lint_passes_the_fixture_and_the_accepted_benchmark(tmp_path):
    assert lint_of(tmp_path) == []
    assert spec.lint(spec.load_benchmark(ROOT), ROOT, HERE) == []


# -- what must not have moved: the accepted configurations' data, from the parent's code

PINNED = {  # sha256 over validators, ring, probes and entry probes; taken on commit 935ae4f
    ("commit-10k", 5): "b7e91f60adeb82c9cfecc18c45d7d76e219264337f75623af64498e6dd1a0122",
    ("commit-10k", 2147483911): "09272523c94a196d6547931bf98eb45e1a16092512ee89c0ede0c9ccd2f86c25",
    ("commit-10k", 3000000402): "9097cafc93fe8c09740e7df1a72b5d4e9085274c86672abda173dd58579c4e17",
    ("commit-1024", 5): "7337239cc3dfcbd6678409a286f57512b30167dc4734dc7f8a81ef27cb2b64a8",
    ("commit-1024", 2147483911): "4d9d2b79b588a35c43f163565c56e0a56a4dc4b88c74b68409dd9c6c2f14a5f0",
    ("commit-1024", 3000000402): "7a0f11576b58236de84354081956dea4d92efd47f15570697d2b8be7f047defa",
    # with its mix `catchup`; taken on commit f28f7b4, before data.py knew a chain
    ("hub-175", 5): "42ef2cc70b941542766732cbe21da32d2d915791e457e4e969074128fce9718a",
    ("hub-175", 2147483911): "eebb3ff8e1389766973321129f1920012c23c3b8c4bb774d2e3bf1de7fb8b9da",
    ("hub-175", 3000000402): "5e3ff8d8ea044ecdf90fb27330fa09a08c46dc1644a81fff0583ca18f0523403",
}
MIX_OF = {"commit-10k": "verify-commit", "commit-1024": "verify-commit", "hub-175": "catchup"}


def digest(config: dict, traffic: dict, seed: int) -> str:
    h = hashlib.sha256()

    def commits(item):
        for c in data.commits_of(item):
            h.update(repr((c.height, c.round, c.block_hash, c.parts_total, c.parts_hash, c.flags,
                           c.timestamps, c.sigs, tuple(c.tampered))).encode())

    vals = data.make_validators(seed, config)
    h.update(repr((vals.pubkeys, vals.powers,
                   [p.private_bytes_raw() for p in vals.privs])).encode())
    ring = data.make_ring(seed, config, traffic, vals)
    for item in ring:
        commits(item)
    h.update(repr(data.probes(seed, int(traffic["probes"]), ring)).encode())
    for label, item in data.entry_probes(seed, config, traffic, ring, vals):
        h.update(label.encode())
        commits(item)
    return h.hexdigest()


@pytest.mark.parametrize("name,seed", sorted(PINNED))
def test_the_accepted_configurations_data_is_the_parents_byte_for_byte(name, seed):
    config = spec.load_json(os.path.join(HERE, "configs", name + ".json"))
    traffic = spec.load_json(os.path.join(HERE, "traffic", MIX_OF[name] + ".json"))
    assert not {"headers", "validator_changes_per_height", "fresh_voting_powers"} & set(config)
    small = data.make_ring(seed, dict(config, validators=8), dict(traffic, commits_per_call=1),
                           data.make_validators(seed, config, 8))
    assert all(isinstance(c, data.CommitData) and c.header is None and c.vals is None
               and c.prev is None for c in small)
    if name != "hub-175":
        assert "voting_powers" not in config and "commits_per_call" not in traffic
    assert digest(config, traffic, seed) == PINNED[(name, seed)]


# taken on commit 3f0c4f0, before a mix could seat its forged rows: the
# two accepted configurations not pinned above, at one seed (full size, 3-4 s
# each), both fixtures, and mixes that forge with no `tampered_seats`, which no
# accepted mix does (an item of one commit, of six, and of a chain's twelve)
FORGED = {"tampered_one_in": 2}
PINNED_BEFORE_SEATS = {
    ("live-10k", "vote-commit", None, 5):
        "88d301b2f6700e4b1bd023ebe860e80d1abcd19af91ffd9a768dd5b54c1f4d1b",
    ("light-seq-100", "sequence", None, 5):
        "a50a2d9f94cc9d6aff83dc17473d6ae6ec4726f79ef643a13f205538f10d3d7b",
    ("skewed-48", "window-6", None, 5):
        "c35e1efd824020624cd96c9d347efbb0c35626b0e75ffca25f3cbc852ffb6e73",
    ("skewed-48", "window-6", None, 2147483911):
        "c98e2537b0acc3786c4ae2f0c1f1aabc8d5c26e4ce41a0089f4ab11bae4e075b",
    ("skewed-48", "window-6", None, 3000000402):
        "8cf25664e6bb3cea5d3a11521a5627a2b7c391739a1da68801ea25faf3846d58",
    ("chain-16", "sequence-12", None, 5):
        "2e0aad8a62e7caa1e3b95d60ecac13c4a17aa78b457fa16a4baaf8d46cd63681",
    ("chain-16", "sequence-12", None, 2147483911):
        "9378c8025b351d76d76d1fab852833cb55f283b27d2231356ef69350251541d4",
    ("chain-16", "sequence-12", None, 3000000402):
        "044021e63b72992f0feebb85c4bf252624a5d0620da33e68fab7714718e5d6b7",
    ("commit-1024", "verify-commit", "forged", 5):
        "f21e6106882a569ef4a164751f9e00d3a1738ebf82a52906aab1f7c28a0ebb16",
    ("commit-1024", "verify-commit", "forged", 2147483911):
        "880fc81f214f7ad2678f2022f9eab6a80e3fcfcfecc62a060337782c08a354f9",
    ("commit-1024", "verify-commit", "forged", 3000000402):
        "28bd4d05087219acd2f594bac32d994a57ecb665d3f4ce787088202395717ae2",
    ("skewed-48", "window-6", "forged", 5):
        "5cde494c947ee32d860076154460eba90ab1776b69ca08dd5fab391c456a4651",
    ("skewed-48", "window-6", "forged", 2147483911):
        "df18ec772639f5b7b76207335c9f0f7f8fe648267d9f9827e69a0ef9d8d2b851",
    ("skewed-48", "window-6", "forged", 3000000402):
        "dbdb8b7a7090e5d1bc101029a9c3aadbae234c1997adcd3f9275f7a7144cab49",
    ("chain-16", "sequence-12", "forged", 5):
        "74da29d8a8c8c130bef934430f7420080596114c4e68e32910c0d4258c254573",
    ("chain-16", "sequence-12", "forged", 2147483911):
        "5c6fec33a046fbcdce5740601e61e719728f614906db356641162d2e4e88240c",
    ("chain-16", "sequence-12", "forged", 3000000402):
        "cd7866986c5f70c23697918a26aa41cf3fa188872be2640d71a5a171752016fa",
}


@pytest.mark.parametrize("name,mix,forged,seed", sorted(PINNED_BEFORE_SEATS, key=str))
def test_every_mix_without_seats_gives_the_parents_data_byte_for_byte(name, mix, forged, seed):
    base = FIXTURES if os.path.isfile(os.path.join(FIXTURES, "configs", name + ".json")) else HERE
    config = spec.load_json(os.path.join(base, "configs", name + ".json"))
    traffic = spec.load_json(os.path.join(base, "traffic", mix + ".json"))
    assert "tampered_seats" not in traffic and traffic.get("tampered_one_in", 0) == 0
    if forged:
        traffic = dict(traffic, **FORGED)
    assert digest(config, traffic, seed) == PINNED_BEFORE_SEATS[(name, mix, forged, seed)]
