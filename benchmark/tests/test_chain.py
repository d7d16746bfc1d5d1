"""The room a chain cell needs, proven with no such cell: an item of signed
headers whose validator set changes from height to height, header and set
hashes in the plain reference, a rule that sees the links. The fixture under
fixtures/ (16 validators, one key replaced at every height, a fresh key's
power its own, calls of 12 headers, a ring of 4, the rule `adjacent_run`, a
driver built on reference.py alone) is laid into a copy of this directory as
a later PR would add it and run through run.py --rehearse, sound and with
each fault a chain cell can have. And the generator against the program, at
a small size on the CPU: the reference's hashes are the program's, and the
program's sequential light client accepts the generated chain and refuses
the `broken_link` one at the broken height.

Run: python -m pytest benchmark/tests -q   (or python benchmark/selftest.py)"""

from __future__ import annotations

import asyncio
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
for p in (ROOT, HERE):
    if p not in sys.path:
        sys.path.insert(0, p)

import data  # noqa: E402
import reference  # noqa: E402
import selftest  # noqa: E402
import spec  # noqa: E402

FIXTURES = os.path.join(HERE, "tests", "fixtures")
CHAIN = spec.load_json(os.path.join(FIXTURES, "configs", "chain-16.json"))
SEQUENCE = spec.load_json(os.path.join(FIXTURES, "traffic", "sequence-12.json"))
RULE = spec.load_module(os.path.join(FIXTURES, "references", "adjacent_run.py")).verdict


# -- the fixture through run.py, sound and broken


@pytest.fixture(scope="module")
def chain_copy(tmp_path_factory):
    tmp = str(tmp_path_factory.mktemp("chain"))
    bm, here = selftest.add_chain_cell(tmp)
    assert spec.lint(bm, tmp, here) == []
    return tmp, here


def run_chain(chain_copy, seed: int, control: str = "") -> dict:
    p = selftest.run_fixture_cell(*chain_copy, selftest.CHAIN_CELL, 16, seed, control, seconds=0.5)
    assert p.returncode == 0, p.stderr[-2000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["device"]["platform"] == "cpu" and out["metrics"] == {}
    assert list(out)[-1] == "checks"
    for name, (value, limit) in out["checks"].items():
        assert f"check {name} {value} limit {limit}" in p.stderr
    return out


def failing(out: dict) -> set:
    return {k for k, (v, limit) in out["checks"].items() if v > limit}


def test_the_chain_cell_is_files_and_entries_only(chain_copy):
    tmp, here = chain_copy
    for dirpath, _, files in os.walk(HERE):
        if "__pycache__" in dirpath or "testdata" in dirpath:
            continue
        for f in files:
            mine = os.path.join(dirpath, f)
            with open(mine, "rb") as a, open(os.path.join(here, os.path.relpath(mine, HERE)), "rb") as b:
                assert a.read() == b.read(), f"{mine} was edited in the copy"
    parent, bm = spec.load_benchmark(ROOT), spec.load_benchmark(tmp)
    for kind in ("configs", "workloads", "end_to_end", "per_layer"):
        assert bm[kind][:len(parent[kind])] == parent[kind]
    assert [len(bm[k]) - len(parent[k]) for k in ("configs", "workloads", "per_layer")] == [1, 1, 1]


@pytest.mark.parametrize("seed", [7, 2_147_483_933, 3_000_000_555])
def test_the_chain_cell_sound_is_correct(chain_copy, seed):
    out = run_chain(chain_copy, seed)
    assert out["correct"] is True and not failing(out), out["checks"]
    assert all(v == 0 for v, _ in out["checks"].values())
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["rows_per_call"] == 12 * 15  # 12 headers, 1 of 16 absent in each
    assert out["notes"]["rows_compared"] == 4 * 180 and out["notes"]["probes"] == 8
    said = out["notes"]["entry_probes"]
    assert set(said) == {"short_power", "invalid_power", "broken_link"}
    for label, v in said.items():
        assert v["got"] == v["want"], (label, v)
        words = "broken link at block #" if label == "broken_link" else "not enough power at block #"
        assert v["want"].startswith(words)


@pytest.mark.parametrize("seed", [8, 2_147_483_934, 3_000_000_556])
def test_every_header_against_the_roots_set_is_not_correct(chain_copy, seed):
    out = run_chain(chain_copy, seed, "roots_set")
    assert out["correct"] is False
    # one height on, a key of another power has shifted the seats: the window's own runs fail
    assert {"verdict_mismatch", "rows_valid_short"} <= failing(out)
    assert out["checks"]["verdict_mismatch"][0] == out["attempted"] == out["failed"]


@pytest.mark.parametrize("seed", [9, 2_147_483_935, 3_000_000_557])
def test_links_not_checked_is_not_correct(chain_copy, seed):
    out = run_chain(chain_copy, seed, "links_unchecked")
    assert out["correct"] is False
    assert failing(out) == {"entry_verdict_mismatch"}
    said = out["notes"]["entry_probes"]
    # every signature of the forged run is valid under the set its header carries
    assert said["broken_link"]["got"] == "accepted" != said["broken_link"]["want"]
    assert all(said[k]["got"] == said[k]["want"] for k in ("short_power", "invalid_power"))
    assert out["failed"] == 0  # the window's own runs are linked


@pytest.mark.parametrize("seed", [10, 2_147_483_936, 3_000_000_558])
def test_only_the_first_header_verified_is_not_correct(chain_copy, seed):
    out = run_chain(chain_copy, seed, "first_header_only")
    assert out["correct"] is False
    # the first header is a twelfth of the rows: of the 8 strata it holds part of one
    assert out["checks"]["probes_accepted"][0] >= 7
    assert {"probes_accepted"} <= failing(out) <= {"probes_accepted", "entry_verdict_mismatch"}
    assert out["notes"]["entry_probes"]["broken_link"]["got"].startswith("broken link")


@pytest.mark.parametrize("seed", [11, 2_147_483_937, 3_000_000_559])
def test_powers_of_the_wrong_height_in_the_tally_is_not_correct(chain_copy, seed):
    out = run_chain(chain_copy, seed, "roots_powers")
    assert out["correct"] is False
    assert failing(out) == {"entry_verdict_mismatch"}
    said = out["notes"]["entry_probes"]
    # the root's equal powers are a head count: the few large signers of a later height are few
    assert said["invalid_power"]["got"] == "accepted" != said["invalid_power"]["want"]
    assert said["broken_link"]["got"] == said["broken_link"]["want"]
    assert out["failed"] == 0


# -- the generator


def make(seed: int, config: dict = CHAIN, traffic: dict = SEQUENCE):
    vals = data.make_validators(seed, config)
    return vals, data.make_ring(seed, config, traffic, vals)


def test_a_chain_is_one_run_of_linked_headers_from_the_trusted_root():
    vals, ring = make(21)
    commits = [c for item in ring for c in item]
    assert [c.height for c in commits] == list(range(21, 21 + 48))
    root = ring[0][0].prev
    assert root.height == 20 and root.prev is None and root.vals is vals
    assert [c.prev for c in commits] == [root] + commits[:-1]  # item j continues where j - 1 ended
    for c in [root] + commits:
        assert set(c.header) == set(reference.HEADER_FIELDS)
        assert c.block_hash == reference.header_hash(c.header)
        assert c.header["validators_hash"] == reference.validators_hash(c.vals.pubkeys, c.vals.powers)
        assert c.header["height"] == c.height and c.header["chain_id"] == CHAIN["chain_id"]
        assert all(reference.verify_rows(*data.rows_of(CHAIN, vals, c)[1:]))
        assert len(c.present()) == 15
    for c in commits:
        assert c.header["validators_hash"] == c.prev.header["next_validators_hash"]
        assert c.header["time_ns"] > c.prev.header["time_ns"]
        assert (c.header["last_block_hash"], c.header["last_parts_total"],
                c.header["last_parts_hash"]) == (c.prev.block_hash, c.prev.parts_total,
                                                 c.prev.parts_hash)
    for item in ring:
        blocks = data.blocks_of(item)
        assert all(b["link_ok"] for b in blocks)
        assert [b["total_power"] for b in blocks] == [c.vals.total_power for c in item]
        idx, pks, msgs, sigs = data.rows_of(CHAIN, vals, item)
        assert RULE([True] * len(idx), idx, vals.powers, vals.total_power, blocks) == "accepted"


def test_at_every_height_the_oldest_key_leaves_and_a_fresh_one_enters():
    vals, ring = make(22)
    commits = [ring[0][0].prev] + [c for item in ring for c in item]
    assert len({pk for c in commits for pk in c.vals.pubkeys}) == 16 + 48
    fresh_powers = CHAIN["fresh_voting_powers"]
    for number, (c, after) in enumerate(zip(commits, commits[1:]), start=16):
        gone = set(c.vals.pubkeys) - set(after.vals.pubkeys)
        came = set(after.vals.pubkeys) - set(c.vals.pubkeys)
        assert len(gone) == len(came) == 1
        oldest = min(range(16), key=c.vals.born.__getitem__)
        assert gone == {c.vals.pubkeys[oldest]}
        seat = after.vals.pubkeys.index(came.pop())
        assert after.vals.born[seat] == number
        assert after.vals.powers[seat] == fresh_powers[number % len(fresh_powers)]
        keys = [(-p, reference.address(pk)) for p, pk in zip(after.vals.powers, after.vals.pubkeys)]
        assert keys == sorted(keys)  # ordered as a set is: power descending, then address
        assert all(p.public_key().public_bytes_raw() == pk
                   for p, pk in zip(after.vals.privs, after.vals.pubkeys))
    by_seat = [tuple(c.vals.powers) for c in commits]
    assert len(set(by_seat)) >= 5 and by_seat[0] not in by_seat[16:]  # a seat's power differs by height
    # no one signer ever holds a third, so the one absentee never sinks a header
    assert all(max(c.vals.powers) * 3 < c.vals.total_power for c in commits)
    # without fresh_voting_powers a fresh key takes the power of the key it replaces
    plain = {k: v for k, v in CHAIN.items() if k != "fresh_voting_powers"}
    _, ring = make(22, plain, dict(SEQUENCE, ring_commits=1, commits_per_call=3))
    assert all(c.vals.powers == [10] * 16 for c in ring[0])
    assert len({tuple(c.vals.pubkeys) for c in ring[0]}) == 3


def test_a_configuration_without_headers_has_none_and_one_set():
    config = {k: v for k, v in CHAIN.items()
              if k not in ("headers", "validator_changes_per_height", "fresh_voting_powers")}
    vals, ring = make(23, config)
    assert all(c.header is None and c.vals is None and c.prev is None for item in ring for c in item)
    assert data.blocks_of(ring[0])[0] == {"height": 21, "rows": 15}
    assert [label for label, _ in data.entry_probes(23, config, SEQUENCE, ring, vals)] == [
        "short_power", "invalid_power"]
    with pytest.raises(ValueError, match="first_height is 2 or more"):
        make(23, CHAIN, dict(SEQUENCE, first_height=1))
    # a chain may stand still: no change a height, every header still linked to the last
    still = dict(CHAIN, validator_changes_per_height=0)
    del still["fresh_voting_powers"]
    vals, ring = make(23, still, dict(SEQUENCE, ring_commits=1, commits_per_call=3, first_height=2))
    root = ring[0][0].prev
    assert root.height == 1 and root.header["last_block_hash"] == b""  # height 1 has no block before it
    assert all(c.vals is vals for c in ring[0]) and all(b["link_ok"] for b in data.blocks_of(ring[0]))


@pytest.mark.parametrize("seed", range(30, 38))
def test_the_broken_link_is_the_only_thing_wrong_with_its_run(seed):
    vals, ring = make(seed)
    got = dict(data.entry_probes(seed, CHAIN, SEQUENCE, ring, vals))
    assert set(got) == {"short_power", "invalid_power", "broken_link"}
    forged, was = got["broken_link"], ring[3]
    idx, pks, msgs, sigs = data.rows_of(CHAIN, vals, forged)
    assert all(reference.verify_rows(pks, msgs, sigs))  # every signature valid under its header's set
    blocks = data.blocks_of(forged)
    (b,) = [k for k, block in enumerate(blocks) if not block["link_ok"]]
    assert RULE([True] * len(idx), idx, vals.powers, vals.total_power, blocks) == f"broken link at block #{b}"
    assert [c is w for c, w in zip(forged, was)] == [True] * b + [False] * (12 - b)
    bad = forged[b]
    # only the hash chain is broken: the header names the set that signed it, not the promised one
    assert bad.header["validators_hash"] == reference.validators_hash(bad.vals.pubkeys, bad.vals.powers)
    assert bad.header["validators_hash"] != bad.prev.header["next_validators_hash"]
    assert bad.block_hash == reference.header_hash(bad.header) != was[b].block_hash
    assert bad.header["next_validators_hash"] == was[b].header["next_validators_hash"]
    assert len(set(bad.vals.pubkeys) - set(was[b].vals.pubkeys)) == 1
    for c, w in zip(forged[b + 1:], was[b + 1:]):  # the headers above hang on the forged one
        assert c.header["last_block_hash"] == c.prev.block_hash and c.vals is w.vals
        assert {k for k in c.header if c.header[k] != w.header[k]} == {"last_block_hash"}
    # the other probes weigh a commit by the set of its own height
    for label in ("short_power", "invalid_power"):
        item = got[label]
        idx, pks, msgs, sigs = data.rows_of(CHAIN, vals, item)
        mask = reference.verify_rows(pks, msgs, sigs)
        said = RULE(mask, idx, vals.powers, vals.total_power, data.blocks_of(item))
        assert said.startswith("not enough power at block #"), (label, said)
        (c,) = [c for c, w in zip(item, ring[0 if label == "short_power" else 2]) if c is not w]
        valid = sum(c.vals.powers[i] for i, ok in zip(c.present(), reference.verify_rows(
            *data.rows_of(CHAIN, vals, c)[1:])) if ok)
        assert valid * 3 <= c.vals.total_power * 2


def test_a_rule_that_exists_gets_what_it_got():
    """`tally_valid_power` reads `height` and `rows` of a block and the one
    set's powers: on a chain item it is handed the same, the block's new
    fields beside them."""
    vals, ring = make(24)
    old = spec.load_module(os.path.join(FIXTURES, "references", "tally_valid_power.py")).verdict
    idx, pks, msgs, sigs = data.rows_of(CHAIN, vals, ring[0])
    blocks = data.blocks_of(ring[0])
    bare = [{"height": b["height"], "rows": b["rows"]} for b in blocks]
    mask = [i % 5 != 0 for i in range(len(idx))]
    assert old(mask, idx, vals.powers, vals.total_power, blocks) == \
        old(mask, idx, vals.powers, vals.total_power, bare)
    with pytest.raises(ValueError, match="verdict_rule"):  # VerifyCommit still speaks of one commit
        reference.verdict(mask, idx, vals.powers, vals.total_power, blocks)


# -- the lint


def lint_of(tmp_path, config=None, traffic=None):
    bm, here = selftest.add_chain_cell(str(tmp_path))
    for kind, name, change in (("configs", "chain-16", config), ("traffic", "sequence-12", traffic)):
        path = os.path.join(here, kind, name + ".json")
        body = spec.load_json(path)
        for k, v in (change or {}).items():
            body.pop(k) if v is None else body.__setitem__(k, v)
        with open(path, "w") as f:
            json.dump(body, f)
    return spec.lint(bm, str(tmp_path), here)


@pytest.mark.parametrize("change,says", [
    ({"config": {"verdict_rule": None}}, "a chain of signed headers needs the configuration to name"),
    ({"config": {"headers": "yes"}}, "headers 'yes' is not true or false"),
    ({"config": {"headers": None, "fresh_voting_powers": None}}, "validator_changes_per_height needs headers"),
    ({"config": {"validator_changes_per_height": 17}}, "validator_changes_per_height 17"),
    ({"config": {"validator_changes_per_height": 0}}, "fresh_voting_powers needs validator_changes"),
    ({"config": {"fresh_voting_powers": [10, 0]}}, "fresh_voting_powers is not a list of whole numbers over 0"),
    ({"traffic": {"first_height": 1}}, "first_height 1: a chain's trusted root"),
])
def test_the_lint_refuses_a_chain(tmp_path, change, says):
    faults = lint_of(tmp_path, **change)
    assert any(says in f for f in faults), faults
    assert len(faults) <= 2  # a chain of 12 headers a call with no rule is told so twice


def test_the_lint_passes_the_chain_fixture(tmp_path):
    assert lint_of(tmp_path) == []


# -- the generator against the program, at a small size on the CPU

SMALL = {"chain_id": "agree-chain", "validators": 8, "voting_power": 7, "headers": True,
         "validator_changes_per_height": 1, "absent_share": 0.0, "verdict_rule": "adjacent_run"}
TEN = {"ring_commits": 1, "commits_per_call": 9, "first_height": 2}  # root at 1, then 2 .. 10
PERIOD_NS = 14 * 24 * 3600 * 10**9
NOW_NS = data.BASE_TIME_NS + 3600 * 10**9


def light_block(c):
    """The program's own LightBlock of a chain's commit."""
    from tendermint_tpu.crypto.keys import Ed25519PubKey
    from tendermint_tpu.types.basic import BlockID, BlockIDFlag, PartSetHeader
    from tendermint_tpu.types.block import Commit, CommitSig, ConsensusVersion, Header
    from tendermint_tpu.types.light import LightBlock, SignedHeader
    from tendermint_tpu.types.validator_set import Validator, ValidatorSet

    h = c.header
    header = Header(
        version=ConsensusVersion(h["version_block"], h["version_app"]), chain_id=h["chain_id"],
        height=h["height"], time_ns=h["time_ns"],
        last_block_id=BlockID(h["last_block_hash"],
                              PartSetHeader(h["last_parts_total"], h["last_parts_hash"])),
        **{k: h[k] for k in reference.HEADER_FIELDS[8:]})
    vals = ValidatorSet([Validator(Ed25519PubKey(pk), p)
                         for pk, p in zip(c.vals.pubkeys, c.vals.powers)])
    assert [v.pub_key.bytes() for v in vals.validators] == c.vals.pubkeys
    addrs = [v.address for v in vals.validators]
    sigs = [CommitSig.absent_sig() if f == reference.FLAG_ABSENT
            else CommitSig(BlockIDFlag.COMMIT, addrs[i], c.timestamps[i], c.sigs[i])
            for i, f in enumerate(c.flags)]
    commit = Commit(c.height, c.round, BlockID(c.block_hash, PartSetHeader(c.parts_total, c.parts_hash)),
                    sigs)
    return LightBlock(SignedHeader(header, commit), vals)


def sequential_client(blocks: dict, root):
    from tendermint_tpu.libs.kvdb import MemDB
    from tendermint_tpu.light import Client, LightStore, TrustOptions
    from tendermint_tpu.light.client import SEQUENTIAL
    from tendermint_tpu.light.provider import MockProvider

    return Client(SMALL["chain_id"], TrustOptions(PERIOD_NS, root.height, root.block_hash),
                  MockProvider(SMALL["chain_id"], blocks), [], LightStore(MemDB()),
                  verification_mode=SEQUENTIAL)


@pytest.mark.parametrize("seed", [41, 2_147_483_941, 3_000_000_641])
def test_the_references_hashes_are_the_programs(seed):
    vals, ring = make(seed, SMALL, TEN)
    chain = [ring[0][0].prev] + ring[0]
    assert [c.height for c in chain] == list(range(1, 11))
    for c in chain:
        lb = light_block(c)
        assert reference.header_hash(c.header) == lb.signed_header.header.hash() == c.block_hash
        assert reference.validators_hash(c.vals.pubkeys, c.vals.powers) == lb.validator_set.hash()
        lb.validate_basic(SMALL["chain_id"])  # the commit is for the header, the set is the header's
    # a header with an app version, an empty field and a negative-free zero time part
    odd = dict(chain[3].header, version_app=7, app_hash=b"", time_ns=data.BASE_TIME_NS)
    assert reference.header_hash(odd) == light_block(data.replace(chain[3], header=odd)) \
        .signed_header.header.hash()


@pytest.mark.parametrize("seed", [42, 2_147_483_942, 3_000_000_642])
def test_the_programs_sequential_light_client_accepts_the_chain_and_refuses_the_broken_one(seed):
    from tendermint_tpu.light.verifier import ErrInvalidHeader

    vals, ring = make(seed, SMALL, TEN)
    root = ring[0][0].prev

    async def to_ten(run):
        client = sequential_client({c.height: light_block(c) for c in [root] + run}, root)
        await client.initialize(NOW_NS)
        try:
            return (await client.verify_light_block_at_height(10, NOW_NS)).height, client
        except ErrInvalidHeader as e:
            return e, client

    got, client = asyncio.run(to_ten(ring[0]))
    assert got == 10 and client.last_trusted_height() == 10
    ((label, forged),) = [p for p in data.entry_probes(seed, SMALL, TEN, ring, vals)
                          if p[0] == "broken_link"]
    (b,) = [k for k, block in enumerate(data.blocks_of(forged)) if not block["link_ok"]]
    err, client = asyncio.run(to_ten(forged))
    assert isinstance(err, ErrInvalidHeader) and "next validators" in str(err)
    assert forged[b].header["validators_hash"].hex() in str(err)
    assert client.last_trusted_height() == forged[b].height - 1  # refused at the broken height
