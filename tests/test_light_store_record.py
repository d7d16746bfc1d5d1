"""The light store's record (types/light.py light_block_to_bytes /
light_block_from_bytes): one versioned binary record a block, columns for the
commit's signatures and the set's validators. What is saved is read back
equal FIELD BY FIELD by a fresh store, an earlier build's JSON record is
still read, and a record that is not whole is refused."""

import dataclasses
import json
import os

import pytest

os.environ.setdefault("TMTPU_CRYPTO_BACKEND", "cpu")

from test_light import CHAIN_ID, make_chain, make_keys, sign_commit

from tendermint_tpu.crypto import keys as K
from tendermint_tpu.crypto import tmhash
from tendermint_tpu.libs.kvdb import MemDB, SQLiteDB
from tendermint_tpu.light import LightStore
from tendermint_tpu.types.basic import BlockID, BlockIDFlag, PartSetHeader
from tendermint_tpu.types.block import Commit, CommitSig, ConsensusVersion, Header
from tendermint_tpu.types.light import (
    LightBlock,
    SignedHeader,
    light_block_from_bytes,
    light_block_to_bytes,
    light_block_to_json,
)
from tendermint_tpu.types.validator_set import Validator, ValidatorSet

try:
    from tendermint_tpu.crypto.sr25519 import gen_sr25519
except ImportError:  # pragma: no cover
    gen_sr25519 = None

T0 = 1_700_000_000 * 10**9


def key_of(height: int) -> bytes:
    return b"lb/" + height.to_bytes(8, "big")


def fields(lb: LightBlock) -> dict:
    """Everything a light block holds, as plain values."""
    header, commit, vs = lb.signed_header.header, lb.signed_header.commit, lb.validator_set

    def validator(v):
        return (v.pub_key.type_name(), v.pub_key.bytes(), v.address,
                v.voting_power, v.proposer_priority)

    return {
        "header": {f.name: getattr(header, f.name) for f in dataclasses.fields(header)},
        "commit": (commit.height, commit.round, commit.block_id.hash,
                   commit.block_id.part_set_header.total, commit.block_id.part_set_header.hash),
        "signatures": [(int(cs.block_id_flag), cs.validator_address, cs.timestamp_ns, cs.signature)
                       for cs in commit.signatures],
        "validators": [validator(v) for v in vs.validators],
        "proposer": validator(vs.proposer) if vs.proposer else None,
    }


def block_of(vals: ValidatorSet, privs, height: int = 7, sigs=None) -> LightBlock:
    """A light block of this set at a height, signed by every key unless
    `sigs` rewrites the commit's signatures."""
    prev = tmhash.sum256(b"prev%d" % height)
    header = Header(
        version=ConsensusVersion(11, 3), chain_id=CHAIN_ID, height=height,
        time_ns=T0 + height * 10**9 + 123_456_789,
        last_block_id=BlockID(prev, PartSetHeader(2, tmhash.sum256(prev))),
        last_commit_hash=tmhash.sum256(b"lc"), data_hash=tmhash.sum256(b"d"),
        validators_hash=vals.hash(), next_validators_hash=vals.hash(),
        consensus_hash=tmhash.sum256(b"c"), app_hash=b"\x01\x02\x03",
        last_results_hash=tmhash.sum256(b"r"), evidence_hash=tmhash.sum256(b"e"),
        proposer_address=vals.get_proposer().address,
    )
    commit = sign_commit(header, vals, privs)
    if sigs is not None:
        commit = Commit(commit.height, 3, commit.block_id, sigs(list(commit.signatures)))
    return LightBlock(SignedHeader(header, commit), vals)


def ed_set(n, powers=None, tag=b"\x21"):
    privs = make_keys(tag, n)
    return ValidatorSet([Validator(p.pub_key(), powers[i] if powers else 10)
                         for i, p in enumerate(privs)]), privs


def all_signed_100():
    return block_of(*ed_set(100))


def absent_and_nil():
    def sigs(rows):
        rows[1] = CommitSig.absent_sig()
        rows[3] = CommitSig(BlockIDFlag.NIL, rows[3].validator_address, -5, rows[3].signature)
        rows[6] = CommitSig.absent_sig()
        return rows
    return block_of(*ed_set(8), sigs=sigs)


def priorities_and_powers():
    vals, privs = ed_set(6, powers=[1, 2**40, 7, 7, 2**60, 3])
    for v, p in zip(vals.validators, [-(2**63), 2**63 - 1, -1, 0, 2**62 + 5, -(2**40)]):
        v.proposer_priority = p
    return block_of(vals, privs)


def another_proposer():
    vals, privs = ed_set(5)
    vals.proposer = [v for v in vals.validators if v is not vals.get_proposer()][2]
    assert vals.proposer.address != vals._compute_proposer().address
    return block_of(vals, privs)


def one_validator():
    return block_of(*ed_set(1))


def sr25519_set():
    privs = [gen_sr25519(bytes([60 + i]) * 32) for i in range(4)]
    return block_of(ValidatorSet([Validator(p.pub_key(), 10 + i) for i, p in enumerate(privs)]),
                    privs)


def bls_set():
    privs = [K.gen_bls12_381(bytes([0x50 + i]) * 32) for i in range(3)]
    lb = block_of(ValidatorSet([Validator(p.pub_key(), 10) for p in privs]), privs)
    assert {len(v.pub_key.bytes()) for v in lb.validator_set.validators} == {48}
    assert {len(cs.signature) for cs in lb.signed_header.commit.signatures} == {96}
    return lb


def mixed_set():
    """ed25519, sr25519 and BLS12-381 in one set: key and signature widths
    differ from row to row, and one signer is absent."""
    privs = make_keys(b"\x33", 3) + [K.gen_bls12_381(bytes([0x70 + i]) * 32) for i in range(2)]
    if gen_sr25519 is not None:
        privs += [gen_sr25519(bytes([90 + i]) * 32) for i in range(2)]
    vals = ValidatorSet([Validator(p.pub_key(), 5 + i) for i, p in enumerate(privs)])

    def sigs(rows):
        rows[2] = CommitSig.absent_sig()
        return rows
    lb = block_of(vals, privs, sigs=sigs)
    assert len({len(v.pub_key.bytes()) for v in vals.validators}) == 2
    assert len({len(cs.signature) for cs in lb.signed_header.commit.signatures}) == 3
    return lb


needs_sr25519 = pytest.mark.skipif(gen_sr25519 is None, reason="no sr25519 backend")
BLOCKS = [all_signed_100, absent_and_nil, priorities_and_powers, another_proposer,
          one_validator, pytest.param(sr25519_set, marks=needs_sr25519), bls_set, mixed_set]


@pytest.fixture(scope="module")
def block_100():
    return all_signed_100()


@pytest.mark.parametrize("make", BLOCKS)
def test_a_saved_block_is_read_back_equal_field_by_field(make):
    lb = make()
    before = fields(lb)
    store = LightStore(MemDB())
    assert store.save_light_block(lb) == len(store.db.get(key_of(lb.height)))
    rt = LightStore(store.db).light_block(lb.height)  # a fresh store over the same db
    assert rt is not lb and fields(rt) == before == fields(lb)
    assert rt.signed_header == lb.signed_header
    # the proposer is the set's own row, as validator_set_from_json leaves it
    assert any(rt.validator_set.proposer is v for v in rt.validator_set.validators)
    rt.validate_basic(CHAIN_ID)
    assert rt.hash() == lb.hash() and rt.validator_set.hash() == lb.validator_set.hash()


@pytest.mark.parametrize("make", BLOCKS)
def test_a_record_never_begins_as_json_does_and_is_written_the_same_twice(make):
    lb = make()
    record = light_block_to_bytes(lb)
    assert record[:1] == b"\x01" != b"{"
    assert light_block_to_bytes(light_block_from_bytes(record)) == record


def test_a_set_without_validators_and_a_commit_without_signatures_come_back():
    lb = absent_and_nil()
    bare = LightBlock(
        SignedHeader(lb.header, Commit(lb.height, 0, BlockID(), [])), ValidatorSet([]))
    rt = light_block_from_bytes(light_block_to_bytes(bare))
    assert fields(rt) == fields(bare) and rt.validator_set.proposer is None


def test_a_proposer_that_is_no_row_of_the_set_is_recomputed_as_from_json():
    """validator_set_from_json looks the saved proposer up by address and
    keeps the computed one where the set has no such row: so does the record."""
    lb = one_validator()
    stranger = Validator(make_keys(b"\x44", 1)[0].pub_key(), 10)
    lb.validator_set.proposer = stranger
    rt = light_block_from_bytes(light_block_to_bytes(lb))
    assert rt.validator_set.proposer is rt.validator_set.validators[0]


def test_the_record_is_under_half_the_json(block_100):
    as_json = json.dumps(light_block_to_json(block_100), separators=(",", ":")).encode()
    record = light_block_to_bytes(block_100)
    assert len(record) * 2 < len(as_json)
    assert 14_000 < len(record) < 15_500  # 93 bytes a signature, 49 a validator, the header


def test_saving_leaves_nothing_on_the_blocks_objects(block_100):
    """No encoded bytes, column or fragment is kept on a LightBlock, its
    commit, its set, a validator or a key (BlockID's wire memo is older than
    the record and its own)."""
    lb = block_100
    watched = [lb, lb.signed_header, lb.signed_header.header, lb.signed_header.commit,
               lb.validator_set, *lb.signed_header.commit.signatures,
               *lb.validator_set.validators, *[v.pub_key for v in lb.validator_set.validators]]
    before = [dict(vars(o)) for o in watched]
    LightStore(MemDB()).save_light_block(lb)
    assert [dict(vars(o)) for o in watched] == before


@pytest.mark.parametrize("make", [all_signed_100, mixed_set])
def test_an_old_json_record_is_read_and_rewritten_when_saved_again(make):
    lb = make()
    as_json = json.dumps(light_block_to_json(lb), separators=(",", ":")).encode()  # the parent's
    db = MemDB()
    db.set(key_of(lb.height), as_json)
    store = LightStore(db)
    assert store.heights() == [lb.height]
    rt = store.light_block(lb.height)
    assert fields(rt) == fields(lb)
    assert db.get(key_of(lb.height)) == as_json  # reading rewrites nothing
    store.save_light_block(rt)
    assert db.get(key_of(lb.height)) == light_block_to_bytes(lb)
    assert fields(LightStore(db).light_block(lb.height)) == fields(lb)
    assert store.heights() == [lb.height]


@pytest.mark.parametrize("share", [i / 16 for i in range(16)])
def test_a_truncated_record_is_refused(block_100, share):
    record = light_block_to_bytes(block_100)
    for cut in {int(len(record) * share), int(len(record) * share) + 1}:
        with pytest.raises(ValueError):
            light_block_from_bytes(record[:cut])


@pytest.mark.parametrize("make", [one_validator, mixed_set])
def test_a_record_cut_at_any_length_is_refused(make):
    record = light_block_to_bytes(make())
    for cut in range(len(record)):
        with pytest.raises(ValueError):
            light_block_from_bytes(record[:cut])
    assert light_block_from_bytes(record).height == 7


@pytest.mark.parametrize("bad", [
    lambda r: r + b"\x00",
    lambda r: r + r,
    lambda r: b"\x02" + r[1:],
    lambda r: b"\x00" + r[1:],
    lambda r: b"\xff" + r[1:],
    lambda r: b"[" + r[1:],
    lambda r: b"",
], ids=["a_trailing_byte", "a_second_record", "version_2", "version_0", "version_255",
        "a_json_list", "nothing"])
def test_a_record_with_bytes_left_over_or_an_unknown_version_is_refused(block_100, bad):
    with pytest.raises(ValueError):
        light_block_from_bytes(bad(light_block_to_bytes(block_100)))


@pytest.mark.parametrize("name,at,value", [
    ("a flag that is no BlockIDFlag", "flags", b"\x09"),
    ("a proposer past the set", "proposer", (100).to_bytes(4, "big")),
    ("a key type past the table", "key_type", b"\x01"),
])
def test_a_record_whose_lengths_fit_and_whose_values_do_not_is_refused(block_100, name, at, value):
    record = light_block_to_bytes(block_100)
    header_len = int.from_bytes(record[1:5], "big")
    block_id_len = int.from_bytes(record[5 + header_len:9 + header_len], "big")
    flags = 9 + header_len + block_id_len + 16
    n_vals = flags + 100 + (4 + 100 * 20) + 100 * 8 + (4 + 100 * 64)
    where = {"flags": flags, "proposer": n_vals + 4, "key_type": n_vals + 9 + 4 + 7}[at]
    with pytest.raises(ValueError):
        light_block_from_bytes(record[:where] + value + record[where + len(value):])


def test_a_store_refuses_a_bad_record_and_leaves_it_under_its_key(block_100):
    db = MemDB()
    store = LightStore(db)
    store.save_light_block(block_100)
    cut = db.get(key_of(7))[:-3]
    db.set(key_of(7), cut)
    with pytest.raises(ValueError):
        store.light_block(7)
    assert db.get(key_of(7)) == cut and store.heights() == [7]


def test_a_value_that_does_not_fit_64_bits_is_refused_before_anything_is_written():
    lb = one_validator()
    lb.validator_set.validators[0].proposer_priority = 2**63
    store = LightStore(MemDB())
    with pytest.raises(ValueError):
        store.save_light_block(lb)
    assert store.heights() == [] and store.db.get(key_of(7)) is None


def test_a_sqlite_store_reopened_reads_every_block_back(tmp_path):
    privs = make_keys(b"\x55", 9)
    blocks = make_chain(5, default_privs=privs)
    path = str(tmp_path / "light.db")
    db = SQLiteDB(path)
    store = LightStore(db)
    written = sum(store.save_light_block(lb) for lb in blocks.values())
    db.close()
    db = SQLiteDB(path)
    reopened = LightStore(db)
    assert reopened.heights() == [1, 2, 3, 4, 5]
    assert written == sum(len(db.get(key_of(h))) for h in blocks)
    for h, lb in blocks.items():
        assert fields(reopened.light_block(h)) == fields(lb)
        reopened.light_block(h).validate_basic(CHAIN_ID)
    assert reopened.latest_light_block().height == 5
    assert reopened.light_block_before(3).height == 2
    db.close()
