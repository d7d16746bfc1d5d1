"""The plain reference: what VerifyCommit means, written straight from the
Tendermint specification with nothing of the program in it. Canonical vote
sign bytes (types/canonical.go, canonical.proto) are encoded here, each row
is checked by one OpenSSL ed25519 verify, and the verdict is the
reference's: every signature checked, the first bad index named, +2/3 of
the power for the block.

For a chain of signed headers (a configuration that states `headers`) it
also has, from the same specification: the Merkle tree of RFC 6962, the
header's hash over its fourteen encoded fields, a validator set's hash, and
the finding `link_ok`: whether a signed header is the one its predecessor
committed to.

On honest and on bit-flipped signatures OpenSSL's (cofactorless) verify and
the configuration's cofactored predicate agree; they differ only on crafted
small-torsion inputs, which no mix of this benchmark generates."""

from __future__ import annotations

import hashlib

from cryptography.exceptions import InvalidSignature
from cryptography.hazmat.primitives.asymmetric.ed25519 import Ed25519PublicKey

PRECOMMIT = 2
FLAG_ABSENT, FLAG_COMMIT = 1, 2


def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        if n:
            out.append(b | 0x80)
        else:
            out.append(b)
            return bytes(out)


def _ld(field: int, body: bytes) -> bytes:
    return _varint(field << 3 | 2) + _varint(len(body)) + body


def _sfixed64(field: int, v: int) -> bytes:
    return _varint(field << 3 | 1) + (v & (2**64 - 1)).to_bytes(8, "little")


def _uvarint_field(field: int, v: int) -> bytes:
    """A proto3 varint field; zero is left out. A negative int64 is its
    two's complement in ten bytes."""
    return _varint(field << 3) + _varint(v & (2**64 - 1)) if v else b""


def _bytes_field(field: int, body: bytes) -> bytes:
    """A proto3 bytes or string field; empty is left out."""
    return _ld(field, body) if body else b""


def address(pubkey: bytes) -> bytes:
    return hashlib.sha256(pubkey).digest()[:20]


def merkle_root(leaves) -> bytes:
    """RFC 6962 section 2.1 as Tendermint uses it (spec: crypto/merkle): a
    leaf hashes as SHA-256(0x00 || leaf), an inner node as SHA-256(0x01 ||
    left || right), the split is the largest power of two under the count,
    and the empty tree hashes the empty string."""
    n = len(leaves)
    if n == 0:
        return hashlib.sha256(b"").digest()
    if n == 1:
        return hashlib.sha256(b"\x00" + leaves[0]).digest()
    k = 1 << (n - 1).bit_length() - 1
    return hashlib.sha256(b"\x01" + merkle_root(leaves[:k]) + merkle_root(leaves[k:])).digest()


def validators_hash(pubkeys, powers) -> bytes:
    """ValidatorSet.Hash (spec: core/data_structures, ValidatorSet): the
    Merkle root over each validator, in the set's order, as SimpleValidator
    {pub_key = 1 {ed25519 = 1}, voting_power = 2}."""
    return merkle_root([_ld(1, _ld(1, pk)) + _uvarint_field(2, power)
                        for pk, power in zip(pubkeys, powers)])


HEADER_FIELDS = ("version_block", "version_app", "chain_id", "height", "time_ns",
                 "last_block_hash", "last_parts_total", "last_parts_hash", "last_commit_hash",
                 "data_hash", "validators_hash", "next_validators_hash", "consensus_hash",
                 "app_hash", "last_results_hash", "evidence_hash", "proposer_address")


def header_hash(h: dict) -> bytes:
    """Header.Hash (spec: core/data_structures, Header): the Merkle root over
    the fourteen fields, each encoded on its own: version as Consensus {block
    = 1, app = 2}, chain id, height and the byte fields each wrapped in a
    message of one field 1 (StringValue, Int64Value, BytesValue), the time as
    Timestamp {seconds = 1, nanos = 2}, the last block id as BlockID {hash =
    1, part_set_header = 2 {total = 1, hash = 2}} whose part-set header is
    always written. `h` holds the plain values under HEADER_FIELDS."""
    sec, nanos = divmod(h["time_ns"], 10**9)
    psh = _uvarint_field(1, h["last_parts_total"]) + _bytes_field(2, h["last_parts_hash"])
    return merkle_root([
        _uvarint_field(1, h["version_block"]) + _uvarint_field(2, h["version_app"]),
        _bytes_field(1, h["chain_id"].encode()),
        _uvarint_field(1, h["height"]),
        _uvarint_field(1, sec) + _uvarint_field(2, nanos),
        _bytes_field(1, h["last_block_hash"]) + _ld(2, psh),
    ] + [_bytes_field(1, h[k]) for k in HEADER_FIELDS[8:]])


def link_ok(trusted: dict, header: dict, block_hash: bytes, height: int, pubkeys, powers) -> bool:
    """Is `header`, with the commit for (`height`, `block_hash`) and the set
    (`pubkeys`, `powers`) that signed it, the one the trusted header before
    it committed to? The header-side conditions of sequential verification
    (spec: light-client/verification, adjacent headers; LightBlock and
    SignedHeader ValidateBasic): the commit is for this header, the set is the
    header's, the header's set is the one its predecessor named as next, the
    chain is the trusted header's, the height is the next one and the time
    rises. The signatures and the tally are the verdict rule's."""
    return (header["chain_id"] == trusted["chain_id"]
            and height == header["height"] == trusted["height"] + 1
            and header["time_ns"] > trusted["time_ns"]
            and header_hash(header) == block_hash
            and validators_hash(pubkeys, powers) == header["validators_hash"]
            and header["validators_hash"] == trusted["next_validators_hash"])


class SignBytes:
    """Length-delimited CanonicalVote of one (chain, height, round, block):
    type=1 varint, height=2 and round=3 sfixed64 (zero left out), block_id=4
    {hash=1, part_set_header=2 {total=1, hash=2}}, timestamp=5 {seconds=1,
    nanos=2} always present, chain_id=6. Only the timestamp differs from
    row to row."""

    def __init__(self, chain_id: str, height: int, round_: int,
                 block_hash: bytes, parts_total: int, parts_hash: bytes):
        head = _varint(1 << 3) + _varint(PRECOMMIT)
        if height:
            head += _sfixed64(2, height)
        if round_:
            head += _sfixed64(3, round_)
        psh = (_varint(1 << 3) + _varint(parts_total) if parts_total else b"") \
            + _ld(2, parts_hash)
        self.head = head + _ld(4, _ld(1, block_hash) + _ld(2, psh))
        self.tail = _ld(6, chain_id.encode())

    def of(self, timestamp_ns: int) -> bytes:
        sec, nanos = divmod(timestamp_ns, 10**9)
        ts = (_varint(1 << 3) + _varint(sec) if sec else b"") \
            + (_varint(2 << 3) + _varint(nanos) if nanos else b"")
        body = self.head + _ld(5, ts) + self.tail
        return _varint(len(body)) + body


def verify_rows(pubkeys, msgs, sigs) -> list:
    """One OpenSSL verify per row."""
    keys = {}
    out = []
    for pk, msg, sig in zip(pubkeys, msgs, sigs):
        key = keys.get(pk)
        if key is None:
            key = keys[pk] = Ed25519PublicKey.from_public_bytes(pk)
        try:
            key.verify(sig, msg)
            out.append(True)
        except InvalidSignature:
            out.append(False)
    return out


def commit_verdict(mask, present, powers, total_power: int) -> str:
    """types/validator_set.go VerifyCommit over the reference's row mask:
    `present` are the validator indices that signed, in order."""
    tallied = 0
    for ok, idx in zip(mask, present):
        if not ok:
            return f"wrong signature (#{idx})"
        tallied += powers[idx]
    if tallied <= total_power * 2 // 3:
        return "not enough voting power"
    return "accepted"


def verdict(mask, signers, powers, total_power, blocks) -> str:
    """VerifyCommit in the form of a verdict rule (references/<name>.py): what
    a configuration that names no `verdict_rule` is held to. `mask` and
    `signers` are the item's rows in block order, `blocks` a block's height
    and row count each (a chain item's also its own `powers`, `total_power`
    and `link_ok`, which a rule for chains reads); VerifyCommit speaks of one
    commit against the one set."""
    if len(blocks) != 1:
        raise ValueError(f"VerifyCommit judges one commit, the item holds {len(blocks)}: "
                         "the configuration has to name its verdict_rule")
    return commit_verdict(mask, signers, powers, total_power)
