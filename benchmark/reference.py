"""The plain reference: what VerifyCommit means, written straight from the
Tendermint specification with nothing of the program in it. Canonical vote
sign bytes (types/canonical.go, canonical.proto) are encoded here, each row
is checked by one OpenSSL ed25519 verify, and the verdict is the
reference's: every signature checked, the first bad index named, +2/3 of
the power for the block.

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


def address(pubkey: bytes) -> bytes:
    return hashlib.sha256(pubkey).digest()[:20]


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
    and row count each; VerifyCommit speaks of one commit."""
    if len(blocks) != 1:
        raise ValueError(f"VerifyCommit judges one commit, the item holds {len(blocks)}: "
                         "the configuration has to name its verdict_rule")
    return commit_verdict(mask, signers, powers, total_power)
