"""Vote type (reference: types/vote.go).

A Vote is a signed prevote or precommit for a block (or nil). Sign-bytes are
the canonical length-delimited proto (tendermint_tpu.types.canonical); the wire
encoding mirrors proto/tendermint/types/types.proto Vote (fields 1-8).
"""

from __future__ import annotations

from dataclasses import dataclass, field, replace

from tendermint_tpu.crypto.keys import PubKey
from tendermint_tpu.libs import protowire as pw
from tendermint_tpu.types import canonical
from tendermint_tpu.types.basic import BlockID, SignedMsgType, ts_seconds_nanos

# Instrumentation: actual protowire/sign-bytes COMPUTES (cache misses), not
# calls. A Vote is immutable post-construction, so each instance should pay
# for each at most once no matter how many ingest layers serialize it (WAL
# frame, gossip re-send, verify). tests/test_hotpath_guard.py budgets these
# per vote; a new call site that bypasses the memo shows up as a counter
# regression there, not as a wall-clock flake.
ENCODE_COMPUTES = 0
SIGN_BYTES_COMPUTES = 0


@dataclass(frozen=True)
class Vote:
    type: SignedMsgType
    height: int
    round: int
    block_id: BlockID
    timestamp_ns: int
    validator_address: bytes
    validator_index: int
    signature: bytes = b""

    def is_nil(self) -> bool:
        return self.block_id.is_zero()

    def sign_bytes(self, chain_id: str) -> bytes:
        """Canonical sign-bytes, memoized per instance (a Vote's fields are
        frozen, so the result can never go stale; dataclasses.replace — e.g.
        with_signature — builds a NEW instance with an empty cache)."""
        cached = self.__dict__.get("_sign_bytes")
        if cached is not None and cached[0] == chain_id:
            return cached[1]
        global SIGN_BYTES_COMPUTES
        SIGN_BYTES_COMPUTES += 1
        data = canonical.vote_sign_bytes(
            chain_id, self.type, self.height, self.round, self.block_id, self.timestamp_ns
        )
        object.__setattr__(self, "_sign_bytes", (chain_id, data))
        return data

    def seed_sign_bytes(self, chain_id: str, data: bytes) -> None:
        """Prime the sign-bytes memo from a batched builder
        (canonical.vote_sign_bytes_many) so a follow-up serial verify does
        not re-run the per-vote encoder. `data` is length-delimited, exactly
        what sign_bytes returns."""
        object.__setattr__(self, "_sign_bytes", (chain_id, data))

    def verify(self, chain_id: str, pubkey: PubKey) -> bool:
        """Serial verification (reference: types/vote.go:149). The batched path
        goes through crypto.batch instead."""
        from tendermint_tpu.crypto.keys import address_from_pubkey_bytes

        if address_from_pubkey_bytes(pubkey.bytes()) != self.validator_address:
            return False
        return pubkey.verify(self.sign_bytes(chain_id), self.signature)

    def validate_basic(self) -> None:
        if self.type not in (SignedMsgType.PREVOTE, SignedMsgType.PRECOMMIT):
            raise ValueError("invalid vote type")
        if self.height < 0:
            raise ValueError("negative height")
        if self.round < 0:
            raise ValueError("negative round")
        self.block_id.validate_basic()
        if not self.block_id.is_zero() and not self.block_id.is_complete():
            raise ValueError(f"blockID must be either empty or complete, got: {self.block_id}")
        if len(self.validator_address) != 20:
            raise ValueError("wrong validator address size")
        if self.validator_index < 0:
            raise ValueError("negative validator index")
        if not self.signature:
            raise ValueError("signature is missing")
        # 96 = compressed-G2 BLS signature; ed25519/sr25519 remain 64
        # (reference caps at MaxSignatureSize=64; raised for the BLS
        # aggregate backend, docs/BLS.md)
        if len(self.signature) > 96:
            raise ValueError("signature too big")

    def with_signature(self, sig: bytes) -> "Vote":
        return replace(self, signature=sig)

    # Precomputed field tags for the flattened encoder below (byte-identical
    # to the Writer-built form; pinned by the decode round-trip tests).
    _T1 = pw.tag(1, pw.VARINT)
    _T2 = pw.tag(2, pw.VARINT)
    _T3 = pw.tag(3, pw.VARINT)
    _T4 = pw.tag(4, pw.BYTES)
    _T5 = pw.tag(5, pw.BYTES)
    _T6 = pw.tag(6, pw.BYTES)
    _T7 = pw.tag(7, pw.VARINT)
    _T8 = pw.tag(8, pw.BYTES)

    # Wire encoding (proto Vote, fields per types.proto), memoized per
    # instance: the ingest path serializes the same Vote for the WAL frame
    # and again for every gossip re-send — immutable post-construction, so
    # one protowire pass serves them all. Flattened (no Writer objects):
    # this runs once per vote on the live receive loop.
    def encode(self) -> bytes:
        cached = self.__dict__.get("_wire")
        if cached is not None:
            return cached
        global ENCODE_COMPUTES
        ENCODE_COMPUTES += 1
        enc = pw.encode_varint
        parts = []
        t = int(self.type)
        if t:
            parts.append(self._T1 + enc(t))
        if self.height:
            parts.append(self._T2 + enc(self.height))
        if self.round:
            parts.append(self._T3 + enc(self.round))
        bid = self.block_id.encode()
        parts.append(self._T4 + enc(len(bid)) + bid)
        sec, nanos = ts_seconds_nanos(self.timestamp_ns)
        ts = pw.encode_timestamp(sec, nanos)
        parts.append(self._T5 + enc(len(ts)) + ts)
        if self.validator_address:
            parts.append(self._T6 + enc(len(self.validator_address)) + self.validator_address)
        if self.validator_index:
            parts.append(self._T7 + enc(self.validator_index))
        if self.signature:
            parts.append(self._T8 + enc(len(self.signature)) + self.signature)
        data = b"".join(parts)
        object.__setattr__(self, "_wire", data)
        return data

    @classmethod
    def decode(cls, data: bytes) -> "Vote":
        vals = {
            "type": SignedMsgType.UNKNOWN,
            "height": 0,
            "round": 0,
            "block_id": BlockID(),
            "timestamp_ns": 0,
            "validator_address": b"",
            "validator_index": 0,
            "signature": b"",
        }
        for f, _, v in pw.Reader(data):
            if f == 1:
                vals["type"] = SignedMsgType(v)
            elif f == 2:
                vals["height"] = pw.int64_from_varint(v)
            elif f == 3:
                vals["round"] = pw.int64_from_varint(v)
            elif f == 4:
                vals["block_id"] = BlockID.decode(v)
            elif f == 5:
                sec = nanos = 0
                for ff, _, vv in pw.Reader(v):
                    if ff == 1:
                        sec = pw.int64_from_varint(vv)
                    elif ff == 2:
                        nanos = pw.int64_from_varint(vv)
                vals["timestamp_ns"] = sec * 1_000_000_000 + nanos
            elif f == 6:
                vals["validator_address"] = v
            elif f == 7:
                vals["validator_index"] = pw.int64_from_varint(v)
            elif f == 8:
                vals["signature"] = v
        return cls(**vals)
