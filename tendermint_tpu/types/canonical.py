"""Canonical sign-bytes construction.

Byte-exact re-implementation of the reference's canonicalization + gogoproto
marshaling (reference: types/canonical.go, proto/tendermint/types/canonical.proto,
proto/tendermint/types/canonical.pb.go MarshalToSizedBuffer):

- fields in ascending order; zero scalars omitted; nil BlockID omitted
- height/round as sfixed64 (fixed size for deterministic length)
- timestamp ALWAYS emitted (gogo non-nullable stdtime)
- the final sign-bytes are length-delimited (protoio.MarshalDelimited)
"""

from __future__ import annotations

import numpy as np

from tendermint_tpu import native
from tendermint_tpu.libs import protowire as pw
from tendermint_tpu.types.basic import BlockID, SignedMsgType, ts_seconds_nanos


def canonical_block_id_bytes(block_id: BlockID) -> bytes | None:
    """None for a zero BlockID (reference: types/canonical.go:18-34)."""
    if block_id is None or block_id.is_zero():
        return None
    w = pw.Writer()
    w.bytes_field(1, block_id.hash)
    psh = pw.Writer()
    psh.varint_field(1, block_id.part_set_header.total)
    psh.bytes_field(2, block_id.part_set_header.hash)
    w.message_field(2, psh.bytes(), always=True)
    return w.bytes()


def _timestamp_bytes(ts_ns: int) -> bytes:
    sec, nanos = ts_seconds_nanos(ts_ns)
    return pw.encode_timestamp(sec, nanos)


def canonical_vote_bytes(
    msg_type: SignedMsgType,
    height: int,
    round_: int,
    block_id: BlockID,
    timestamp_ns: int,
    chain_id: str,
) -> bytes:
    """CanonicalVote marshal (fields: type=1, height=2 sfixed64, round=3
    sfixed64, block_id=4, timestamp=5, chain_id=6)."""
    w = pw.Writer()
    w.varint_field(1, int(msg_type))
    w.sfixed64_field(2, height)
    w.sfixed64_field(3, round_)
    w.message_field(4, canonical_block_id_bytes(block_id))
    w.message_field(5, _timestamp_bytes(timestamp_ns), always=True)
    w.string_field(6, chain_id)
    return w.bytes()


def canonical_proposal_bytes(
    height: int,
    round_: int,
    pol_round: int,
    block_id: BlockID,
    timestamp_ns: int,
    chain_id: str,
) -> bytes:
    """CanonicalProposal marshal (type=1, height=2, round=3, pol_round=4 int64,
    block_id=5, timestamp=6, chain_id=7)."""
    w = pw.Writer()
    w.varint_field(1, int(SignedMsgType.PROPOSAL))
    w.sfixed64_field(2, height)
    w.sfixed64_field(3, round_)
    w.varint_field(4, pol_round)  # int64 varint; -1 encodes as 10 bytes
    w.message_field(5, canonical_block_id_bytes(block_id))
    w.message_field(6, _timestamp_bytes(timestamp_ns), always=True)
    w.string_field(7, chain_id)
    return w.bytes()


def vote_sign_bytes(
    chain_id: str,
    msg_type: SignedMsgType,
    height: int,
    round_: int,
    block_id: BlockID,
    timestamp_ns: int,
) -> bytes:
    """Length-delimited canonical vote (reference: types/vote.go:95 VoteSignBytes)."""
    return pw.length_delimited(
        canonical_vote_bytes(msg_type, height, round_, block_id, timestamp_ns, chain_id)
    )


# Below this many rows the Python loop is the cheaper builder: the native
# call has a fixed cost (two column arrays, the part table, a dozen ctypes
# arguments) that the loop's per-row cost only overtakes here (CHANGES.md,
# PR 27, states the measurement). A live vote flush of a handful of rows
# stays on the loop; a commit takes the native pass.
NATIVE_MIN_ROWS = 20


def vote_sign_bytes_many(
    chain_id: str,
    msg_type: SignedMsgType,
    height: int,
    round_: int,
    rows,
) -> list:
    """Batched vote_sign_bytes for rows sharing (chain_id, type, height,
    round): `rows` is an iterable of (block_id, timestamp_ns).

    A vote storm / commit shares everything except the BlockID (a handful of
    distinct values, hundreds from Byzantine voters) and the timestamp, so
    the rows become two columns, an index into the table of distinct block
    ids and the timestamp, for the builders of vote_sign_bytes_columns.
    Byte-identical to vote_sign_bytes per row (differentially tested)."""
    block_ids: list = []
    index: dict = {}
    sel = []
    ts_ns = []
    for block_id, ts in rows:
        bkey = None if block_id is None else block_id.key()
        k = index.get(bkey)
        if k is None:
            k = index[bkey] = len(block_ids)
            block_ids.append(block_id)
        sel.append(k)
        ts_ns.append(ts)
    out, _ = vote_sign_bytes_columns(chain_id, msg_type, height, round_, block_ids, sel, ts_ns)
    return out


def vote_sign_bytes_columns(
    chain_id: str,
    msg_type: SignedMsgType,
    height: int,
    round_: int,
    block_ids,
    sel,
    ts_ns,
) -> tuple:
    """vote_sign_bytes for row i = (block_ids[sel[i]], ts_ns[i]), everything
    else shared -> (list of sign bytes, the builder that wrote them).

    The shared prefix (type, height, round), the suffix (chain_id) and each
    distinct block-id part are encoded ONCE. Builder "native": one C pass
    (native.vote_sign_bytes) writes every row into one blob, sliced back
    into bytes here. Builder "python": the same rows by a loop, where the
    library is absent (or TMTPU_NATIVE=0), under NATIVE_MIN_ROWS rows, or
    for a timestamp outside int64 (a hostile commit can decode to one).
    Nothing is kept between calls: every call encodes every row."""
    w = pw.Writer()
    w.varint_field(1, int(msg_type))
    w.sfixed64_field(2, height)
    w.sfixed64_field(3, round_)
    prefix = w.bytes()
    sw = pw.Writer()
    sw.string_field(6, chain_id)
    suffix = sw.bytes()
    tag4 = pw.tag(4, pw.BYTES)
    parts = []
    for block_id in block_ids:
        body = canonical_block_id_bytes(block_id)
        parts.append(b"" if body is None else tag4 + pw.encode_varint(len(body)) + body)
    if len(ts_ns) >= NATIVE_MIN_ROWS and native.available():
        out = _native_rows(prefix, parts, sel, ts_ns, suffix)
        if out is not None:
            return out, "native"
    return _python_rows(prefix, parts, sel, ts_ns, suffix), "python"


def _native_rows(prefix, parts, sel, ts_ns, suffix) -> "list | None":
    try:
        ts_col = np.asarray(ts_ns, dtype=np.int64)
    except OverflowError:  # a timestamp outside int64: the loop takes it
        return None
    blob, offs = native.vote_sign_bytes(prefix, parts, sel, ts_col, suffix)
    offs = offs.tolist()
    return [blob[a:b] for a, b in zip(offs, offs[1:])]


def _python_rows(prefix, parts, sel, ts_ns, suffix) -> list:
    tag5 = pw.tag(5, pw.BYTES)
    enc = pw.encode_varint
    # Whole-row memo: a vote storm's rows mostly share (block_id, timestamp)
    # entirely: a dict hit replaces the encode for those.
    row_cache: dict = {}
    out = []
    for key in zip(sel, ts_ns):
        row = row_cache.get(key)
        if row is None:
            k, ts = key
            tb = _timestamp_bytes(ts)
            body = prefix + parts[k] + tag5 + enc(len(tb)) + tb + suffix
            row = row_cache[key] = enc(len(body)) + body
        out.append(row)
    return out


def proposal_sign_bytes(
    chain_id: str,
    height: int,
    round_: int,
    pol_round: int,
    block_id: BlockID,
    timestamp_ns: int,
) -> bytes:
    """Length-delimited canonical proposal (reference: types/proposal.go ProposalSignBytes)."""
    return pw.length_delimited(
        canonical_proposal_bytes(height, round_, pol_round, block_id, timestamp_ns, chain_id)
    )
