"""vote_sign_bytes_many must be byte-identical to the per-row builder,
whichever builder writes the rows (PR 27: one native pass over two columns,
or the Python loop where the library is absent or the batch is tiny)."""

import os
import subprocess
import sys

import pytest

from tendermint_tpu import native
from tendermint_tpu.types import canonical
from tendermint_tpu.types.basic import SignedMsgType
from tendermint_tpu.types.block import BlockID, PartSetHeader


def test_vote_sign_bytes_many_matches_per_row():
    bid = BlockID(b"\x01" * 32, PartSetHeader(3, b"\x02" * 32))
    nil = BlockID(b"", PartSetHeader(0, b""))
    rows = [
        (bid, 0),
        (nil, 0),
        (bid, 1),
        (bid, 1_700_000_000_123_456_789),
        (None, 5),
        (bid, 999_999_999),  # nanos boundary
        (nil, 1 << 40),
    ]
    for msg_type in (SignedMsgType.PREVOTE, SignedMsgType.PRECOMMIT):
        for h, r in ((1, 0), (12345, 7), (1 << 40, 2)):
            many = canonical.vote_sign_bytes_many("chain-x", msg_type, h, r, rows)
            for got, (b, ts) in zip(many, rows):
                exp = canonical.vote_sign_bytes("chain-x", msg_type, h, r, b, ts)
                assert got == exp


def test_commit_vote_sign_bytes_many_matches_per_row():
    import dataclasses

    from tendermint_tpu.crypto.keys import gen_ed25519
    from tendermint_tpu.types.basic import BlockIDFlag
    from tendermint_tpu.types.block import Commit, CommitSig

    bid = BlockID(b"\x03" * 32, PartSetHeader(2, b"\x04" * 32))
    sigs = []
    for i in range(6):
        flag = [BlockIDFlag.COMMIT, BlockIDFlag.NIL, BlockIDFlag.COMMIT][i % 3]
        sigs.append(
            CommitSig(flag, bytes([i + 1]) * 20, 1000 + i, bytes([i]) * 64)
        )
    commit = Commit(9, 1, bid, tuple(sigs))
    idxs = [0, 2, 3, 5]
    many = commit.vote_sign_bytes_many("c", idxs)
    for got, i in zip(many, idxs):
        assert got == commit.vote_sign_bytes("c", i)


# --- the columnar builders: native C pass and Python loop --------------------

BID = BlockID(b"\x01" * 32, PartSetHeader(3, b"\x02" * 32))
NIL = BlockID(b"", PartSetHeader(0, b""))
I64_MIN, I64_MAX = -(1 << 63), (1 << 63) - 1
TIMESTAMPS = {
    "zero": 0,
    "one-ns": 1,
    "nanos-max": 999_999_999,
    "whole-second": 10**9,
    "minus-one": -1,
    "minus-five": -5,
    "int64-min": I64_MIN,
    "int64-max": I64_MAX,
    "present-day": 1_790_000_000_123_456_789,
}
MIN = canonical.NATIVE_MIN_ROWS


def _mixed_rows(n, block_ids=(BID, NIL, None)):
    """n rows cycling through every timestamp and the given block ids."""
    return [(block_ids[i % len(block_ids)], _ts(i)) for i in range(n)]


def _ts(i):
    """Row i's timestamp: the i-th boundary value, moved by a few ns on
    every lap so that rows differ, and kept inside int64."""
    ts = list(TIMESTAMPS.values())[i % len(TIMESTAMPS)]
    lap = i // len(TIMESTAMPS)
    return ts - lap if ts == I64_MAX else ts + lap


def _distinct_block_ids(k):
    return tuple(
        BlockID(i.to_bytes(32, "big"), PartSetHeader(i % 7 + 1, (i * 31).to_bytes(32, "big")))
        for i in range(1, k + 1)
    )


# name -> (chain_id, msg_type, height, round, rows)
_DEFAULT = ("test_chain_id", SignedMsgType.PRECOMMIT, 12345, 7)
CASES = {
    **{
        f"ts-{name}": (*_DEFAULT, [(b, ts) for b in (BID, NIL, None)] * 8)
        for name, ts in TIMESTAMPS.items()
    },
    # outside int64: a hostile Commit.decode can produce one; the loop takes it
    "ts-over-int64": (*_DEFAULT, [(BID, 1 << 63), (NIL, I64_MIN - 1), (BID, 10**30)] * 8),
    "bid-nil": (*_DEFAULT, _mixed_rows(27, (NIL,))),
    "bid-none": (*_DEFAULT, _mixed_rows(27, (None,))),
    "bid-commit": (*_DEFAULT, _mixed_rows(27, (BID,))),
    "bid-300-distinct": (*_DEFAULT, _mixed_rows(900, _distinct_block_ids(300))),
    "height0-round0": ("test_chain_id", SignedMsgType.PRECOMMIT, 0, 0, _mixed_rows(27)),
    "height1-round0": ("test_chain_id", SignedMsgType.PRECOMMIT, 1, 0, _mixed_rows(27)),
    "height-round-large": ("test_chain_id", SignedMsgType.PRECOMMIT, I64_MAX, (1 << 31) - 1, _mixed_rows(27)),
    "prevote": ("test_chain_id", SignedMsgType.PREVOTE, 12345, 7, _mixed_rows(27)),
    "chain-empty": ("", SignedMsgType.PRECOMMIT, 12345, 7, _mixed_rows(27)),
    # body over 127 bytes: the outer length takes two bytes
    "chain-200-chars": ("c" * 200, SignedMsgType.PRECOMMIT, 12345, 7, _mixed_rows(27)),
    "n-0": (*_DEFAULT, []),
    "n-1": (*_DEFAULT, _mixed_rows(1)),
    "n-crossover-minus-1": (*_DEFAULT, _mixed_rows(MIN - 1)),
    "n-crossover": (*_DEFAULT, _mixed_rows(MIN)),
    "n-crossover-plus-1": (*_DEFAULT, _mixed_rows(MIN + 1)),
    "n-10000": (*_DEFAULT, _mixed_rows(10_000)),
}


@pytest.fixture
def builder(request, monkeypatch):
    """The builder a case runs under, and a count of the native calls made."""
    calls = []
    if request.param == "python":
        monkeypatch.setattr(native, "available", lambda: False)
    elif not native.available():
        pytest.skip("native batchhost unavailable (no compiler?)")
    real = native.vote_sign_bytes
    monkeypatch.setattr(
        native, "vote_sign_bytes", lambda *a: calls.append(len(a[3])) or real(*a)
    )
    return request.param, calls


@pytest.mark.parametrize("builder", ["native", "python"], indirect=True)
@pytest.mark.parametrize("case", CASES)
def test_columnar_builders_match_per_row(case, builder):
    which, native_calls = builder
    chain_id, msg_type, h, r, rows = CASES[case]
    many = canonical.vote_sign_bytes_many(chain_id, msg_type, h, r, iter(rows))
    assert isinstance(many, list) and all(type(m) is bytes for m in many)
    assert many == [
        canonical.vote_sign_bytes(chain_id, msg_type, h, r, b, ts) for b, ts in rows
    ]
    in_int64 = all(I64_MIN <= ts <= I64_MAX for _, ts in rows)
    engaged = which == "native" and len(rows) >= MIN and in_int64
    assert native_calls == ([len(rows)] if engaged else [])


def _commit(n):
    from tendermint_tpu.types.basic import BlockIDFlag
    from tendermint_tpu.types.block import Commit, CommitSig

    flags = [BlockIDFlag.COMMIT, BlockIDFlag.NIL, BlockIDFlag.ABSENT, BlockIDFlag.COMMIT]
    sigs = []
    for i in range(n):
        if flags[i % 4] == BlockIDFlag.ABSENT:
            sigs.append(CommitSig.absent_sig())
        else:
            sigs.append(CommitSig(flags[i % 4], bytes([i % 251 + 1]) * 20, _ts(i), bytes([i % 256]) * 64))
    return Commit(9, 1, BID, tuple(sigs))


@pytest.mark.parametrize("builder", ["native", "python"], indirect=True)
@pytest.mark.parametrize("n", [8, 4 * MIN])
def test_commit_columns_match_per_row(n, builder):
    """Absent and nil rows among the commit's, idxs with gaps and out of order."""
    which, native_calls = builder
    commit = _commit(n)
    idxs = [i for i in range(n) if i % 5 != 3][::-1]
    many, built = commit.vote_sign_bytes_built("test_chain_id", idxs)
    assert many == [commit.vote_sign_bytes("test_chain_id", i) for i in idxs]
    assert many == commit.vote_sign_bytes_many("test_chain_id", idxs)
    engaged = which == "native" and len(idxs) >= MIN
    assert built == ("native" if engaged else "python")
    assert native_calls == ([len(idxs)] * 2 if engaged else [])


def test_env_switch_takes_the_python_builder():
    """TMTPU_NATIVE=0 (read once, at the library's first use): same bytes."""
    code = (
        "from tendermint_tpu import native\n"
        "from tendermint_tpu.types.basic import BlockID, BlockIDFlag, PartSetHeader\n"
        "from tendermint_tpu.types.block import Commit, CommitSig\n"
        "bid = BlockID(b'\\x01' * 32, PartSetHeader(3, b'\\x02' * 32))\n"
        "sigs = [CommitSig(BlockIDFlag.NIL if i % 3 else BlockIDFlag.COMMIT, b'a' * 20,\n"
        "                  17 * 10**17 + 999_999_937 * i, b's' * 64) for i in range(2000)]\n"
        "c = Commit(9, 1, bid, tuple(sigs))\n"
        "msgs, built = c.vote_sign_bytes_built('test_chain_id', range(2000))\n"
        "assert msgs == [c.vote_sign_bytes('test_chain_id', i) for i in range(2000)]\n"
        "print(native.available(), built, len(msgs))\n"
    )
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    p = subprocess.run(
        [sys.executable, "-c", code], cwd=root, capture_output=True, text=True,
        timeout=120, env=dict(os.environ, TMTPU_NATIVE="0", JAX_PLATFORMS="cpu"),
    )
    assert p.returncode == 0, p.stderr[-2000:]
    assert p.stdout.split() == ["False", "python", "2000"]
