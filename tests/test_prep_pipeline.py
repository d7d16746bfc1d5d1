"""ISSUE 18 — prep-pipeline tests: the staged single-flush submit (hashing
on the prep pool, A-block upload early, sort hoisted), the in-budget
chunk-bucket flush over the stream floor (path `rlc-pipelined`: ONE chunk
since ISSUE 30), and the striped host-RLC path.

The invariants pinned here:
  - byte identity: staged == CPU verdicts, bit for bit, across geometries
    and with precheck-rejected rows at stage boundaries (the unstaged
    native arm went with its switch, ISSUE 31);
  - a prep-pool hashing failure latches in the future and fails the flush
    LOUDLY (and the pool is still usable afterwards);
  - hot-path hash budget: a clean flush challenge-hashes every row AT MOST
    once (batch.HASH_ROWS_HASHED);
  - the pipelined path takes every flush the planner's chunk holds as
    ONE chunk on the planner's bucket, labels itself "rlc-pipelined", and
    records chunks / chunk_lanes / padding_lanes for the benchmark;
  - the striped host-RLC path returns verdicts identical to the unstriped
    path, including exact recovery around a tampered row.

Device kernels are replaced with ed25519_ref host twins (identical math,
real curve points) — see tests/test_flush_planner.py.
"""

import numpy as np
import pytest

from tendermint_tpu import native
from tendermint_tpu.crypto import batch
from tendermint_tpu.crypto.keys import gen_ed25519
from test_flush_planner import _install_host_twins, _signed_rows

needs_native = pytest.mark.skipif(
    not native.available(), reason="native helper module unavailable"
)


@pytest.fixture
def prep_cfg():
    """Snapshot/restore the process-global prep-pipeline config."""
    prev = dict(batch._PREP_CFG)
    yield batch._PREP_CFG
    batch._PREP_CFG.clear()
    batch._PREP_CFG.update(prev)


@pytest.fixture
def small_rlc(monkeypatch, prep_cfg):
    """RLC_MIN=8 + a 64-lane planner bucket (31 rows/chunk), restored after."""
    monkeypatch.setattr(batch, "RLC_MIN", 8)
    prev = batch.planner_budget()
    batch.configure_planner(max_flush_lanes=64)
    yield 31
    batch.configure_planner(max_flush_lanes=prev)
    batch.set_device_fault_hook(None)


SINGLE_FLUSH = 1 << 30  # a stream floor no flush reaches: the per-size `rlc` program


def _rows_with_rejects(n, seed=b"\x21"):
    """n signed rows with stage-boundary rejects mixed in: a non-canonical
    s (>= L, rejected at precheck BEFORE hashing), an invalid pubkey
    encoding (rejected at the A-cache fill boundary), and a tampered
    message (valid encodings; only the combined check can catch it)."""
    pks, msgs, sigs = _signed_rows(n, seed)
    pks, msgs, sigs = list(pks), list(msgs), list(sigs)
    expect = np.ones(n, dtype=bool)
    # row 1: s >= L — precheck reject, stage-1 boundary
    sigs[1] = sigs[1][:32] + b"\xff" * 32
    expect[1] = False
    # row 3: y >= p — invalid point encoding, A-fill boundary
    pks[3] = b"\xff" * 32
    expect[3] = False
    # row n-2: bitflipped message — combined-check failure, recovery path
    msgs[n - 2] = msgs[n - 2][:-1] + bytes([msgs[n - 2][-1] ^ 1])
    expect[n - 2] = False
    return pks, msgs, sigs, expect


# ---------------------------------------------------------------------------
# staged single-flush submit


@needs_native
@pytest.mark.parametrize("n", [9, 16, 31], ids=["tiny", "pow2", "bucket-edge"])
def test_staged_vs_serial_vs_cpu_byte_identical(small_rlc, monkeypatch,
                                                prep_cfg, n):
    """Staged submit == CPU host path, bit for bit."""
    _install_host_twins(monkeypatch)
    pks, msgs, sigs = _signed_rows(n, b"\x22")
    cpu = batch.verify_batch_cpu(pks, msgs, sigs)

    prep_cfg["stream_floor"] = SINGLE_FLUSH  # isolate the staged single flush
    staged = batch.verify_batch(pks, msgs, sigs, backend="jax")
    assert batch.LAST_JAX_PATH[0] == "rlc"
    assert "precheck_s" in batch.LAST_FLUSH_DETAIL["prep_stages"]  # the staged arm's

    assert staged.tobytes() == cpu.tobytes()
    assert staged.all()


@needs_native
def test_staged_precheck_rejected_rows_at_stage_boundaries(small_rlc,
                                                           monkeypatch,
                                                           prep_cfg):
    """Rows rejected at each stage boundary (pre-hash precheck, A-fill
    exclusion, combined-check recovery) produce verdicts identical to the
    CPU referee."""
    _install_host_twins(monkeypatch)
    pks, msgs, sigs, expect = _rows_with_rejects(20)
    cpu = batch.verify_batch_cpu(pks, msgs, sigs)

    prep_cfg["stream_floor"] = SINGLE_FLUSH
    staged = batch.verify_batch(pks, msgs, sigs, backend="jax")

    assert staged.tobytes() == cpu.tobytes()
    assert staged.tobytes() == expect.tobytes()


@needs_native
def test_prep_pool_exception_fails_flush_loudly(small_rlc, monkeypatch,
                                                prep_cfg):
    """A hashing failure on the prep pool latches in the future, re-raises
    at .result() on the dispatch thread, and leaves the pool usable."""
    _install_host_twins(monkeypatch)
    prep_cfg["stream_floor"] = SINGLE_FLUSH
    pks, msgs, sigs = _signed_rows(12, b"\x23")

    real = native.ed25519_h_batch

    def boom(*a, **kw):
        raise RuntimeError("injected prep-pool hash failure")

    monkeypatch.setattr(native, "ed25519_h_batch", boom)
    with pytest.raises(RuntimeError, match="injected prep-pool hash"):
        batch._rlc_submit(pks, msgs, sigs)

    # the pool is not wedged: the very next staged flush succeeds
    monkeypatch.setattr(native, "ed25519_h_batch", real)
    mask = batch.verify_batch(pks, msgs, sigs, backend="jax")
    assert mask.all()


@needs_native
def test_hash_budget_at_most_once_per_row(small_rlc, monkeypatch, prep_cfg):
    """Hot-path guard: a clean flush challenge-hashes each row EXACTLY once
    — on the staged single flush and on the chunk-bucket flush."""
    _install_host_twins(monkeypatch)
    pks, msgs, sigs = _signed_rows(24, b"\x24")

    prep_cfg["stream_floor"] = SINGLE_FLUSH
    batch.HASH_ROWS_HASHED[0] = 0
    assert batch.verify_batch(pks, msgs, sigs, backend="jax").all()
    assert batch.HASH_ROWS_HASHED[0] == 24

    prep_cfg["stream_floor"] = 16
    batch.HASH_ROWS_HASHED[0] = 0
    assert batch.verify_batch(pks, msgs, sigs, backend="jax").all()
    assert batch.LAST_JAX_PATH[0] == "rlc-pipelined"
    assert batch.HASH_ROWS_HASHED[0] == 24


# ---------------------------------------------------------------------------
# the in-budget chunk-bucket flush (path rlc-pipelined)


def _count_submits(monkeypatch):
    """Call counts of the three device submits of the streamed check."""
    from tendermint_tpu.ops import msm_jax

    counts = {}
    for name in ("rlc_partial_submit", "partial_fold_submit", "partial_identity_submit"):
        def wrapper(*a, _name=name, _real=getattr(msm_jax, name), **k):
            counts[_name] = counts.get(_name, 0) + 1
            return _real(*a, **k)

        monkeypatch.setattr(msm_jax, name, wrapper)
    return counts


def test_pipelined_byte_identical_and_telemetry(small_rlc, monkeypatch,
                                                prep_cfg):
    """Above the stream floor (and inside the planner budget) a single
    flush rides ONE chunk on the planner's bucket — one partial submit, no
    fold, one identity check — labels itself rlc-pipelined, and records
    chunks/prep_overlap_s/prep_stages; verdicts byte-identical to the
    unstriped serial flush and the CPU path."""
    _install_host_twins(monkeypatch)
    counts = _count_submits(monkeypatch)
    pks, msgs, sigs = _signed_rows(24, b"\x25")
    cpu = batch.verify_batch_cpu(pks, msgs, sigs)

    prep_cfg["stream_floor"] = 16
    piped = batch.verify_batch(pks, msgs, sigs, backend="jax")
    assert batch.LAST_JAX_PATH[0] == "rlc-pipelined"
    det = dict(batch.LAST_FLUSH_DETAIL)
    assert counts == {"rlc_partial_submit": 1, "partial_identity_submit": 1}

    prep_cfg["stream_floor"] = SINGLE_FLUSH
    single = batch.verify_batch(pks, msgs, sigs, backend="jax")
    assert batch.LAST_JAX_PATH[0] == "rlc"

    assert piped.tobytes() == single.tobytes() == cpu.tobytes()
    assert piped.all()
    assert det.get("chunks") == 1
    assert not det.get("prep_overlap_s")  # one chunk hides behind nothing
    assert isinstance(det.get("prep_stages"), dict)


def test_pipelined_geometry_guard_declines(small_rlc, monkeypatch, prep_cfg):
    """The guard is the planner's chunk alone: _verify_batch_pipelined
    takes planner_chunk_rows() rows as one chunk and declines (returns
    None) one row over it instead of compiling a new shape."""
    _install_host_twins(monkeypatch)
    counts = _count_submits(monkeypatch)
    assert batch.planner_chunk_rows() == small_rlc == 31
    pks, msgs, sigs = _signed_rows(32, b"\x26")
    mask = batch._verify_batch_pipelined(pks[:31], msgs[:31], sigs[:31])
    assert mask is not None and mask.all() and len(mask) == 31
    assert batch.LAST_FLUSH_DETAIL["chunks"] == 1
    assert counts.pop("rlc_partial_submit") == 1
    assert batch._verify_batch_pipelined(pks, msgs, sigs) is None
    assert "rlc_partial_submit" not in counts  # declined before any submit


def test_pipelined_flush_record_arithmetic(small_rlc, monkeypatch, prep_cfg):
    """What `planner.padding_pct` reads from the flush record: a flush
    over the floor is one chunk of the budget's lanes, padded by the
    budget less two lanes a row and the one base-point lane."""
    _install_host_twins(monkeypatch)
    prep_cfg["stream_floor"] = 16
    n, budget = 24, batch.planner_budget()
    pks, msgs, sigs = _signed_rows(n, b"\x2d")
    assert batch.verify_batch(pks, msgs, sigs, backend="jax").all()
    assert batch.LAST_JAX_PATH[0] == "rlc-pipelined"
    det = dict(batch.LAST_FLUSH_DETAIL)
    assert det["chunks"] == 1
    assert det["chunk_lanes"] == det["peak_lanes_in_flight"] == budget == 64
    assert det["padding_lanes"] == budget - (2 * n + 1)
    assert not det.get("prep_overlap_s")


def test_pipelined_takes_every_row_count_the_chunk_holds(small_rlc, monkeypatch,
                                                         prep_cfg):
    """Routing by rows alone: under the floor the per-size `rlc` program,
    from the floor to planner_chunk_rows() the chunk-bucket flush as ONE
    chunk (no size is declined), one row more the streamed path."""
    _install_host_twins(monkeypatch)
    prep_cfg["stream_floor"] = 12
    top = batch.planner_chunk_rows()
    assert top == small_rlc
    pks, msgs, sigs = _signed_rows(top + 1, b"\x2e")

    def route(n):
        assert batch.verify_batch_jax(pks[:n], msgs[:n], sigs[:n]).all()
        return batch.LAST_JAX_PATH[0], batch.LAST_FLUSH_DETAIL.get("chunks")

    assert route(11)[0] == "rlc"
    for n in range(12, top + 1):
        assert route(n) == ("rlc-pipelined", 1), n
    assert route(top + 1) == ("rlc-streamed", 2)


def test_pipelined_bad_row_exact_recovery(small_rlc, monkeypatch, prep_cfg):
    """A tampered row in a pipelined flush still resolves to the exact
    per-row mask (combined check fails -> per-signature ladder)."""
    _install_host_twins(monkeypatch)
    pks, msgs, sigs, expect = _rows_with_rejects(24, b"\x27")
    prep_cfg["stream_floor"] = 16
    mask = batch.verify_batch(pks, msgs, sigs, backend="jax")
    assert mask.tobytes() == expect.tobytes()
    cpu = batch.verify_batch_cpu(pks, msgs, sigs)
    assert mask.tobytes() == cpu.tobytes()


# ---------------------------------------------------------------------------
# striped host RLC


def _tiled_rows(n, base, seed=b"\x28"):
    pks, msgs, sigs = _signed_rows(base, seed)
    reps = -(-n // base)
    return (
        (list(pks) * reps)[:n],
        (list(msgs) * reps)[:n],
        (list(sigs) * reps)[:n],
    )


def test_striped_host_rlc_parity_and_overlap(prep_cfg):
    """The striped host-RLC path (striping on, n >= floor) returns verdicts
    identical to the unstriped host path, and records the pipelined
    overlap telemetry (prep_overlap_s, prep_stages, chunks)."""
    n = 2100  # 1024-row stripe floor -> 3 stripes
    pks, msgs, sigs = _tiled_rows(n, 128)

    prep_cfg["stream_floor"] = 512
    prep_cfg["host_stripe"] = True  # force: "auto" is off on 1-core hosts
    striped = batch.verify_batch_cpu(pks, msgs, sigs)
    det = dict(batch.LAST_FLUSH_DETAIL)

    prep_cfg["host_stripe"] = False
    serial = batch.verify_batch_cpu(pks, msgs, sigs)

    assert striped.tobytes() == serial.tobytes()
    assert striped.all()
    assert det.get("chunks") == 3
    assert det.get("prep_overlap_s") is not None
    assert isinstance(det.get("prep_stages"), dict)
    assert det.get("prep_s") is not None


def test_striped_host_rlc_bad_row_exact(prep_cfg):
    """A tampered row inside one stripe recovers the exact serial mask."""
    n = 1100  # 2 stripes (1024 + 76)
    pks, msgs, sigs = _tiled_rows(n, 64, b"\x29")
    msgs[1050] = msgs[1050][:-1] + bytes([msgs[1050][-1] ^ 1])

    prep_cfg["stream_floor"] = 512
    prep_cfg["host_stripe"] = True
    striped = batch.verify_batch_cpu(pks, msgs, sigs)
    prep_cfg["host_stripe"] = False
    serial = batch.verify_batch_cpu(pks, msgs, sigs)

    assert striped.tobytes() == serial.tobytes()
    assert not striped[1050]
    assert striped.sum() == n - 1


# ---------------------------------------------------------------------------
# native prep pool config


@needs_native
def test_prep_pool_configure_roundtrip():
    """configure_prep(prep_threads=...) resizes the native worker pool;
    0/None restores the host default min(cores, 8)."""
    import os

    default = min(8, os.cpu_count() or 1)
    try:
        batch.configure_prep(prep_threads=2)
        assert native.prep_pool_size() == 2
        batch.configure_prep(prep_threads=3)
        assert native.prep_pool_size() == 3
    finally:
        batch.configure_prep(prep_threads=0)
    assert native.prep_pool_size() == default


def test_config_plumbing_defaults():
    """CryptoConfig carries the ISSUE 18 knobs with production defaults."""
    from tendermint_tpu.config.config import CryptoConfig

    c = CryptoConfig()
    assert c.prep_threads == 0
    assert c.prep_stream_floor == 2048
    assert c.prep_host_stripe == "auto"
    assert c.verified_memo_rows == 65536


def test_an_old_config_file_with_the_removed_keys_still_loads(tmp_path):
    """`prep_staged` and `prep_stream` went (ISSUE 31): an operator's file
    that still sets them loads, the two are ignored, its other keys hold."""
    import json

    from tendermint_tpu.config.config import Config

    path = tmp_path / "config.json"
    path.write_text(json.dumps({"crypto": {
        "prep_staged": False, "prep_stream": False, "prep_stream_floor": 4096}}))
    cfg = Config.load(str(path))
    assert cfg.crypto.prep_stream_floor == 4096
    assert not hasattr(cfg.crypto, "prep_staged") and not hasattr(cfg.crypto, "prep_stream")
