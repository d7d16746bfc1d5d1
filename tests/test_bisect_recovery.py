"""Bisection recovery (ISSUE 20) — log-cost exact-mask recovery after a
failed combined check.

When the RLC combined check fails, the old recovery was one monolithic
per-signature flush over ALL n rows; the bisection ladder
(crypto/batch.py _bisect_recover / _bisect_recover_host) instead splits
the failed range at the largest power of two below its size, re-checks
halves with combined sub-checks over the SAME warm pow2 lane buckets,
and runs the per-sig kernel only at small leaves. These tests pin the
CONTRACT with the device kernels replaced by ed25519_ref host twins
(tests/test_flush_planner.py pattern — tier-1 pays no XLA compile):

- one bad row over C = ceil(n/leaf) chunks recovers in at most
  ceil(log2 C)+2 device flushes (a check a level, one union of the second
  halves put off, the leaf), counted TWO ways: the recovery
  ledger (LAST_FLUSH_DETAIL / trace counters) and an independent
  kernel-submission witness wrapped around the host twins;
- the recovered mask is byte-identical across every arm: single-chip
  bisect, streamed planner recovery, sharded-streamed recovery (fake
  mesh), host-RLC bisect, and the naive TMTPU_BISECT=0 fallback;
- the host arm (_bisect_recover_host) keeps the same log-cost bound;
- a dense flood trips the adaptive bail (batch._BISECT_MAX_BAD) and the
  mask stays exact;
- TMTPU_BISECT=0 restores the straight-to-per-sig arm (one recovery
  flush, identical mask);
- both arms walk ONE ladder (_ladder_walk): its `recovery.*` spans and the
  flush record's recovery_* counts are the split rule's, written out here
  independently, and its leaf sizes are the ones `prewarm` loads;
- the second halves the descent put off are checked as ONE union, once a
  ladder (a failed union costs one flush over the one-at-a-time rule), on
  the per-size program only on a lane bucket the walk ran; at 10,000 rows
  no place of one bad row takes more than 6 checks.
"""

import math
import os

import numpy as np
import pytest

os.environ.setdefault("TMTPU_CRYPTO_BACKEND", "cpu")

from tendermint_tpu.crypto import batch
from tendermint_tpu.libs import trace as _trace
from tests.test_flush_planner import (
    _fake_mesh_env,
    _install_host_twins,
    _signed_rows,
)


@pytest.fixture
def bisect_env(monkeypatch):
    """Small-geometry bisection: RLC floor 8, leaf 8, planner out of the
    way, verified-row memo off (a memo hit would skip the flush whose
    count this file pins)."""
    monkeypatch.setattr(batch, "RLC_MIN", 8)
    monkeypatch.setattr(batch, "_BISECT_LEAF", 8)
    prev = batch.planner_budget()
    batch.configure_planner(max_flush_lanes=1 << 16)
    batch.configure_verified_memo(0)
    yield
    batch.configure_planner(max_flush_lanes=prev)
    batch.configure_verified_memo(batch._memo_env_rows())


class _FlushWitness:
    """Independent device-flush counter: wraps the host-twin kernel entry
    points AFTER _install_host_twins, so the recovery ledger is checked
    against actual kernel submissions, not its own bookkeeping."""

    def __init__(self, monkeypatch):
        from tendermint_tpu.ops import ed25519_jax, msm_jax

        _install_host_twins(monkeypatch)
        self.combined = 0
        self.persig = 0
        real_full = msm_jax.rlc_check_submit
        real_persig = ed25519_jax.verify_prepared

        def counting_full(*a, **k):
            self.combined += 1
            return real_full(*a, **k)

        def counting_persig(*a, **k):
            self.persig += 1
            return real_persig(*a, **k)

        monkeypatch.setattr(msm_jax, "rlc_check_submit", counting_full)
        monkeypatch.setattr(ed25519_jax, "verify_prepared", counting_persig)


def _flip(sigs, i):
    """Valid encodings, wrong signature: only the curve check fails —
    precheck passes, so the row survives to the combined check (the
    poisoning shape; docs/ROBUSTNESS.md)."""
    sigs[i] = sigs[i][:32] + (1).to_bytes(32, "little")


# ---------------------------------------------------------------------------
# The flush bound.


@pytest.mark.parametrize("bad_row", [0, 27, 63], ids=["head", "mid", "tail"])
def test_one_bad_row_flush_bound_and_exact_mask(
    bisect_env, monkeypatch, bad_row
):
    """One poisoned row in 64 (8 chunks of leaf=8) recovers in at most
    ceil(log2 8)+2 = 5 device flushes — pinned by the recovery ledger,
    the trace counters AND the independent submission witness — and the
    mask is byte-identical to the CPU reference."""
    witness = _FlushWitness(monkeypatch)
    pks, msgs, sigs = _signed_rows(64)
    sigs = list(sigs)
    _flip(sigs, bad_row)

    counters0 = _trace.verify_stats()["counters"]["recovery_flushes"]
    mask = batch.verify_batch(pks, msgs, sigs, backend="jax")
    recovery = batch.LAST_FLUSH_DETAIL.get("recovery_flushes", 0)

    assert not mask[bad_row] and mask.sum() == 63
    cpu = batch.verify_batch_cpu(pks, msgs, sigs)
    assert mask.tobytes() == cpu.tobytes()

    chunks = math.ceil(64 / 8)
    bound = math.ceil(math.log2(chunks)) + 2
    assert 1 <= recovery <= bound
    assert batch.LAST_JAX_PATH[0] == "rlc-bisect"
    # the witness: total kernel submissions = 1 initial (failed) combined
    # check + the recovery flushes the ledger claims
    assert witness.combined - 1 + witness.persig == recovery
    # and the cumulative trace counter grew by exactly that many
    assert (
        _trace.verify_stats()["counters"]["recovery_flushes"] - counters0
        == recovery
    )
    assert _trace.verify_stats()["last_flush"]["recovery_flushes"] == recovery


def test_two_bad_rows_cost_at_most_two_descents(bisect_env, monkeypatch):
    """k bad rows cost at most k independent descents: 2 poisoned rows in
    separate halves stay within 2 * (2*ceil(log2 C)+1) flushes."""
    witness = _FlushWitness(monkeypatch)
    pks, msgs, sigs = _signed_rows(64)
    sigs = list(sigs)
    _flip(sigs, 5)
    _flip(sigs, 60)

    mask = batch.verify_batch(pks, msgs, sigs, backend="jax")
    recovery = batch.LAST_FLUSH_DETAIL.get("recovery_flushes", 0)

    assert not mask[5] and not mask[60] and mask.sum() == 62
    bound = 2 * math.ceil(math.log2(math.ceil(64 / 8))) + 1
    assert recovery <= 2 * bound
    assert witness.combined - 1 + witness.persig == recovery


def test_dense_flood_trips_adaptive_bail_mask_exact(bisect_env, monkeypatch):
    """A dense flood (half the rows poisoned) trips _BISECT_MAX_BAD:
    remaining ranges skip their combined checks and go straight per-sig,
    so bisection never costs more than the naive arm by a growing factor
    — and the mask stays exact."""
    monkeypatch.setattr(batch, "_BISECT_MAX_BAD", 2)
    _FlushWitness(monkeypatch)
    pks, msgs, sigs = _signed_rows(64)
    sigs = list(sigs)
    bad = set(range(0, 64, 2))
    for i in bad:
        _flip(sigs, i)

    mask = batch.verify_batch(pks, msgs, sigs, backend="jax")
    assert all(bool(mask[i]) != (i in bad) for i in range(64))
    cpu = batch.verify_batch_cpu(pks, msgs, sigs)
    assert mask.tobytes() == cpu.tobytes()


def test_bisect_disabled_restores_naive_arm(bisect_env, monkeypatch):
    """TMTPU_BISECT=0: straight-to-per-sig recovery — ONE whole-batch
    recovery flush, identical mask (the bench baseline arm)."""
    monkeypatch.setenv("TMTPU_BISECT", "0")
    witness = _FlushWitness(monkeypatch)
    pks, msgs, sigs = _signed_rows(64)
    sigs = list(sigs)
    _flip(sigs, 13)

    mask = batch.verify_batch(pks, msgs, sigs, backend="jax")

    assert not mask[13] and mask.sum() == 63
    assert batch.LAST_FLUSH_DETAIL.get("recovery_flushes") == 1
    assert witness.persig == 1  # one monolithic per-sig flush, all 64 rows
    assert batch.LAST_JAX_PATH[0] == "persig"


# ---------------------------------------------------------------------------
# After a pipelined flush the ladder rides the planner's one chunk bucket.


@pytest.mark.parametrize(
    "bad_rows", [(41,), (0, 99), (30, 31, 32, 33, 34, 35)],
    ids=["one", "head-and-tail", "six-in-a-row"],
)
def test_ladder_after_a_pipelined_flush_rides_the_chunk_bucket(
    bisect_env, monkeypatch, bad_rows
):
    """A flush over the stream floor compiled ONE shape, the chunk bucket
    (rlc_partial): every combined check of its recovery ladder runs as one
    chunk on that bucket, none on a whole-flush program (rlc_check_submit,
    a program per pow2 sub-range size: what a catch-up run of 10,624 rows
    paid minutes for, PR 29). The mask is the CPU reference's, the flush
    count the ledger's."""
    from tendermint_tpu.ops import msm_jax

    witness = _FlushWitness(monkeypatch)
    partial_lanes = []
    real_partial = msm_jax.rlc_partial_submit

    def counting_partial(pts, *a, **k):
        partial_lanes.append(pts.shape[0])
        return real_partial(pts, *a, **k)

    monkeypatch.setattr(msm_jax, "rlc_partial_submit", counting_partial)
    prev_cfg = dict(batch._PREP_CFG)
    batch.configure_planner(max_flush_lanes=256)  # 127 rows a chunk
    batch._PREP_CFG.update(stream_floor=16)
    try:
        pks, msgs, sigs = _signed_rows(100)
        sigs = list(sigs)
        for i in bad_rows:
            _flip(sigs, i)
        mask = batch.verify_batch(pks, msgs, sigs, backend="jax")
        recovery = batch.LAST_FLUSH_DETAIL.get("recovery_flushes", 0)
    finally:
        batch._PREP_CFG.clear()
        batch._PREP_CFG.update(prev_cfg)

    assert mask.tobytes() == batch.verify_batch_cpu(pks, msgs, sigs).tobytes()
    assert [i for i in range(100) if not mask[i]] == list(bad_rows)
    assert batch.LAST_JAX_PATH[0] == "rlc-bisect"
    assert witness.combined == 0  # no whole-flush program at any sub-range size
    assert set(partial_lanes) == {256}  # one shape, the failed flush's own
    # the failed flush's one chunk, then one chunk a combined check
    assert len(partial_lanes) - 1 + witness.persig == recovery
    assert witness.persig >= 1


# ---------------------------------------------------------------------------
# Host arm (_bisect_recover_host): same ladder on the striped host RLC.


def test_host_bisect_flush_bound_and_exact_mask(bisect_env, monkeypatch):
    """The CPU fallback's bisection keeps the log-cost shape: one bad row
    in 64 (host leaf 2, host-RLC floor lowered to 8 so the ladder actually
    splits) recovers with at most 2*ceil(log2 C)+1 host-RLC sub-checks."""
    monkeypatch.setattr(batch, "_HOST_RLC_MIN", 8)
    combined = [0]
    serial_rows = [0]
    real_rlc = batch._verify_batch_cpu_rlc
    real_serial = batch._verify_serial_host

    def counting_rlc(pks, msgs, sigs):
        combined[0] += 1
        return real_rlc(pks, msgs, sigs)

    def counting_serial(pks, msgs, sigs):
        serial_rows[0] += len(pks)
        return real_serial(pks, msgs, sigs)

    monkeypatch.setattr(batch, "_verify_batch_cpu_rlc", counting_rlc)
    monkeypatch.setattr(batch, "_verify_serial_host", counting_serial)

    pks, msgs, sigs = _signed_rows(64)
    sigs = list(sigs)
    _flip(sigs, 41)
    detail0 = batch.LAST_FLUSH_DETAIL.get("recovery_flushes", 0)

    mask = batch.verify_batch_cpu(pks, msgs, sigs)

    assert not mask[41] and mask.sum() == 63
    # host leaf = max(8 // 4, 1) = 2, but the _HOST_RLC_MIN guard stops
    # splitting at ranges under 16 rows: C = ceil(64 / 8) = 8 chunks
    bound = 2 * math.ceil(math.log2(8)) + 1
    recovery = batch.LAST_FLUSH_DETAIL.get("recovery_flushes", 0) - detail0
    assert 1 <= recovery <= bound
    # the serial loop ran on a small leaf, never the whole batch
    assert serial_rows[0] < 64
    # combined sub-checks: 1 initial (failed) + the ladder's re-checks
    assert combined[0] - 1 + (1 if serial_rows[0] else 0) <= bound + 1


def test_host_naive_arm_counts_its_recovery_flush(bisect_env, monkeypatch):
    """TMTPU_BISECT=0 on the host arm: the whole-batch serial pass is
    counted as one recovery flush (the ledger covers both arms)."""
    monkeypatch.setattr(batch, "_HOST_RLC_MIN", 8)
    monkeypatch.setenv("TMTPU_BISECT", "0")
    pks, msgs, sigs = _signed_rows(64)
    sigs = list(sigs)
    _flip(sigs, 7)
    detail0 = batch.LAST_FLUSH_DETAIL.get("recovery_flushes", 0)

    mask = batch.verify_batch_cpu(pks, msgs, sigs)

    assert not mask[7] and mask.sum() == 63
    assert batch.LAST_FLUSH_DETAIL.get("recovery_flushes", 0) - detail0 == 1


# ---------------------------------------------------------------------------
# One walk for both arms: its spans and the flush record's counts.


# The per-size program's lane buckets, as crypto/batch.py has them: a check of
# m rows runs the smallest bucket of at least m + 1 lanes (its B lane).
_PER_SIZE_LANES = [64, 256, 512, 1024, 1536, 2048, 3072, 4096, 5120, 6144, 8192,
                   10240, 12288, 16384, 20480, 24576, 32768]


def _per_size_lanes(rows):
    return next(b for b in _PER_SIZE_LANES if rows + 1 <= b)


def _split_rule_walk(n, bad, leaf, floor, lanes=None, unions=True):
    """The ladder's rule, written out. A range splits at the largest power of
    two under its size unless it has at most `leaf` rows or fewer than
    2 x `floor`. The first half is checked: a passing one leaves the second
    known bad, which descends unchecked; a failing one descends, and the
    second half is put off where it has `floor` rows or more, else it
    descends unchecked. Once the descent is done, two or more halves put off
    are checked as ONE union, once a walk, where `lanes` (rows -> the
    program's bucket; None where every check runs one shape) gives a bucket
    the walk has run: the failed whole flush's or a check's. A passing union
    clears them all; else each is checked alone and a failing one descends.
    After the union every second half is checked at once, as it is
    everywhere with `unions=False`, the one-at-a-time rule of earlier builds.
    `bad`: a row or a set of rows (few enough that no walk reaches the
    dense-flood bail, which is not written out here)."""
    bad = {bad} if isinstance(bad, int) else set(bad)
    got = {"checks": 0, "leaves": 0, "rows_checked": 0, "leaf_rows": 0, "unions": 0,
           "unions_ok": 0, "check_spans": [], "leaf_sizes": []}
    ran = {lanes(n)} if lanes else set()
    put_off = []

    def check(ranges):
        rows = sum(hi - lo for lo, hi in ranges)
        ok = not any(lo <= b < hi for lo, hi in ranges for b in bad)
        got["checks"] += 1
        got["rows_checked"] += rows
        got["check_spans"].append((rows, len(ranges), ok))
        if lanes:
            ran.add(lanes(rows))
        return ok

    def go(lo, hi):
        m = hi - lo
        if m <= leaf or m < 2 * floor:
            got["leaves"] += 1
            got["leaf_rows"] += m
            got["leaf_sizes"].append(m)
            return
        mid = lo + (1 << ((m - 1).bit_length() - 1))
        if check([(lo, mid)]):
            go(mid, hi)
            return
        go(lo, mid)
        if hi - mid < floor:
            go(mid, hi)
        elif unions and not got["unions"]:
            put_off.append((mid, hi))
        elif not check([(mid, hi)]):
            go(mid, hi)

    go(0, n)
    while put_off:
        ranges, put_off[:] = put_off[:], []
        rows = sum(hi - lo for lo, hi in ranges)
        if len(ranges) > 1 and not got["unions"] and (not lanes or lanes(rows) in ran):
            got["unions"] += 1
            if check(ranges):
                got["unions_ok"] += 1
                continue
        for lo, hi in ranges:
            if not check([(lo, hi)]):
                go(lo, hi)
    got["flushes"] = got["checks"] + got["leaves"]
    return got


def _recovery_events():
    events = _trace.tracer.dump()
    return [e for e in events if e["name"].startswith("recovery.")]


def _walk_on_arm(monkeypatch, arm, bad_rows):
    """Flip `bad_rows` of 100 and verify them on the device's arm (the
    per-size program, host twins) or the host's; check the ladder's spans
    and the flush record against the split rule's walk, which is returned,
    and the mask against the serial loop's."""
    monkeypatch.setattr(_trace.tracer, "enabled", True)
    pks, msgs, sigs = _signed_rows(100)
    sigs = list(sigs)
    for i in bad_rows:
        _flip(sigs, i)
    if arm == "device":
        _FlushWitness(monkeypatch)
        want = _split_rule_walk(100, bad_rows, leaf=8, floor=8, lanes=_per_size_lanes)
        run = lambda: batch.verify_batch(pks, msgs, sigs, backend="jax")  # noqa: E731
    else:
        monkeypatch.setattr(batch, "_HOST_RLC_MIN", 8)
        want = _split_rule_walk(100, bad_rows, leaf=2, floor=8)
        run = lambda: batch.verify_batch(pks, msgs, sigs, backend="cpu")  # noqa: E731
    counters0 = _trace.verify_stats()["counters"]["recovery_flushes"]
    _trace.tracer.clear()

    mask = run()

    assert mask.tobytes() == batch._verify_serial_host(pks, msgs, sigs).tobytes()
    assert [i for i in range(100) if not mask[i]] == sorted(bad_rows)
    events = _recovery_events()
    (ladder,) = [e for e in events if e["name"] == "recovery.ladder"]
    checks = [e for e in events if e["name"] == "recovery.check"]
    leaves = [e for e in events if e["name"] == "recovery.leaf"]
    a = ladder["attrs"]
    counts = ("checks", "leaves", "flushes", "rows_checked", "leaf_rows", "unions", "unions_ok")
    assert {k: a[k] for k in counts} == {k: want[k] for k in counts}
    assert (a["rows"], a["backend"]) == (100, "jax" if arm == "device" else "cpu")
    assert [(e["attrs"]["rows"], e["attrs"]["ranges"], e["attrs"]["ok"])
            for e in checks] == want["check_spans"]
    assert [e["attrs"]["rows"] for e in leaves] == want["leaf_sizes"]
    assert all(e["root"] == ladder["root"] and e["parent"] is not None for e in checks + leaves)
    last = _trace.verify_stats()["last_flush"]
    assert (last["recovery_checks"], last["recovery_unions"], last["recovery_leaves"],
            last["recovery_rows_checked"], last["recovery_flushes"]) == (
        a["checks"], a["unions"], a["leaves"], a["rows_checked"], a["flushes"])
    assert _trace.verify_stats()["counters"]["recovery_flushes"] - counters0 == a["flushes"]
    return want


@pytest.mark.parametrize("arm", ["device", "host"])
@pytest.mark.parametrize("bad_row", [0, 37, 70, 99], ids=["head", "mid", "third-quarter", "tail"])
def test_ladder_spans_and_record_follow_the_split_rule(bisect_env, monkeypatch, arm, bad_row):
    """One bad row in 100, on each arm: the `recovery.ladder` span's counts
    are the split rule's, with one `recovery.check` span a combined check
    (its rows, its `ranges`, `ok` false on the failing ones) and one
    `recovery.leaf` a leaf; the flush record's counts are the span's; the
    mask is the serial loop's. The halves put off pass as one union, where
    there are two or more; with the one bad row in a first half, none."""
    want = _walk_on_arm(monkeypatch, arm, [bad_row])
    assert want["unions"] == want["unions_ok"] == (bad_row != 99)
    assert want["flushes"] <= _split_rule_walk(100, bad_row, 8, 8, unions=False)["flushes"]


@pytest.mark.parametrize("arm", ["device", "host"])
@pytest.mark.parametrize("bad_rows", [(37, 90), (5, 60)], ids=["mid-and-tail", "head-and-middle"])
def test_a_bad_row_in_a_half_put_off_fails_the_union_once(bisect_env, monkeypatch, arm, bad_rows):
    """Two bad rows, the second in a half the descent put off: the union of
    the halves put off fails once, then each half is checked alone and the
    failing one descends as the one-at-a-time rule does, with no second
    union. The mask is exact and the ladder costs at most one flush more
    than the one-at-a-time rule."""
    want = _walk_on_arm(monkeypatch, arm, list(bad_rows))
    assert (want["unions"], want["unions_ok"]) == (1, 0)
    assert [ranges for _, ranges, _ in want["check_spans"]].count(1) == want["checks"] - 1
    leaf = 8 if arm == "device" else 2
    before = _split_rule_walk(100, bad_rows, leaf, 8, unions=False)
    assert before["flushes"] < want["flushes"] <= before["flushes"] + 1


def _fake_check(bad):
    """A combined check over (lo, hi) ranges of row indices: the exact mask,
    all True, where no bad row is among them, else None; one flush."""
    def combined(ranges):
        clean = not any(lo <= b < hi for lo, hi in ranges for b in bad)
        return (np.ones(sum(hi - lo for lo, hi in ranges), dtype=bool) if clean else None), 1
    return combined


def test_every_place_of_one_bad_row_in_10k_takes_at_most_six_checks(monkeypatch):
    """The walk at 10,000 rows with the device's thresholds (leaf 256, floor
    512) and a fake combined check, for the bad row at each of the 10,000
    places: 2, 4 or 6 checks, at 784, 1,024 and 8,192 places, never more
    (the one-at-a-time rule took 7 to 10 at 77% of them); the rows checked,
    the checks and the leaves are the split rule's at each place, and the
    mask is exact. At seat 0.5, row 5,000: checks of 8,192, 4,096, 2,048,
    1,024 and 512 rows, then one union of 4,880, over 20,752 rows in all."""
    monkeypatch.setattr(batch, "LAST_FLUSH_DETAIL", {})
    n = 10_000
    by_checks = {}
    for bad in range(n):
        seen = []

        def combined(ranges, check=_fake_check({bad})):
            seen.append(sum(hi - lo for lo, hi in ranges))
            return check(ranges)

        out, tally = batch._ladder_walk(
            n, combined, lambda lo, hi: np.arange(lo, hi) != bad, 256, 512, "jax")
        assert not out[bad] and out.sum() == n - 1
        want = _split_rule_walk(n, bad, 256, 512)
        assert [w[0] for w in want["check_spans"]] == seen
        assert (tally["checks"], tally["leaves"], tally["rows_checked"], tally["unions"]) == (
            want["checks"], want["leaves"], want["rows_checked"], want["unions"])
        by_checks[tally["checks"]] = by_checks.get(tally["checks"], 0) + 1
        if bad == 5_000:
            assert seen == [8192, 4096, 2048, 1024, 512, 4880] and sum(seen) == 20_752
            assert (tally["unions"], tally["unions_ok"], tally["flushes"]) == (1, 1, 7)
    assert by_checks == {2: 784, 4: 1_024, 6: 8_192}


def test_the_per_size_arm_takes_a_union_only_on_a_bucket_it_ran(bisect_env, monkeypatch):
    """On the per-size program (`_verify_batch_rlc`, a shape a lane bucket)
    the union of the halves put off runs only on a bucket the walk has
    already run: the failed whole flush's or an earlier check's. Where the
    union's bucket is new, each half is checked alone. Walked at 2,500 rows
    (leaf and floor 8) for one bad row at every 7th place, with fakes in
    the place of the program and the leaf that take row indices for rows;
    on the chunk bucket every place gets its union."""
    n = 2_500
    monkeypatch.setattr(batch, "_sharded_runner", lambda: None)
    refused = taken = 0
    for bad in range(0, n, 7):
        buckets = [_per_size_lanes(n)]  # the failed whole flush

        def rlc(pk, ms, sg, bad=bad, buckets=buckets):
            if pk != list(range(pk[0], pk[0] + len(pk))):  # a union of ranges
                assert _per_size_lanes(len(pk)) in buckets
            buckets.append(_per_size_lanes(len(pk)))
            return None if bad in pk else np.ones(len(pk), dtype=bool)

        monkeypatch.setattr(batch, "_verify_batch_rlc", rlc)
        monkeypatch.setattr(batch, "_persig_flush",
                            lambda pk, ms, sg, sharded, bad=bad: np.array(pk) != bad)
        rows = list(range(n))
        mask = batch._bisect_recover(rows, rows, rows, chunk_bucket=False)
        assert [i for i in range(n) if not mask[i]] == [bad]
        want = _split_rule_walk(n, bad, 8, 8, lanes=_per_size_lanes)
        assert len(buckets) - 1 == want["checks"]
        if want["unions"]:
            taken += 1
        elif _split_rule_walk(n, bad, 8, 8)["unions"]:
            refused += 1
    assert refused and taken


def test_rows_checked_counter_grows_by_the_record(bisect_env, monkeypatch):
    """`tendermint_batch_verify_recovery_rows_checked_total` grows by the
    record's `recovery_rows_checked`; a clean flush adds nothing."""
    from tendermint_tpu.libs import metrics as _metrics

    _FlushWitness(monkeypatch)
    counter = _metrics.batch_metrics().recovery_rows_checked
    value = lambda: counter._values.get((), 0.0)  # noqa: E731
    pks, msgs, sigs = _signed_rows(64)
    before = value()
    batch.verify_batch(pks, msgs, sigs, backend="jax")
    assert value() == before
    sigs = list(sigs)
    _flip(sigs, 27)
    batch.verify_batch(pks, msgs, sigs, backend="jax")
    rows = _trace.verify_stats()["last_flush"]["recovery_rows_checked"]
    assert rows == _split_rule_walk(64, 27, leaf=8, floor=8)["rows_checked"]
    assert value() - before == rows


@pytest.mark.parametrize("n, leaf, floor, sizes", [
    (10_000, 256, 512, {512, 784}),
    (4_096, 256, 512, {512}),
    (100, 8, 8, {4, 8}),
    (512, 256, 512, {512}),
])
def test_leaf_sizes_are_the_split_trees(n, leaf, floor, sizes):
    """The leaves the split rule reaches from n rows, whichever row is bad:
    the sizes `prewarm` loads the per-signature kernel at."""
    assert batch._ladder_leaf_sizes(n, leaf, floor) == sizes
    reached = set()
    for bad in range(0, n, max(1, n // 97)):
        reached.update(_split_rule_walk(n, bad, leaf, floor)["leaf_sizes"])
    assert reached == sizes


# ---------------------------------------------------------------------------
# Byte-identity across every recovery arm.


def test_mask_byte_identical_across_all_arms(bisect_env, monkeypatch):
    """The same poisoned 93-row set recovers the IDENTICAL mask through
    single-chip bisect, streamed planner recovery, sharded-streamed
    recovery, host-RLC bisect, and the naive fallback."""
    _FlushWitness(monkeypatch)
    pks, msgs, sigs = _signed_rows(93)
    sigs = list(sigs)
    for i in (0, 31, 62, 92):  # chunk boundaries of the 31-row planner
        _flip(sigs, i)

    reference = batch.verify_batch_cpu(pks, msgs, sigs)
    assert reference.sum() == 89

    arms = {}
    arms["bisect"] = batch.verify_batch(pks, msgs, sigs, backend="jax")
    assert batch.LAST_JAX_PATH[0] == "rlc-bisect"

    batch.configure_planner(max_flush_lanes=64)  # 31 rows per chunk
    arms["streamed"] = batch.verify_batch(pks, msgs, sigs, backend="jax")
    assert batch.LAST_JAX_PATH[0] == "rlc-streamed-recovery"

    env = _fake_mesh_env(4)
    monkeypatch.setattr(batch, "_sharded_env", lambda: env)
    arms["sharded-streamed"] = batch._verify_batch_streamed(pks, msgs, sigs)
    monkeypatch.setattr(batch, "_sharded_env", lambda: None)
    batch.configure_planner(max_flush_lanes=1 << 16)

    monkeypatch.setenv("TMTPU_BISECT", "0")
    arms["naive-persig"] = batch.verify_batch(pks, msgs, sigs, backend="jax")
    arms["naive-host"] = batch.verify_batch_cpu(pks, msgs, sigs)

    for name, mask in arms.items():
        assert mask.tobytes() == reference.tobytes(), name
