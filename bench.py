"""Benchmark harness: BASELINE.md configs, CPU-serial vs TPU.

Prints exactly ONE JSON line on stdout:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, "extra": {...}}

The headline metric is the LARGEST config that completed within the time
budget (TMTPU_BENCH_BUDGET_S, default 1500s) — ideally the north star
(BASELINE.md): wall latency to verify a 10k-validator commit on TPU, with
vs_baseline = serial-CPU-time / TPU-time (the reference's serial loop
semantics, types/validator_set.go:680-702). The metric name carries the
config, e.g. "verify_commit_10k_latency".

Two TPU paths are timed per config:
  - rlc:   the production fast path (crypto/batch.verify_batch): ONE
           random-linear-combination Pippenger multiscalar check
           (ops/msm_jax.py), with decompressed-pubkey caching. This is what
           consensus actually runs.
  - persig: the per-signature ladder kernel (ops/ed25519_jax.py) — the
           fallback path, also the exact-mask recovery path.

Sub-benchmarks (in "extra", budget permitting):
  batch128            — 128-sig batch verify (BASELINE config 1; per-sig path,
                        RLC is not engaged below RLC_MIN)
  verify_commit_1k    — VerifyCommit, 1k validators (config 2)
  light_trusting_4k   — VerifyCommitLightTrusting, 4k validators (config 3)
  verify_commit_10k   — the north-star config
  verify_commit_100k  — ONE 100k-validator commit through the streamed
                        flush planner (crypto/batch.py, ISSUE 13):
                        fixed-bucket chunks, double-buffered host prep,
                        on-device partial accumulation; reports chunk
                        telemetry (chunks/chunk_lanes/prep_overlap_ms/
                        peak_lanes_in_flight + the 2-chunk double-buffer
                        bound as lanes_in_flight_ok), slope_samples, and
                        speedup vs the extrapolated serial baseline
  super_batch         — multi-commit cross-height super-batch: H commits x
                        V validators as ONE streamed flush vs one flush
                        per commit; speedup = per-commit wall / streamed
                        wall, plus the same planner telemetry
  fastsync_replay     — blocks x validators batched replay (config 4)
  mixed_streaming     — ed25519+sr25519 mixed 10k set (config 5)
  streaming_{n}_sigs_per_sec — sustained sigs/s, pipelined RLC batches
  chaos_recovery      — the robustness scenario (docs/ROBUSTNESS.md): a
                        chaos-injected persistent device failure drives the
                        verify-path circuit breaker; reports
                        flushes_to_trip (should equal the threshold),
                        trip_latency_ms (first failure -> breaker OPEN),
                        open_flush_ms vs closed_flush_ms (the degraded
                        CPU flush cost; open flushes must not touch the
                        device — device_calls_while_open is asserted 0),
                        and rearm_ms (heal -> passing probe -> TPU again)
  overload            — the overload-protection scenario
                        (docs/ROBUSTNESS.md "Overload protection"): a live
                        node flooded with concurrent tx admissions;
                        reports tx-admission latency (p50/p90/p99 us),
                        eviction/TTL/rejection counts by reason, the
                        overload controller's pressure snapshot, and
                        block_interval_ratio (flooded vs unloaded — the
                        acceptance bound is <= 2x)
  light_serve         — light-client-as-a-service (docs/LIGHT.md): N
                        concurrent clients issue Zipfian-height
                        skipping-verification requests against a
                        LightService; reports sustained
                        client_verifs_per_sec, p50/p99 request latency,
                        device_flushes (coalesced cross-height windows),
                        cache/single-flight hit counts, and speedup =
                        serial per-request verification cost / coalesced
                        per-request cost
  tx_admission        — device-batched CheckTx admission
                        (docs/SCHEDULER.md): a live node + signed-tx flood
                        through the scheduler's admission lane vs the
                        app-side serial verify; reports admissions/s per
                        arm, speedup (serial vs batched), admission flush
                        sizes, and the vote-path flush-wall p99
                        baseline-vs-flood (votes preempt: must stay flat)
  multichip           — fused single-chip AND sharded multi-chip RLC over
                        one batch (ROADMAP item 1): slope-methodology raw
                        samples, per-shard mesh telemetry, sharded-vs-
                        single speedup; 8 VIRTUAL devices on CPU-only
                        hosts (marked virtual_devices)

Scenario isolation (round 7): every scenario runs in its OWN subprocess
with a per-stage watchdog inside and a hard process-group deadline outside.
A device-init stall or crash degrades THAT scenario to clearly-marked CPU
numbers (`extra.<scenario>.degraded = "cpu-fallback"` with
`degrade_reason`) instead of costing the whole run its datapoint — no more
whole-run `value: -1` for one sick scenario (BENCH_r05 lost round 5 that
way). Plan override: TMTPU_BENCH_SCENARIOS=comma,list; fault drill:
TMTPU_BENCH_FAULT="<scenario>[:raise|:hang]".

Slope methodology (round 7): RLC configs report `pipelined_slope_ms` with
the RAW `slope_samples` (k, seconds) pairs behind the fit — k chained
submits, one batched sync each — so a suspicious slope can be re-fit
post-hoc (PERF.md documents why single-sync timings lie on this runtime).
`slope_fused` marks whether the fused MSM pipeline (TMTPU_FUSED_MSM,
ops/pallas_msm.py) was active for the sampled flushes.

Flight-recorder breakdown (always in "extra", including the stall fallback):
  verify_stats  — per-stage pipeline telemetry from libs/trace.py:
                  "totals" (flushes/sigs/seconds per backend+path),
                  "stage_seconds" (prep = host hashing/scalar math,
                  compile = kernel trace/export/load, transfer = blocked in
                  device sync, total = end-to-end), "counters" (RLC
                  fallbacks, pubkey-cache hits/misses) and "last_flush"
                  (batch size, jit bucket + padding waste, chosen path).
                  Stage-to-pipeline mapping: docs/OBSERVABILITY.md.
  device_health — device_up (1/0/None), init_seconds,
                  last_call_age_s, last_error — so a
                  "verify_commit_latency = -1" run names the stalled stage
                  instead of reporting one opaque number.
  node_metrics  — node/consensus metrics snapshot from the most recent
                  in-process Node (the live_consensus / vote_storm
                  sub-benchmarks): every written node-local Prometheus
                  series as {name: {type, series}}, histograms collapsed to
                  count+sum — chain-side context (step/round durations,
                  block intervals, commit-verify seconds) next to the
                  device-side verify_stats. null when no sub-benchmark
                  constructed a node.

Run WITHOUT the test conftest (the device children need the TPU):
`python bench.py`.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np


def log(*a):
    print(*a, file=sys.stderr, flush=True)


_HOST_STAMP = None


def _host_stamp() -> dict:
    """Host identity stamped on every emitted datapoint (and each scenario
    child's JSON): machine fingerprint + git SHA + jax/jaxlib versions. The
    r04→r05 AOT failures were cross-host artifact reuse that stayed
    invisible precisely because BENCH json carried no host identity — the
    perf ledger (tools/perf_ledger.py) keys trajectory comparisons on this."""
    global _HOST_STAMP
    if _HOST_STAMP is not None:
        return _HOST_STAMP
    import platform

    out = {
        "python": platform.python_version(),
        "machine": platform.machine(),
    }
    try:
        # no jax import needed: fingerprint reads cpuinfo + dist metadata
        from tendermint_tpu.ops.cache_hardening import machine_fingerprint

        out["machine_fingerprint"] = machine_fingerprint()
    except Exception:
        out["machine_fingerprint"] = None
    from importlib import metadata

    for dist in ("jax", "jaxlib"):
        try:
            out[dist] = metadata.version(dist)
        except Exception:
            pass
    try:
        import subprocess

        sha = subprocess.run(
            ["git", "-C", os.path.dirname(os.path.abspath(__file__)),
             "rev-parse", "HEAD"],
            capture_output=True, text=True, timeout=10,
        ).stdout.strip()
        out["git_sha"] = sha or None
    except Exception:
        out["git_sha"] = None
    _HOST_STAMP = out
    return out


def make_batch(n: int, msg_len: int = 110, n_sr: int = 0):
    """n real signed (pubkey, msg, sig) triples, distinct keys, vote-sized
    msgs. The last n_sr rows are sr25519 (BASELINE config 5); the rest
    ed25519. Returns (pubkeys, msgs, sigs, key_types)."""
    from tendermint_tpu.crypto.keys import gen_ed25519

    rng = np.random.default_rng(1234)
    pubkeys, msgs, sigs, types = [], [], [], []
    n_ed = n - n_sr
    for i in range(n):
        seed = rng.integers(0, 256, 32, dtype=np.uint8).tobytes()
        msg = b"%06d|" % i + bytes(rng.integers(0, 256, msg_len - 7, dtype=np.uint8))
        if i < n_ed:
            priv = gen_ed25519(seed)
            types.append("ed25519")
        else:
            from tendermint_tpu.crypto.sr25519 import gen_sr25519

            priv = gen_sr25519(seed)
            types.append("sr25519")
        pubkeys.append(priv.pub_key().bytes())
        msgs.append(msg)
        sigs.append(priv.sign(msg))
    return pubkeys, msgs, sigs, types


def time_cpu_serial(pubkeys, msgs, sigs, types=None) -> float:
    """The reference-shaped baseline: one verify per signature, serial."""
    from tendermint_tpu.crypto.batch import verify_batch_cpu

    if types is not None and any(t != "ed25519" for t in types):
        from tendermint_tpu.crypto.keys import Ed25519PubKey
        from tendermint_tpu.crypto.sr25519 import sr25519_verify

        t0 = time.perf_counter()
        for pk, m, s, ty in zip(pubkeys, msgs, sigs, types):
            if ty == "ed25519":
                assert Ed25519PubKey(bytes(pk)).verify(bytes(m), bytes(s))
            else:
                assert sr25519_verify(bytes(pk), bytes(m), bytes(s))
        return time.perf_counter() - t0
    t0 = time.perf_counter()
    mask = verify_batch_cpu(pubkeys, msgs, sigs)
    dt = time.perf_counter() - t0
    assert mask.all()
    return dt


def time_persig(pubkeys, msgs, sigs, iters: int = 3):
    """Per-signature kernel: end-to-end (host prep + device) and device-only."""
    from tendermint_tpu.crypto.batch import prepare_batch
    from tendermint_tpu.ops.ed25519_jax import verify_prepared

    best_e2e = best_dev = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        a, r, s_bits, h_bits, precheck, n = prepare_batch(pubkeys, msgs, sigs)
        t1 = time.perf_counter()
        mask = np.asarray(verify_prepared(a, r, s_bits, h_bits))[:n]
        t2 = time.perf_counter()
        assert (mask & precheck).all()
        best_e2e = min(best_e2e, t2 - t0)
        best_dev = min(best_dev, t2 - t1)
    return best_e2e, best_dev


def time_rlc(pubkeys, msgs, sigs, iters: int = 3):
    """Production path (verify_batch -> RLC fast path). Returns
    (first_call_s, best_warm_s, prep_s_of_best). The pubkey cache is
    PREFILLED so every call (including the first) runs the cached-A kernel
    — the consensus steady state — and the plain-kernel variant never has
    to compile inside the bench budget."""
    import numpy as np

    from tendermint_tpu.crypto import batch as B

    B._fill_a_cache(np.stack([np.frombuffer(pk, dtype=np.uint8) for pk in pubkeys]))
    t0 = time.perf_counter()
    # explicit backend="jax" rides the instrumented verify_batch wrapper, so
    # each timed call also lands in the flight recorder's verify_stats
    mask = B.verify_batch(pubkeys, msgs, sigs, backend="jax")
    first = time.perf_counter() - t0
    assert mask.all()
    best = float("inf")
    prep = None
    for _ in range(iters):
        t0 = time.perf_counter()
        mask = B.verify_batch(pubkeys, msgs, sigs, backend="jax")
        dt = time.perf_counter() - t0
        assert mask.all()
        if dt < best:
            best = dt
            prep = B.LAST_RLC_TIMINGS.get("prep_ms", 0.0) / 1e3
    return first, best, prep or 0.0


def time_production(pubkeys, msgs, sigs, iters: int = 3):
    """What the framework actually does for this batch size: verify_batch
    with auto backend selection (small one-shots route to the host loop —
    a one-shot device call is RTT-bound regardless of size)."""
    from tendermint_tpu.crypto.batch import verify_batch

    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        mask = verify_batch(pubkeys, msgs, sigs)
        best = min(best, time.perf_counter() - t0)
        assert mask.all()
    return best


def rlc_slope_samples(pubkeys, msgs, sigs, ks=(1, 2, 4, 8)):
    """Slope-methodology RAW samples for the pipelined RLC path: for each k,
    time k chained submits finished with ONE batched sync. PERF.md documents
    why single-sync timings misled on an earlier runtime (a D2H sync cost a
    large VARIABLE constant); the slope of t(k) is the per-commit number — and recording the (k, t) pairs lets a suspicious slope be
    RE-FIT post-hoc instead of taken on faith. Returns
    (samples [[k, seconds], ...], slope_ms_per_batch)."""
    from tendermint_tpu.crypto import batch as B

    samples = []
    for k in ks:
        t0 = time.perf_counter()
        calls = [B._rlc_submit(pubkeys, msgs, sigs) for _ in range(k)]
        masks = B._rlc_finish_many(calls)
        dt = time.perf_counter() - t0
        for m in masks:
            assert m is not None and m.all()
        samples.append([k, round(dt, 6)])
    xs = np.array([s[0] for s in samples], dtype=np.float64)
    ys = np.array([s[1] for s in samples], dtype=np.float64)
    slope = float(((xs - xs.mean()) * (ys - ys.mean())).sum() / ((xs - xs.mean()) ** 2).sum())
    try:
        # expose the raw pairs through /debug/verify_stats too (they ride
        # extra.verify_stats into the bench JSON from there): a suspicious
        # slope is re-fittable from the stats read, no bench rerun
        from tendermint_tpu.libs import trace as _tr

        _tr.record_slope_samples(
            samples,
            slope_ms=slope * 1e3,
            fused=bool(B.LAST_FLUSH_DETAIL.get("fused")),
            source="bench",
        )
    except Exception:
        pass
    return samples, slope * 1e3


def _prep_hidden_extra(det: dict) -> dict:
    """ISSUE 18 prep-overlap telemetry from a LAST_FLUSH_DETAIL snapshot:
    prep_wall_hidden = fraction of host-prep wall that ran concurrently
    with device (or co-scheduled MSM) work, plus the per-stage prep
    breakdown. Empty dict when the path measured neither (e.g. the plain
    serial submit)."""
    out = {}
    prep_s = det.get("prep_s")
    ov = det.get("prep_overlap_s")
    if prep_s and ov is not None:
        out["prep_wall_hidden"] = round(min(1.0, ov / prep_s), 3)
        out["prep_overlap_ms"] = round(ov * 1e3, 3)
        out["prep_wall_ms"] = round(prep_s * 1e3, 3)
    stages = det.get("prep_stages")
    if stages:
        out["prep_stages_ms"] = {
            (k[:-2] if k.endswith("_s") else k): round(v * 1e3, 3)
            for k, v in stages.items()
        }
    return out


def bench_config(name: str, n: int, serial_n: int | None = None, rlc: bool = True):
    """One config: serial CPU baseline vs TPU. serial_n: subsample for the CPU
    loop when n is large (extrapolate linearly — the loop is exactly linear)."""
    log(f"[{name}] building {n} signed triples...")
    pubkeys, msgs, sigs, _ = make_batch(n)

    sn = serial_n or n
    cpu_s = time_cpu_serial(pubkeys[:sn], msgs[:sn], sigs[:sn]) * (n / sn)

    log(f"[{name}] cpu-serial {cpu_s*1e3:.2f} ms; compiling+running TPU paths...")
    persig_e2e, persig_dev = time_persig(pubkeys, msgs, sigs)
    res = {
        "n": n,
        "cpu_serial_ms": round(cpu_s * 1e3, 3),
        "persig_e2e_ms": round(persig_e2e * 1e3, 3),
        "persig_device_ms": round(persig_dev * 1e3, 3),
    }
    e2e = persig_e2e
    from tendermint_tpu.crypto.batch import RLC_MIN as _rlc_min

    if n < _rlc_min:
        # production routing: batches this small are latency-bound one-shot,
        # so verify_batch sends them to the host loop — the framework never
        # loses to the CPU baseline at sizes the device can't help with
        prod = time_production(pubkeys, msgs, sigs)
        res["production_e2e_ms"] = round(prod * 1e3, 3)
        e2e = min(e2e, prod)
    if rlc:
        rlc_first, rlc_best, rlc_prep = time_rlc(pubkeys, msgs, sigs)
        res.update(
            rlc_first_ms=round(rlc_first * 1e3, 3),
            rlc_e2e_ms=round(rlc_best * 1e3, 3),
            rlc_prep_ms=round(rlc_prep * 1e3, 3),
        )
        e2e = min(e2e, rlc_best)
        from tendermint_tpu.crypto import batch as B

        # prep-overlap telemetry for the flush time_rlc just timed (the
        # staged single-flush A-upload overlap below the stream floor;
        # above it the one chunk-bucket chunk hides nothing and reads 0)
        res.update(_prep_hidden_extra(dict(B.LAST_FLUSH_DETAIL)))

        # pipelined slope + its raw samples (warm: time_rlc prefilled the
        # caches and ran the cached-A kernel variant this samples)
        try:
            samples, slope_ms = rlc_slope_samples(pubkeys, msgs, sigs)
            res["slope_samples"] = samples
            res["pipelined_slope_ms"] = round(slope_ms, 3)
            res["slope_fused"] = bool(B.LAST_FLUSH_DETAIL.get("fused"))
            log(f"[{name}] pipelined slope {slope_ms:.1f} ms/batch, samples {samples}")
        except Exception as e:
            log(f"[{name}] slope sampling FAILED: {e}")
    res.update(
        tpu_e2e_ms=round(e2e * 1e3, 3),
        tpu_device_ms=round(min(persig_dev, e2e) * 1e3, 3),
        sigs_per_sec_e2e=round(n / e2e),
        speedup_e2e=round(cpu_s / e2e, 2),
        speedup_device=round(cpu_s / min(persig_dev, e2e), 2),
    )
    log(
        f"[{name}] persig e2e {persig_e2e*1e3:.1f} ms"
        + (f"; rlc e2e {res['rlc_e2e_ms']:.1f} ms" if rlc else "")
        + f" — {n/e2e:,.0f} sigs/s, speedup {cpu_s/e2e:.1f}x"
    )
    return res


def bench_streaming(n: int, batches: int = 6):
    """Sustained throughput: pipelined RLC submits — host prep of batch i+1
    overlaps device compute of batch i (JAX async dispatch). The shape of a
    real deployment where the verifier streams commits."""
    from tendermint_tpu.crypto import batch as B

    pubkeys, msgs, sigs, _ = make_batch(n)
    # Warm the EXACT kernel variant + shape bucket the timed loop runs:
    # prefill the pubkey cache, then one cached-A submit/finish round trip.
    # (Warming via verify_batch_jax with a cold cache compiles the PLAIN
    # kernel while the timed loop runs the CACHED one — a different
    # program — which put a 100-200s compile inside the timed region in
    # the round-3 driver run.)
    B._fill_a_cache(np.stack([np.frombuffer(pk, dtype=np.uint8) for pk in pubkeys]))
    warm = B._rlc_finish(B._rlc_submit(pubkeys, msgs, sigs))
    assert warm is not None and warm.all()
    best = 0.0
    for _ in range(2):  # first pass pays per-process dispatch warm-up
        t0 = time.perf_counter()
        calls = [B._rlc_submit(pubkeys, msgs, sigs) for _ in range(batches)]
        masks = B._rlc_finish_many(calls)
        dt = time.perf_counter() - t0
        for m in masks:
            assert m is not None and m.all()
        best = max(best, batches * n / dt)
    return best


def bench_fastsync_replay(n_blocks: int = 16, n_vals: int = 1024):
    """BASELINE config 4: fast-sync replay verifying historical commits,
    blocks x validators batched (reference: blockchain/v0/reactor.go applies
    VerifyCommitLight per block, types/validator_set.go:719 — serial in the
    reference, one device batch per block-group here). Pipelined like the
    real blocksync pool. Reports blocks/s."""
    from tendermint_tpu.crypto import batch as B
    from tendermint_tpu.crypto.keys import gen_ed25519

    # one fixed valset; each "block" has distinct vote messages signed by it
    # (host signing is setup, not timed — fast-sync receives signed commits)
    rng = np.random.default_rng(1234)
    privs = [gen_ed25519(rng.integers(0, 256, 32, dtype=np.uint8).tobytes()) for _ in range(n_vals)]
    pks = [p.pub_key().bytes() for p in privs]
    per_block = [
        [b"blk%05d|vote%06d-signbytes-padding" % (blk, i) for i in range(n_vals)]
        for blk in range(n_blocks)
    ]
    per_block_sigs = [[p.sign(m) for p, m in zip(privs, bms)] for bms in per_block]

    cpu_s = time_cpu_serial(pks[:256], per_block[0][:256], per_block_sigs[0][:256])
    cpu_blocks_per_s = 1.0 / (cpu_s * (n_vals / 256))

    # Warm the EXACT kernel variant + shape the timed loop runs (cached-A
    # submit at this lane bucket) — see bench_streaming for why warming via
    # verify_batch_jax is NOT sufficient (plain vs cached kernel variants).
    B._fill_a_cache(np.stack([np.frombuffer(pk, dtype=np.uint8) for pk in pks]))
    warm = B._rlc_finish(B._rlc_submit(pks, per_block[0], per_block_sigs[0]))
    assert warm is not None and warm.all()
    # Sentinel: one timed single-block round trip, compared against the
    # pipelined loop below — a compile sneaking into the timed region shows
    # up as first_block_ms >> the per-block pipelined time.
    t0 = time.perf_counter()
    j = min(1, n_blocks - 1)
    m0 = B._rlc_finish(B._rlc_submit(pks, per_block[j], per_block_sigs[j]))
    first_block_s = time.perf_counter() - t0
    assert m0 is not None and m0.all()
    # Two pipelined passes: the FIRST pays a per-process dispatch warm-up
    # that disappears on the second pass; steady state is the number a
    # long-running sync reaches, first-pass reported alongside.
    results = []
    for _ in range(2):
        t0 = time.perf_counter()
        calls = [B._rlc_submit(pks, per_block[i], per_block_sigs[i]) for i in range(n_blocks)]
        masks = B._rlc_finish_many(calls)
        dt = time.perf_counter() - t0
        for m in masks:
            assert m is not None and m.all()
        results.append(n_blocks / dt)
    blocks_per_s = max(results)
    return {
        "n_blocks": n_blocks,
        "n_vals": n_vals,
        "cpu_blocks_per_sec": round(cpu_blocks_per_s, 3),
        "tpu_blocks_per_sec": round(blocks_per_s, 3),
        "tpu_blocks_per_sec_first_pass": round(results[0], 3),
        "first_block_ms": round(first_block_s * 1e3, 3),
        "sigs_per_sec": round(blocks_per_s * n_vals),
        "speedup": round(blocks_per_s / cpu_blocks_per_s, 2),
    }


def bench_catchup(n_blocks: int = 48, n_vals: int = 128, super_batch: int = 16):
    """ISSUE 12: the pipelined blocksync arm vs the serial fastsync_replay
    baseline, over one synthetic signed chain. Three arms:

      serial    — the reference shape (and fastsync_replay's baseline key):
                  per block, one CPU verify per signature, then ABCI replay
                  (sampled and extrapolated like time_cpu_serial);
      per_block — one batched verify_batch per block then replay: the
                  PRE-ISSUE-12 sync loop;
      pipelined — cross-height super-batches of `super_batch` blocks
                  verified in a worker thread while the main thread replays
                  the previously verified run (the three-stage pipeline's
                  verify/apply overlap; per-signer coefficient collapse
                  makes the super-batch cheaper per signature than
                  per-block flushes on every backend).

    Reports blocks/s per arm; `speedup` = pipelined vs the serial baseline
    (the perf-ledger key; acceptance gate >= 3x)."""
    import queue as _queue
    import threading

    from tendermint_tpu.abci import types as abci_t
    from tendermint_tpu.abci.kvstore import KVStoreApplication
    from tendermint_tpu.crypto.batch import verify_batch
    from tendermint_tpu.crypto.keys import gen_ed25519

    rng = np.random.default_rng(1234)
    privs = [
        gen_ed25519(rng.integers(0, 256, 32, dtype=np.uint8).tobytes())
        for _ in range(n_vals)
    ]
    pks = [p.pub_key().bytes() for p in privs]
    per_block = [
        [b"cu%05d|vote%06d-signbytes-padding" % (blk, i) for i in range(n_vals)]
        for blk in range(n_blocks)
    ]
    per_block_sigs = [[p.sign(m) for p, m in zip(privs, bms)] for bms in per_block]
    TXS_PER_BLOCK = 8

    def apply_block(app, blk):
        for j in range(TXS_PER_BLOCK):
            app.deliver_tx(abci_t.RequestDeliverTx(tx=b"cu%05d-%d=v" % (blk, j)))
        app.commit()

    # serial baseline: one-verify-per-signature (reference VerifyCommitLight
    # loop), sampled then extrapolated, plus the per-block replay cost
    sn = min(n_vals, 128)
    cpu_s = time_cpu_serial(pks[:sn], per_block[0][:sn], per_block_sigs[0][:sn])
    app = KVStoreApplication()
    t0 = time.perf_counter()
    apply_block(app, 0)
    apply_s = time.perf_counter() - t0
    serial_bps = 1.0 / (cpu_s * (n_vals / sn) + apply_s)

    # per-block arm: one batched flush per block, verify then apply serially
    app = KVStoreApplication()
    t0 = time.perf_counter()
    for i in range(n_blocks):
        mask = verify_batch(pks, per_block[i], per_block_sigs[i])
        assert mask.all()
        apply_block(app, i)
    per_block_bps = n_blocks / (time.perf_counter() - t0)

    # pipelined arm: super-batch verify in a worker thread, replay of the
    # previous run overlapped on this thread (bounded window, like the
    # reactor's PIPELINE_WINDOW)
    app = KVStoreApplication()
    verified: "_queue.Queue" = _queue.Queue(maxsize=2)
    verify_err = []

    def verifier():
        try:
            for s in range(0, n_blocks, super_batch):
                idxs = list(range(s, min(s + super_batch, n_blocks)))
                pk_rows = [pk for _ in idxs for pk in pks]
                msg_rows = [m for i in idxs for m in per_block[i]]
                sig_rows = [sg for i in idxs for sg in per_block_sigs[i]]
                mask = verify_batch(pk_rows, msg_rows, sig_rows)
                assert mask.all()
                verified.put(idxs)
        except BaseException as e:  # surface in the main thread
            verify_err.append(e)
        finally:
            verified.put(None)

    t0 = time.perf_counter()
    th = threading.Thread(target=verifier, name="catchup-verify")
    th.start()
    while True:
        idxs = verified.get()
        if idxs is None:
            break
        for i in idxs:
            apply_block(app, i)
    th.join()
    if verify_err:
        raise verify_err[0]
    pipelined_bps = n_blocks / (time.perf_counter() - t0)

    return {
        "n_blocks": n_blocks,
        "n_vals": n_vals,
        "super_batch": super_batch,
        "serial_blocks_per_sec": round(serial_bps, 3),
        "per_block_blocks_per_sec": round(per_block_bps, 3),
        "pipelined_blocks_per_sec": round(pipelined_bps, 3),
        "sigs_per_sec": round(pipelined_bps * n_vals),
        "speedup": round(pipelined_bps / serial_bps, 2),
        "speedup_vs_per_block": round(pipelined_bps / per_block_bps, 2),
    }


def _tiled_batch(n: int, base: int):
    """n signed rows tiled from `base` distinct signed triples: pure-Python
    signing costs ~4 ms/row on wheel-less hosts, so the jumbo scenarios
    sign a base set and tile it — verification work is identical per row
    (the streamed plain kernel decompresses in-kernel per chunk), and the
    result records `tiled_from` so the ledger knows."""
    pk_b, msg_b, sig_b, _ = make_batch(min(n, base))
    reps = -(-n // len(pk_b))
    return (pk_b * reps)[:n], (msg_b * reps)[:n], (sig_b * reps)[:n], pk_b, msg_b, sig_b


def bench_verify_commit_100k(
    n: int = 100_000, base: int = 4096, sample: int | None = None,
    backend: str | None = "jax", serial_n: int = 256,
):
    """ISSUE 13 — the streamed flush planner's headline workload: ONE
    100k-validator commit (~200k MSM lanes, far past the lane-bucket
    ladder) verified as fixed-bucket chunks streamed through the RLC
    pipeline with double-buffered host prep and on-device partial
    accumulation. Reports the streamed e2e wall, the planner's chunk
    telemetry (chunks / chunk_lanes / peak lanes in flight — the
    double-buffer bound the acceptance pins at 2x the chunk bucket),
    slope-methodology RAW samples over chained streamed flushes, and
    `speedup` vs the extrapolated serial baseline. The CPU-fallback variant
    measures the same body on a `sample` subset through the chunked
    host-RLC path (this host's fast path) and extrapolates linearly."""
    from tendermint_tpu.crypto import batch as B

    rows = sample or n
    log(f"[verify_commit_100k] building {min(rows, base)} signed triples "
        f"(tiled to {rows})...")
    pubkeys, msgs, sigs, pk_b, msg_b, sig_b = _tiled_batch(rows, base)
    sn = min(serial_n, len(pk_b))
    cpu_s = time_cpu_serial(pk_b[:sn], msg_b[:sn], sig_b[:sn]) * (n / sn)

    log(f"[verify_commit_100k] serial baseline {cpu_s:.1f} s (extrapolated); "
        f"running streamed flushes...")
    first = best = None
    for _ in range(3):
        t0 = time.perf_counter()
        mask = B.verify_batch(pubkeys, msgs, sigs, backend=backend)
        dt = time.perf_counter() - t0
        assert mask.all()
        if first is None:
            first = dt
        best = dt if best is None else min(best, dt)
    det = dict(B.LAST_FLUSH_DETAIL)
    scale = n / rows
    e2e = best * scale
    # slope-methodology raw samples: k chained streamed flushes (each flush
    # syncs internally at its chunk cadence; the slope is the
    # per-super-batch number)
    samples = []
    for k in (1, 2):
        t0 = time.perf_counter()
        for _ in range(k):
            assert B.verify_batch(pubkeys, msgs, sigs, backend=backend).all()
        samples.append([k, round(time.perf_counter() - t0, 6)])
    slope_ms = (samples[1][1] - samples[0][1]) * 1e3 * scale
    chunk_lanes = det.get("chunk_lanes") or B.planner_budget()
    # planner-side accounting, absent on paths that don't stream device
    # chunks (host-RLC): report None, never a vacuous pass — the
    # independent throttle-order pin lives in tests/test_flush_planner.py
    peak = det.get("peak_lanes_in_flight")
    out = {
        "n": n,
        "tiled_from": len(pk_b),
        "cpu_serial_ms": round(cpu_s * 1e3, 3),
        "tpu_e2e_ms": round(e2e * 1e3, 3),
        "first_ms": round(first * scale * 1e3, 3),
        "sigs_per_sec_e2e": round(n / e2e),
        "speedup_e2e": round(cpu_s / e2e, 2),
        "speedup": round(cpu_s / e2e, 2),
        "slope_samples": samples,
        "pipelined_slope_ms": round(slope_ms, 3),
        "planner_budget": B.planner_budget(),
        "chunks": det.get("chunks"),
        "chunk_lanes": det.get("chunk_lanes"),
        "prep_overlap_ms": round((det.get("prep_overlap_s") or 0.0) * 1e3, 3),
        "peak_lanes_in_flight": peak,
        # the double-buffer bound: lanes in flight never exceed 2 chunks
        # (None = not measured on this path, NOT a pass)
        "lanes_in_flight_ok": (
            bool(peak <= 2 * chunk_lanes) if peak is not None else None
        ),
        "host_rlc": bool(det.get("host_rlc")),
    }
    out.update(_prep_hidden_extra(det))
    if rows != n:
        out["sample_n"] = rows
    log(f"[verify_commit_100k] streamed e2e {e2e*1e3:.1f} ms "
        f"({out['chunks']} chunks), speedup {out['speedup']}x")
    return out


def bench_super_batch(
    n_blocks: int = 16, n_vals: int = 1024, base_blocks: int = 4,
    backend: str | None = "jax", serial_n: int = 256,
):
    """ISSUE 13 — multi-commit super-batch: commits for H heights x V
    validators verified as ONE streamed cross-height flush (the shape
    blocksync's raised 64-block run cap feeds through the scheduler's
    catch-up lane) vs one flush per commit (the pre-planner loop).
    `speedup` = per-commit wall over streamed wall; slope samples ride the
    streamed arm. Rows tile `base_blocks` distinct signed commit row sets
    across the H heights (signing cost, see _tiled_batch)."""
    from tendermint_tpu.crypto import batch as B
    from tendermint_tpu.crypto.keys import gen_ed25519

    rng = np.random.default_rng(4321)
    privs = [
        gen_ed25519(rng.integers(0, 256, 32, dtype=np.uint8).tobytes())
        for _ in range(n_vals)
    ]
    pks = [p.pub_key().bytes() for p in privs]
    nb_base = min(base_blocks, n_blocks)
    log(f"[super_batch] signing {nb_base}x{n_vals} commit rows "
        f"(tiled to {n_blocks} heights)...")
    block_msgs, block_sigs = [], []
    for b in range(nb_base):
        ms = [b"sb%04d|vote%06d-signbytes-padding" % (b, i) for i in range(n_vals)]
        block_msgs.append(ms)
        block_sigs.append([p.sign(m) for p, m in zip(privs, ms)])
    blocks = [(block_msgs[b % nb_base], block_sigs[b % nb_base]) for b in range(n_blocks)]

    sn = min(serial_n, n_vals)
    cpu_s = time_cpu_serial(pks[:sn], block_msgs[0][:sn], block_sigs[0][:sn])
    serial_s = cpu_s * (n_vals / sn) * n_blocks

    # warm BOTH arms before timing either: one untimed per-commit flush
    # pays the one-time costs (kernel compile at the commit's lane bucket,
    # cold A-cache / host point-cache fill) that would otherwise land in
    # the per-commit arm only — while the streamed arm's marginal sample
    # below strips its own — biasing `speedup` upward
    assert B.verify_batch(pks, blocks[0][0], blocks[0][1], backend=backend).all()

    # per-commit arm: one flush per height (the pre-planner shape)
    t0 = time.perf_counter()
    for ms, sg in blocks:
        assert B.verify_batch(pks, ms, sg, backend=backend).all()
    per_commit_s = time.perf_counter() - t0

    # streamed arm: ONE cross-height flush through the planner
    pk_rows = [pk for _ in blocks for pk in pks]
    msg_rows = [m for ms, _ in blocks for m in ms]
    sig_rows = [s for _, sg in blocks for s in sg]
    samples = []
    streamed_s = None
    for k in (1, 2):
        t0 = time.perf_counter()
        for _ in range(k):
            assert B.verify_batch(pk_rows, msg_rows, sig_rows, backend=backend).all()
        dt = time.perf_counter() - t0
        samples.append([k, round(dt, 6)])
        if k == 1:
            streamed_s = dt
    det = dict(B.LAST_FLUSH_DETAIL)
    streamed_s = min(streamed_s, samples[1][1] - samples[0][1])
    chunk_lanes = det.get("chunk_lanes") or B.planner_budget()
    peak = det.get("peak_lanes_in_flight")  # None = path didn't measure it
    out = {
        "n_blocks": n_blocks,
        "n_vals": n_vals,
        "rows": len(pk_rows),
        "serial_s": round(serial_s, 3),
        "per_commit_commits_per_sec": round(n_blocks / per_commit_s, 3),
        "streamed_commits_per_sec": round(n_blocks / streamed_s, 3),
        "sigs_per_sec": round(len(pk_rows) / streamed_s),
        "speedup": round(per_commit_s / streamed_s, 2),
        "speedup_vs_serial": round(serial_s / streamed_s, 2),
        "slope_samples": samples,
        "planner_budget": B.planner_budget(),
        "chunks": det.get("chunks"),
        "chunk_lanes": det.get("chunk_lanes"),
        "prep_overlap_ms": round((det.get("prep_overlap_s") or 0.0) * 1e3, 3),
        "peak_lanes_in_flight": peak,
        "lanes_in_flight_ok": (
            bool(peak <= 2 * chunk_lanes) if peak is not None else None
        ),
        "host_rlc": bool(det.get("host_rlc")),
    }
    out.update(_prep_hidden_extra(det))
    log(f"[super_batch] per-commit {n_blocks/per_commit_s:.2f} commits/s, "
        f"streamed {n_blocks/streamed_s:.2f} commits/s "
        f"({out['chunks']} chunks) — {out['speedup']}x")
    return out


def bench_vote_storm(n_vals: int = 1024, heights: int = 4):
    """Live vote-path ingest shape WITHOUT the asyncio machinery: per vote,
    the receive loop's host bookkeeping — WAL MsgInfo frame (group-commit
    writer), VoteSet add (deferred vs serial verify at add time,
    reference: types/vote_set.go:203), event-bus publish — with one WAL
    flush + one deferred verify flush per 512-vote drain (the receive
    loop's batch bound). Reports votes/s both ways plus the per-stage
    µs/vote breakdown. (Before round 6 this config measured VoteSet alone;
    the ingest stages were added so the bookkeeping number covers the
    layers the live loop actually pays — PERF.md round 6.)"""
    import dataclasses
    import tempfile

    from tendermint_tpu.consensus.messages import VoteMessage
    from tendermint_tpu.consensus.wal import WAL, MsgInfo
    from tendermint_tpu.crypto.keys import gen_ed25519
    from tendermint_tpu.types.block import BlockID, PartSetHeader
    from tendermint_tpu.types.event_bus import EventBus
    from tendermint_tpu.types.validator_set import Validator, ValidatorSet
    from tendermint_tpu.types.vote import Vote
    from tendermint_tpu.types.vote_set import VoteSet

    rng = np.random.default_rng(7)
    privs = [
        gen_ed25519(rng.integers(0, 256, 32, dtype=np.uint8).tobytes())
        for _ in range(n_vals)
    ]
    vals = ValidatorSet([Validator(p.pub_key(), 10) for p in privs])
    by_addr = {p.pub_key().address(): p for p in privs}
    sorted_privs = [by_addr[v.address] for v in vals.validators]
    bid = BlockID(b"\x01" * 32, PartSetHeader(1, b"\x02" * 32))

    def signed_votes(height):
        votes = []
        for i, (val, priv) in enumerate(zip(vals.validators, sorted_privs)):
            v = Vote(type=2, height=height, round=0, block_id=bid,
                     timestamp_ns=0, validator_address=val.address,
                     validator_index=i)
            votes.append(dataclasses.replace(v, signature=priv.sign(v.sign_bytes("storm"))))
        return votes

    all_votes = [signed_votes(h + 1) for h in range(heights)]

    n_votes = heights * n_vals
    DRAIN = 512  # the receive loop's greedy-drain batch bound

    def run(defer: bool, wal: WAL):
        # FRESH Vote instances per run: the per-instance encode/sign-bytes
        # memos must start cold, as they do for votes arriving off the wire
        votes = [[dataclasses.replace(v) for v in hv] for hv in all_votes]
        bus = EventBus()  # zero subscribers — the node-without-listeners case
        t0 = time.perf_counter()
        for h in range(heights):
            vs = VoteSet("storm", h + 1, 0, 2, vals, defer_verification=defer)
            for i, v in enumerate(votes[h]):
                wal.write(MsgInfo(VoteMessage(v), "storm-peer"))
                added = vs.add_vote(v)
                if added and added != "pending":
                    bus.publish_vote(v)
                if (i + 1) % DRAIN == 0:
                    wal.flush_buffered()
                    if defer:
                        committed, _failed = vs.flush()
                        bus.publish_votes(committed)
            wal.flush_buffered()
            if defer:
                committed, failed = vs.flush()
                bus.publish_votes(committed)
                assert not failed
            assert vs.has_two_thirds_majority()
        return n_votes / (time.perf_counter() - t0)

    with tempfile.TemporaryDirectory() as tmp:
        def make_wal(tag):
            return WAL(os.path.join(tmp, f"wal-{tag}", "wal"), group_commit=True)

        run(True, make_wal("warm"))  # warm device kernels for the deferred path
        deferred = run(True, make_wal("deferred"))
        serial = run(False, make_wal("serial"))
    return {
        "n_vals": n_vals,
        "heights": heights,
        "votes_per_sec_serial": round(serial),
        "votes_per_sec_deferred": round(deferred),
        "speedup": round(deferred / serial, 2),
    }


def bench_live_consensus(n_vals: int = 1024, heights: int = 3):
    """LIVE consensus block rate: one real ConsensusState (validator 0 of an
    n_vals set) driven through its actual receive loop by n_vals-1 stub
    validators injecting signed proposals, block parts, prevotes and
    precommits — the reference's live surface (consensus/state.go
    receiveRoutine; per-vote serial verify at types/vote_set.go:203).
    Measures blocks/s with defer_vote_verification OFF (reference-shaped:
    one host verify per vote at add time) vs ON (votes queue unverified,
    flushed as one device batch per receive-loop boundary). Vote signing and
    block building are NOT timed (they belong to the other validators)."""
    import asyncio
    import dataclasses
    import tempfile

    from tendermint_tpu.abci.kvstore import KVStoreApplication
    from tendermint_tpu.config.config import test_config
    from tendermint_tpu.consensus.cs_state import ConsensusState
    from tendermint_tpu.consensus.messages import (
        BlockPartMessage,
        ProposalMessage,
        VoteMessage,
    )
    from tendermint_tpu.consensus.replay import Handshaker
    from tendermint_tpu.consensus.wal import WAL
    from tendermint_tpu.crypto.keys import gen_ed25519
    from tendermint_tpu.evidence.pool import EvidencePool
    from tendermint_tpu.libs.kvdb import MemDB
    from tendermint_tpu.mempool.mempool import Mempool
    from tendermint_tpu.privval.file_pv import FilePV
    from tendermint_tpu.proxy.multi import AppConns, local_client_creator
    from tendermint_tpu.state.execution import BlockExecutor
    from tendermint_tpu.state.sm_state import state_from_genesis
    from tendermint_tpu.state.store import StateStore
    from tendermint_tpu.store.blockstore import BlockStore
    from tendermint_tpu.types.basic import BlockID, SignedMsgType
    from tendermint_tpu.types.event_bus import EventBus
    from tendermint_tpu.types.genesis import GenesisDoc, GenesisValidator
    from tendermint_tpu.types.part_set import PartSet
    from tendermint_tpu.types.proposal import Proposal
    from tendermint_tpu.types.vote import Vote

    rng = np.random.default_rng(77)
    seeds = [rng.integers(0, 256, 32, dtype=np.uint8).tobytes() for _ in range(n_vals)]
    gen = GenesisDoc(
        chain_id="live-bench",
        validators=[
            GenesisValidator(FilePV(gen_ed25519(s)).get_pub_key(), 10) for s in seeds
        ],
    )
    gen.validate_and_complete()

    def build(defer: bool, tmp):
        # FRESH FilePVs per run: the double-sign guard carries last-signed
        # HRS across chains, so reusing them for the second (serial) run
        # would refuse to sign at height 1 ("height regression").
        privs = [FilePV(gen_ed25519(s)) for s in seeds]
        state = state_from_genesis(gen)
        by_addr = {p.get_pub_key().address(): p for p in privs}
        sorted_privs = [by_addr[v.address] for v in state.validators.validators]
        proxy = AppConns(local_client_creator(KVStoreApplication()))
        block_store = BlockStore(MemDB())
        state_store = StateStore(MemDB())
        state_store.save(state)
        event_bus = EventBus()
        mempool = Mempool(proxy.mempool)
        evpool = EvidencePool(MemDB(), state_store, block_store)
        evpool.set_state(state)
        block_exec = BlockExecutor(
            state_store, proxy.consensus, mempool, evpool,
            event_bus=event_bus, block_store=block_store,
        )
        cfg = test_config().consensus
        cfg.defer_vote_verification = defer
        cfg.wal_path = os.path.join(tmp, "wal-defer" if defer else "wal-serial", "wal")
        state = Handshaker(state_store, state, block_store, gen, event_bus).handshake(proxy)
        cs = ConsensusState(
            cfg, state, block_exec, block_store, mempool, evpool,
            WAL(
                cfg.wal_path,
                group_commit=cfg.wal_group_commit,
                group_commit_max_latency=cfg.wal_group_commit_max_latency,
            ),
            event_bus=event_bus,
            priv_validator=sorted_privs[0],
        )
        return cs, block_exec, sorted_privs

    async def run(defer: bool, tmp) -> dict:
        cs, block_exec, sorted_privs = build(defer, tmp)
        await cs.start()
        me = sorted_privs[0].get_pub_key().address()
        timed = 0.0
        votes_injected = 0
        try:
            for target_h in range(1, heights + 1):
                log(f"[live_consensus] defer={defer} height {target_h}: waiting")
                # wait for the state machine to enter the height
                while cs.rs.height != target_h:
                    await asyncio.sleep(0.005)
                rs = cs.rs
                prop_addr = rs.validators.get_proposer().address
                prop_idx = next(
                    i for i, v in enumerate(rs.validators.validators)
                    if v.address == prop_addr
                )
                # ---- untimed: the other validators' work (block + signing)
                if prop_addr != me:
                    if target_h == cs.state.initial_height:
                        from tendermint_tpu.types.block import Commit as CommitT

                        commit = CommitT(0, 0, BlockID(), ())
                    else:
                        commit = cs.rs.last_commit.make_commit()
                    block = block_exec.create_proposal_block(
                        target_h, cs.state, commit, prop_addr, time.time_ns()
                    )
                    parts = PartSet.from_data(block.encode())
                    bid = BlockID(block.hash(), parts.header)
                    prop = Proposal(
                        height=target_h, round=0, pol_round=-1,
                        block_id=bid, timestamp_ns=time.time_ns(),
                    )
                    prop = sorted_privs[prop_idx].sign_proposal("live-bench", prop)
                else:
                    # our node proposes by itself; wait for its proposal block
                    while cs.rs.proposal_block is None or cs.rs.proposal_block_parts is None:
                        await asyncio.sleep(0.005)
                    block = cs.rs.proposal_block
                    parts = cs.rs.proposal_block_parts
                    bid = BlockID(block.hash(), parts.header)
                    prop = None

                def sign_votes(vtype):
                    out = []
                    for i, p in enumerate(sorted_privs[1:], start=1):
                        v = Vote(
                            type=vtype, height=target_h, round=0, block_id=bid,
                            timestamp_ns=time.time_ns(),
                            validator_address=p.get_pub_key().address(),
                            validator_index=i,
                        )
                        sig = p.priv_key.sign(v.sign_bytes("live-bench"))
                        out.append(dataclasses.replace(v, signature=sig))
                    return out

                prevotes = sign_votes(SignedMsgType.PREVOTE)
                precommits = sign_votes(SignedMsgType.PRECOMMIT)
                log(
                    f"[live_consensus] height {target_h}: proposer_idx={prop_idx} "
                    f"injecting {len(prevotes) + len(precommits)} votes"
                )

                # ---- timed: OUR node's processing of the wire messages
                t0 = time.perf_counter()
                if prop is not None:
                    await cs.add_peer_message(ProposalMessage(prop), "bench-peer")
                    for i in range(parts.total):
                        await cs.add_peer_message(
                            BlockPartMessage(target_h, 0, parts.get_part(i)),
                            "bench-peer",
                        )
                for v in prevotes:
                    await cs.add_peer_message(VoteMessage(v), f"bench-{v.validator_index}")
                for v in precommits:
                    await cs.add_peer_message(VoteMessage(v), f"bench-{v.validator_index}")
                votes_injected += len(prevotes) + len(precommits)
                while cs.rs.height == target_h:
                    await asyncio.sleep(0.002)
                timed += time.perf_counter() - t0
        finally:
            await cs.stop()
        return {
            "blocks_per_sec": heights / timed,
            "votes_per_sec": votes_injected / timed,
            "timed_s": timed,
        }

    with tempfile.TemporaryDirectory() as tmp:
        # warm the kernels/caches the deferred path needs, then measure
        from tendermint_tpu.crypto import batch as B

        try:
            B.prewarm(n_vals - 1)
        except Exception:
            pass
        deferred = asyncio.run(run(True, tmp))
        serial = asyncio.run(run(False, tmp))
    return {
        "n_vals": n_vals,
        "heights": heights,
        "serial_blocks_per_sec": round(serial["blocks_per_sec"], 2),
        "deferred_blocks_per_sec": round(deferred["blocks_per_sec"], 2),
        "serial_votes_per_sec": round(serial["votes_per_sec"]),
        "deferred_votes_per_sec": round(deferred["votes_per_sec"]),
        "speedup": round(
            deferred["blocks_per_sec"] / serial["blocks_per_sec"], 2
        ),
        # Each deferred flush pays one device round trip; what that costs
        # against serially host-verifying the same ~1k votes is not
        # measured on today's chip.
        "note": "the device round trip floors the deferred flush",
    }


def _native_mod():
    from tendermint_tpu import native

    return native


def bench_mixed_streaming(n: int = 10000, sr_frac: float = 0.2):
    """BASELINE config 5: mixed ed25519+sr25519 validator set, streaming
    (reference: types/vote_set.go:203 verifies each vote by its key type).
    ed25519 rows ride the RLC/TPU path; sr25519 rows the host path
    (crypto/batch.verify_batch key_types routing)."""
    from tendermint_tpu.crypto.batch import verify_batch

    n_sr = int(n * sr_frac)
    pubkeys, msgs, sigs, types = make_batch(n, n_sr=n_sr)
    # type-proportional baseline: sample ed and sr rows separately and scale
    # each (make_batch puts sr rows last; a head slice would price the mixed
    # set as pure-ed25519 and understate the serial baseline)
    n_ed = n - n_sr
    se, ss = min(384, n_ed), min(128, n_sr)
    cpu_s = time_cpu_serial(pubkeys[:se], msgs[:se], sigs[:se], types[:se]) * (n_ed / se)
    cpu_s += time_cpu_serial(
        pubkeys[n_ed : n_ed + ss], msgs[n_ed : n_ed + ss], sigs[n_ed : n_ed + ss],
        types[n_ed : n_ed + ss],
    ) * (n_sr / ss)

    # warm
    assert verify_batch(pubkeys, msgs, sigs, key_types=types).all()
    best = float("inf")
    for _ in range(2):
        t0 = time.perf_counter()
        assert verify_batch(pubkeys, msgs, sigs, key_types=types).all()
        best = min(best, time.perf_counter() - t0)
    return {
        "n": n,
        "n_sr25519": n_sr,
        "cpu_serial_ms": round(cpu_s * 1e3, 3),
        "tpu_e2e_ms": round(best * 1e3, 3),
        "sigs_per_sec": round(n / best),
        "speedup": round(cpu_s / best, 2),
        # The serial baseline's sr25519 rows run the framework's own NATIVE
        # C verifier (~100 us/sig, tendermint_tpu/native/sr25519.c) — a
        # defensible native-speed host baseline, not the pure-Python merlin
        # path that inflated this headline before r5. The note reports which
        # one actually ran (no-compiler machines fall back to Python).
        "cpu_baseline_note": (
            "sr25519 host baseline is the native C verifier"
            if _native_mod().available()
            else "sr25519 host baseline is pure-Python merlin (native unavailable)"
        ),
    }


def _bls_bench_valset(n: int):
    """n-validator BLS valset with CHEAP key derivation: sk_i = sk0 + i,
    pk_{i+1} = pk_i + G1 (one Jacobian add per key instead of a full
    scalar mult — ~50x faster setup at 100k). PoP entries are injected
    directly: registration cost is per-VALIDATOR-LIFETIME, not per-commit,
    so it does not belong in the verify measurement."""
    from tendermint_tpu.crypto import bls_ref as B
    from tendermint_tpu.crypto import keys as K
    from tendermint_tpu.types.validator_set import Validator, ValidatorSet

    sk0 = B.keygen(b"\x5a" * 32)
    sks, pubs, pt = [], [], B._jac_mul(B.G1_GEN, sk0)
    for i in range(n):
        sks.append((sk0 + i) % B.R)
        pubs.append(B.g1_to_bytes(pt))
        pt = B._jac_add(pt, B.G1_GEN)
    vals = ValidatorSet(
        [Validator(K.Bls12381PubKey(pk), 10) for pk in pubs]
    )
    for pk in pubs:
        K._POP_VERIFIED.add(pk)
    # sk lookup must follow the set's address sort for signing
    by_pk = dict(zip(pubs, sks))
    ordered_sks = [by_pk[v.pub_key.bytes()] for v in vals.validators]
    return vals, ordered_sks


def bench_aggregate_verify(sizes=(1000, 10000, 100000), persig_sample: int = 4):
    """BLS aggregate-commit verification (ISSUE 14 / ROADMAP item 4): ONE
    96-byte signature + signer bitmap per commit, verified with one
    bitmap-MSM (ops/bls12_msm, the device-schedule CPU twin on this
    backend) + one pairing check (crypto/bls_ref) — against (a) the
    serial per-signature BLS baseline (what a non-aggregating BLS chain
    would pay, sampled then linearly extrapolated) and (b) the ed25519
    RLC production path at the same validator count (sampled at <= 10k,
    linearly extrapolated above — marked via ed_rlc_sample_n).

    `backend: bls12_381` keeps these numbers in their OWN perf-ledger
    column — they must never fold into the ed25519 RLC headline."""
    from tendermint_tpu.crypto import bls_ref as B
    from tendermint_tpu.crypto.batch import verify_batch
    from tendermint_tpu.types.basic import BlockID, PartSetHeader
    from tendermint_tpu.types.block import AggregateCommit

    bid = BlockID(b"\x07" * 32, PartSetHeader(1, b"\x08" * 32))
    arms = {}
    for n in sizes:
        vals, sks = _bls_bench_valset(n)
        agg_proto = AggregateCommit(
            5, 0, bid, 123456789, AggregateCommit.bitmap_of(range(n), n), b"\x00" * 96
        )
        msg = agg_proto.sign_bytes("bench-bls")
        # one aggregate signature = (sum sk_i) * H(msg): exact and O(1)
        s_total = sum(sks) % B.R
        sig = B.g2_to_bytes(B._jac_mul(B.hash_to_g2(msg), s_total))
        agg = AggregateCommit(5, 0, bid, 123456789, agg_proto.signers, sig)
        # warm + best-of-2 measured verify
        vals.verify_aggregate_commit("bench-bls", bid, 5, agg)
        best = float("inf")
        for _ in range(2):
            t0 = time.perf_counter()
            vals.verify_aggregate_commit("bench-bls", bid, 5, agg)
            best = min(best, time.perf_counter() - t0)
        # serial per-sig BLS baseline (sampled): one sign+verify per row
        sample = min(persig_sample, n)
        pks = [vals.validators[i].pub_key.bytes() for i in range(sample)]
        persig_sigs = [B.sign(sks[i], msg) for i in range(sample)]
        t0 = time.perf_counter()
        for pk, s in zip(pks, persig_sigs):
            assert B.verify(pk, msg, s)
        persig_ms = (time.perf_counter() - t0) / sample * n * 1e3
        proof_bytes = 96 + len(agg.signers)
        arms[str(n)] = {
            "agg_verify_ms": round(best * 1e3, 3),
            "bls_persig_ms": round(persig_ms, 1),
            "persig_sample_n": sample,
            "speedup": round(persig_ms / (best * 1e3), 2),
            "proof_bytes": proof_bytes,
            "ed25519_proof_bytes": n * 64,
            "proof_shrink": round(n * 64 / proof_bytes, 1),
        }
    # ed25519-RLC production arm at the same count (sampled <= 10k)
    n_top = sizes[-1]
    ed_n = min(n_top, 10000)
    pubkeys, msgs, sigs_, _ = make_batch(ed_n)
    assert verify_batch(pubkeys, msgs, sigs_).all()  # warm
    t0 = time.perf_counter()
    assert verify_batch(pubkeys, msgs, sigs_).all()
    ed_rlc_ms = (time.perf_counter() - t0) / ed_n * n_top * 1e3
    top = arms[str(n_top)]
    return {
        "n": n_top,
        "backend": "bls12_381",
        "agg_verify_ms": top["agg_verify_ms"],
        "speedup": top["speedup"],
        "proof_shrink": top["proof_shrink"],
        "ed25519_rlc_ms": round(ed_rlc_ms, 1),
        "ed_rlc_sample_n": ed_n,
        "vs_ed25519_rlc": round(ed_rlc_ms / top["agg_verify_ms"], 2),
        "arms": arms,
    }


import contextlib


def bench_chaos_recovery(n: int = 512):
    """Chaos scenario: persistent injected device failure -> circuit breaker
    trips -> sticky CPU flushes (no device retries) -> heal -> probe re-arms
    the TPU path. Reports the recovery latencies a production operator cares
    about. Cheap by construction: the injector raises at the device ENTRY
    points, so no kernel runs while faulted."""
    from tendermint_tpu.chaos.device import DeviceFaultInjector
    from tendermint_tpu.crypto import batch
    from tendermint_tpu.crypto.circuit_breaker import VerifyCircuitBreaker

    pubkeys, msgs, sigs, _types = make_batch(n)
    orig_breaker = batch.BREAKER
    inj = DeviceFaultInjector().install()
    try:
        batch.BREAKER = VerifyCircuitBreaker(
            probe=batch._breaker_probe,
            failure_threshold=3,
            spawn_probe_thread=False,  # re-arm timed explicitly below
        )
        # healthy baseline flush (first call may compile; time the second)
        batch.verify_batch(pubkeys, msgs, sigs, backend="jax")
        t0 = time.perf_counter()
        batch.verify_batch(pubkeys, msgs, sigs, backend="jax")
        closed_flush_ms = (time.perf_counter() - t0) * 1e3

        # persistent failure: count flushes until the breaker opens
        inj.set_persistent(True)
        flushes_to_trip = 0
        t0 = time.perf_counter()
        while batch.BREAKER.allow_device():
            batch.verify_batch(pubkeys, msgs, sigs, backend="jax")
            flushes_to_trip += 1
            if flushes_to_trip > 50:
                raise RuntimeError("breaker never tripped")
        trip_latency_ms = (time.perf_counter() - t0) * 1e3

        # OPEN: degraded flushes must be pure CPU (zero device entries)
        calls_at_open = inj.calls
        t0 = time.perf_counter()
        batch.verify_batch(pubkeys, msgs, sigs, backend="jax")
        open_flush_ms = (time.perf_counter() - t0) * 1e3
        device_calls_while_open = inj.calls - calls_at_open

        # heal -> probe -> TPU path restored
        inj.heal()
        t0 = time.perf_counter()
        probe_ok = batch.BREAKER.probe_now()
        rearm_ms = (time.perf_counter() - t0) * 1e3
        snap = batch.BREAKER.snapshot()
        return {
            "n": n,
            "closed_flush_ms": round(closed_flush_ms, 3),
            "flushes_to_trip": flushes_to_trip,
            "trip_latency_ms": round(trip_latency_ms, 3),
            "open_flush_ms": round(open_flush_ms, 3),
            "device_calls_while_open": device_calls_while_open,
            "probe_ok": bool(probe_ok),
            "rearm_ms": round(rearm_ms, 3),
            "trips": snap["trips"],
        }
    finally:
        inj.uninstall()
        batch.BREAKER = orig_breaker


def bench_overload():
    """Overload scenario (docs/ROBUSTNESS.md "Overload protection"): a live
    single-validator node flooded with tx admissions from concurrent
    threads — the RPC-broadcast-burst shape without HTTP overhead. Reports
    tx-admission latency under flood, the shed/eviction/rejection counts
    the admission layer produced, and the block-interval delta vs the
    unloaded baseline. Host-side by construction (no device work: admission
    control is mempool/RPC/lock behavior)."""
    import asyncio
    import threading

    from tendermint_tpu.abci.kvstore import KVStoreApplication
    from tendermint_tpu.config.config import test_config
    from tendermint_tpu.crypto import gen_ed25519
    from tendermint_tpu.node.node import Node
    from tendermint_tpu.privval.file_pv import FilePV
    from tendermint_tpu.types.genesis import GenesisDoc, GenesisValidator

    cfg = test_config()
    cfg.base.db_backend = "memdb"
    cfg.rpc.laddr = ""
    cfg.root_dir = ""
    import tempfile

    cfg.consensus.wal_path = os.path.join(tempfile.mkdtemp(), "wal")
    cfg.mempool.size = 500  # small enough that the flood saturates it
    cfg.mempool.ttl_num_blocks = 4
    cfg.overload.sample_interval = 0.05
    priv = FilePV(gen_ed25519(b"\x71" * 32))
    gen = GenesisDoc(
        chain_id="bench-overload",
        validators=[GenesisValidator(priv.get_pub_key(), 10)],
    )
    node = Node(cfg, gen, priv_validator=priv, app=KVStoreApplication())
    # admission control is host-side; don't spend the bench budget compiling
    # single-validator verify kernels in the prewarm thread
    node._start_crypto_prewarm = lambda: None

    BASELINE_HEIGHTS, FLOOD_HEIGHTS, N_FLOODERS = 8, 12, 4
    lat: list = []
    stop = threading.Event()

    def flooder(k: int):
        i = 0
        while not stop.is_set():
            tx = b"ov-%d-%d=x" % (k, i)
            i += 1
            t0 = time.perf_counter()
            try:
                node.mempool.check_tx(tx)
            except Exception:
                pass
            lat.append(time.perf_counter() - t0)

    async def run():
        await node.start()
        try:
            await node.wait_for_height(2, timeout=60)
            h0 = node.block_store.height
            t0 = time.perf_counter()
            await node.wait_for_height(h0 + BASELINE_HEIGHTS, timeout=120)
            baseline_s = (time.perf_counter() - t0) / BASELINE_HEIGHTS

            threads = [
                threading.Thread(target=flooder, args=(k,), daemon=True)
                for k in range(N_FLOODERS)
            ]
            h1 = node.block_store.height
            t1 = time.perf_counter()
            for t in threads:
                t.start()
            await node.wait_for_height(h1 + FLOOD_HEIGHTS, timeout=300)
            flood_s = (time.perf_counter() - t1) / FLOOD_HEIGHTS
            stop.set()
            for t in threads:
                t.join(timeout=5.0)
            return baseline_s, flood_s
        finally:
            stop.set()
            await node.stop()

    baseline_s, flood_s = asyncio.run(run())
    lat.sort()

    def pct(p):
        return round(lat[min(len(lat) - 1, int(p * len(lat)))] * 1e6, 1) if lat else None

    mm = node.metrics.mempool
    rejected = {k[0]: int(v) for k, v in mm.rejected_txs._values.items()}
    out = {
        "baseline_block_interval_ms": round(baseline_s * 1e3, 1),
        "flood_block_interval_ms": round(flood_s * 1e3, 1),
        "block_interval_ratio": round(flood_s / baseline_s, 2),
        "admissions_attempted": len(lat),
        "admission_latency_us": {"p50": pct(0.50), "p90": pct(0.90), "p99": pct(0.99)},
        "evicted_txs": node.mempool.evicted_total,
        "expired_txs": node.mempool.expired_total,
        "rejected_txs": rejected,
        "overload": node.overload.snapshot(),
    }
    # per-stage lifecycle waterfall under flood (libs/txtrace.py): the perf
    # ledger's trajectory gains latency ATTRIBUTION columns — where between
    # admission and commit the flood's txs spent their time, and how each
    # journey ended — not just throughput
    tt = getattr(node, "tx_tracker", None)
    if tt is not None:
        tstats = tt.stats()
        out["tx_stage_waterfall"] = {
            "stage_percentiles": tstats["stage_percentiles"],
            "terminals": tstats["terminals"],
            "tracked": tstats["tracked"],
            "ring_evictions": tstats["ring_evictions"],
        }
    return out


def make_light_chain(heights: int, n_vals: int, chain_id: str = "bench-light"):
    """`heights` signed light blocks with correct hash/valset chaining
    (constant validator set — the scenario measures the serving layer's
    coalescing, not bisection). Returns (blocks, now_ns, period_ns)."""
    from tendermint_tpu.crypto import tmhash
    from tendermint_tpu.crypto.keys import gen_ed25519
    from tendermint_tpu.types.basic import (
        NANOS,
        BlockID,
        BlockIDFlag,
        PartSetHeader,
    )
    from tendermint_tpu.types.block import (
        Commit,
        CommitSig,
        ConsensusVersion,
        Header,
    )
    from tendermint_tpu.types.light import LightBlock, SignedHeader
    from tendermint_tpu.types.validator_set import Validator, ValidatorSet

    privs = [
        gen_ed25519(bytes([i % 256, i // 256]) + b"\x5a" * 30)
        for i in range(n_vals)
    ]
    vals = ValidatorSet([Validator(p.pub_key(), 10) for p in privs])
    by_addr = {p.pub_key().address(): p for p in privs}
    t0 = 1_700_000_000 * NANOS
    blocks = {}
    prev_hash = b""
    for h in range(1, heights + 1):
        header = Header(
            version=ConsensusVersion(),
            chain_id=chain_id,
            height=h,
            time_ns=t0 + h * NANOS,
            last_block_id=(
                BlockID(prev_hash, PartSetHeader(1, tmhash.sum256(prev_hash)))
                if prev_hash
                else BlockID()
            ),
            last_commit_hash=tmhash.sum256(b"lc%d" % h),
            data_hash=tmhash.sum256(b"d%d" % h),
            validators_hash=vals.hash(),
            next_validators_hash=vals.hash(),
            consensus_hash=tmhash.sum256(b"c"),
            app_hash=tmhash.sum256(b"a%d" % h),
            last_results_hash=tmhash.sum256(b"r%d" % h),
            evidence_hash=tmhash.sum256(b"e"),
            proposer_address=vals.get_proposer().address,
        )
        block_id = BlockID(header.hash(), PartSetHeader(1, tmhash.sum256(header.hash())))
        placeholder = [
            CommitSig(BlockIDFlag.COMMIT, v.address, header.time_ns, b"\x00" * 64)
            for v in vals.validators
        ]
        commit = Commit(h, 0, block_id, placeholder)
        sigs = []
        for idx, v in enumerate(vals.validators):
            sb = commit.vote_sign_bytes(chain_id, idx)
            sigs.append(
                CommitSig(
                    BlockIDFlag.COMMIT, v.address, header.time_ns,
                    by_addr[v.address].sign(sb),
                )
            )
        blocks[h] = LightBlock(SignedHeader(header, Commit(h, 0, block_id, sigs)), vals)
        prev_hash = header.hash()
    now_ns = t0 + (heights + 3600) * NANOS
    return blocks, now_ns, 7 * 24 * 3600 * NANOS


def bench_light_serve(
    heights: int = 24,
    n_vals: int = 32,
    clients: int = 32,
    requests: int = 600,
    window: float = 0.02,
    seed: int = 7,
):
    """Light-client-as-a-service scenario (docs/LIGHT.md, ROADMAP item 3):
    N concurrent clients issue `requests` skipping-verification requests
    with Zipfian height popularity against a LightService over a synthetic
    signed chain. Reports sustained client-verifications/s, per-request
    p50/p99 latency, and the coalesced-vs-serial speedup — serial = each
    request running its OWN verify_non_adjacent (no cache, no shared
    flushes), which is what answering every client individually costs.
    Host-side by construction on CPU backends; on a device backend the
    coalesced flush is the same verify_batch pipeline the consensus path
    uses."""
    import asyncio
    import random

    from tendermint_tpu.config.config import LightServiceConfig
    from tendermint_tpu.light import verifier as light_verifier
    from tendermint_tpu.light.provider import MockProvider
    from tendermint_tpu.light.service import LightService
    from tendermint_tpu.types.basic import NANOS

    chain_id = "bench-light"
    log(f"[light_serve] building {heights}x{n_vals} signed chain...")
    blocks, now_ns, period_ns = make_light_chain(heights, n_vals, chain_id)
    drift_ns = 10 * NANOS

    rng = random.Random(seed)
    ranks = list(range(2, heights + 1))
    weights = [1.0 / (i + 1) ** 1.1 for i in range(len(ranks))]
    reqs = rng.choices(ranks, weights, k=requests)

    # serial baseline: per-request skipping verification from the anchor,
    # sampled and extrapolated (it is exactly linear in requests)
    anchor = blocks[1]
    sample = reqs[: min(len(reqs), 60)]
    t0 = time.perf_counter()
    for h in sample:
        light_verifier.verify(
            chain_id, anchor.signed_header, anchor.validator_set,
            blocks[h].signed_header, blocks[h].validator_set,
            period_ns, now_ns, drift_ns,
        )
    serial_per_req = (time.perf_counter() - t0) / len(sample)

    svc = LightService(
        chain_id,
        MockProvider(chain_id, blocks),
        LightServiceConfig(
            coalesce_window=window,
            max_heights_per_flush=heights + 1,
            max_pending=0,  # the bench measures throughput, not shedding
        ),
        now_ns=lambda: now_ns,
    )
    lats: list = []

    async def client_task(my_reqs):
        for h in my_reqs:
            t1 = time.perf_counter()
            await svc.verify_height(h)
            lats.append(time.perf_counter() - t1)

    async def run():
        chunks = [reqs[i::clients] for i in range(clients)]
        t1 = time.perf_counter()
        await asyncio.gather(*[client_task(c) for c in chunks if c])
        return time.perf_counter() - t1

    wall = asyncio.run(run())
    svc.close()
    lats.sort()

    def pct(p):
        return round(lats[min(len(lats) - 1, int(p * len(lats)))] * 1e3, 3)

    stats = svc.stats()
    coalesced_per_req = wall / len(reqs)
    return {
        "heights": heights,
        "validators": n_vals,
        "clients": clients,
        "requests": len(reqs),
        "zipf_exponent": 1.1,
        "seed": seed,
        "client_verifs_per_sec": round(len(reqs) / wall),
        "latency_ms": {"p50": pct(0.50), "p99": pct(0.99)},
        "serial_per_req_ms": round(serial_per_req * 1e3, 3),
        "coalesced_per_req_ms": round(coalesced_per_req * 1e3, 3),
        "speedup": round(serial_per_req / coalesced_per_req, 2),
        "device_flushes": stats["flushes"],
        "coalesced_lanes_total": stats["lanes_total"],
        "cache_hits": stats["cache_hits"],
        "singleflight_waits": stats["singleflight_waits"],
        "windows_fired": stats["coalescer"]["windows_fired"],
        # per-request stage attribution (ISSUE 10): the p99 above decomposed
        # into cache probe / coalesce wait / flush wall / bisection
        "stage_percentiles": stats.get("stage_percentiles", {}),
    }


def bench_multichip(n: int = 4096):
    """ROADMAP item 1 leftover: fused single-chip AND sharded multi-chip RLC
    numbers in ONE scenario, with slope-methodology raw samples and the
    per-shard mesh telemetry (PR 7) attached — so a device round records
    both datapoints in the perf ledger instead of MULTICHIP dryruns that
    leave no benchmark. On a CPU-only host the mesh is 8 VIRTUAL devices
    (XLA_FLAGS --xla_force_host_platform_device_count, set by the scenario
    child env): the numbers are marked `virtual_devices` and prove the
    plumbing, not the hardware."""
    import jax

    from tendermint_tpu.crypto import batch as B
    from tendermint_tpu.parallel import telemetry as mesh_tm

    devices = jax.devices()
    report = {
        "n": n,
        "devices_visible": len(devices),
        "platform": devices[0].platform if devices else "none",
        "virtual_devices": bool(devices) and devices[0].platform == "cpu",
    }
    pubkeys, msgs, sigs, _ = make_batch(n)

    # -- fused single-chip: the production RLC path, slope methodology ------
    os.environ["TMTPU_SHARDED"] = "0"
    B._SHARDED_RUNNER = None
    try:
        log(f"[multichip] single-chip RLC over {n} sigs...")
        rlc_first, rlc_best, rlc_prep = time_rlc(pubkeys, msgs, sigs)
        single = {
            "rlc_first_ms": round(rlc_first * 1e3, 3),
            "rlc_e2e_ms": round(rlc_best * 1e3, 3),
            "rlc_prep_ms": round(rlc_prep * 1e3, 3),
            "fused": bool(B.LAST_FLUSH_DETAIL.get("fused")),
        }
        try:
            samples, slope_ms = rlc_slope_samples(pubkeys, msgs, sigs)
            single["slope_samples"] = samples
            single["pipelined_slope_ms"] = round(slope_ms, 3)
        except Exception as e:
            log(f"[multichip] single-chip slope sampling FAILED: {e}")
        report["single_chip"] = single

        # -- sharded: the same combined check over the mesh -----------------
        os.environ["TMTPU_SHARDED"] = "1"
        B._SHARDED_RUNNER = None
        env = B._sharded_env()
        if env is None:
            # no mesh: the sharded arm did NOT run — omit the ledger's
            # `speedup` key entirely rather than fabricate parity
            report["sharded"] = {"error": "no multi-device mesh available"}
            return report
        log(f"[multichip] sharded RLC over {env[0]} devices...")
        t0 = time.perf_counter()
        mask = B.verify_batch_jax(pubkeys, msgs, sigs)
        sharded_first = time.perf_counter() - t0
        assert mask.all() and B.LAST_JAX_PATH[0] == "rlc-sharded", B.LAST_JAX_PATH[0]
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            mask = B.verify_batch_jax(pubkeys, msgs, sigs)
            best = min(best, time.perf_counter() - t0)
            assert mask.all()
        report["sharded"] = {
            "n_devices": env[0],
            "first_ms": round(sharded_first * 1e3, 3),
            "e2e_ms": round(best * 1e3, 3),
            "path": B.LAST_JAX_PATH[0],
            # per-shard evidence (PR 7): lanes, pad waste, all_gather bytes
            "mesh_telemetry": mesh_tm.mesh_stats(),
        }
        # the ledger's matrix key: sharded speedup over the fused
        # single-chip path on the SAME host (virtual CPU meshes typically
        # read < 1x — the honest number for plumbing-only rounds)
        report["speedup"] = round(rlc_best / best, 2)
        report["sigs_per_sec_sharded"] = round(n / best)
        return report
    finally:
        os.environ.pop("TMTPU_SHARDED", None)
        B._SHARDED_RUNNER = None


def bench_mesh_failover(n: int = 2048):
    """ISSUE 19 elastic mesh: throughput BEFORE / DURING / AFTER a seeded
    device loss on the sharded mesh, the rebuild latency, and a zero-lost
    -verdicts check. One mesh device is declared lost mid-run (chaos
    injector, deterministic): the faulted flush replays on the survivor
    mesh and must return the byte-identical verdict mask; subsequent
    flushes stay SHARDED (survivor rung, not CPU-degraded); after revive +
    clean probes the device re-joins and full-mesh throughput returns. On
    a CPU-only host the mesh is 8 VIRTUAL devices (same XLA flag as the
    multichip scenario): numbers prove the plumbing, not the hardware."""
    import jax

    from tendermint_tpu.chaos.device import DeviceFaultInjector
    from tendermint_tpu.crypto import batch as B
    from tendermint_tpu.parallel import telemetry as mesh_tm
    from tendermint_tpu.parallel.health import MESH_HEALTH

    devices = jax.devices()
    report = {
        "n": n,
        "devices_visible": len(devices),
        "platform": devices[0].platform if devices else "none",
        "virtual_devices": bool(devices) and devices[0].platform == "cpu",
    }
    pubkeys, msgs, sigs, _ = make_batch(n)

    os.environ["TMTPU_SHARDED"] = "1"
    B._SHARDED_RUNNER = None
    B.BREAKER.reset()
    MESH_HEALTH.reset()
    old_memo = B._MEMO
    B.configure_verified_memo(rows=0)  # repeat flushes must hit the device
    old_spawn = MESH_HEALTH._spawn_probe_thread
    MESH_HEALTH._spawn_probe_thread = False  # drive probes deterministically
    inj = DeviceFaultInjector().install()
    try:
        env = B._sharded_env()
        if env is None:
            report["error"] = "no multi-device mesh available"
            return report
        nd_full = env[0]
        report["n_devices"] = nd_full

        def _best_of(k: int) -> float:
            best = float("inf")
            for _ in range(k):
                t0 = time.perf_counter()
                mask = B.verify_batch_jax(pubkeys, msgs, sigs)
                best = min(best, time.perf_counter() - t0)
                assert mask.all()
            return best

        log(f"[mesh_failover] full mesh ({nd_full} devices): warm + baseline...")
        baseline_mask = B.verify_batch_jax(pubkeys, msgs, sigs)  # compile
        assert baseline_mask.all() and B.LAST_JAX_PATH[0] == "rlc-sharded"
        before = _best_of(3)
        report["before"] = {
            "e2e_ms": round(before * 1e3, 3),
            "sigs_per_sec": round(n / before),
            "ladder": B.mesh_ladder_state(),
        }

        # -- DURING: lose the last mesh device; the flush must replay on the
        # survivor mesh and lose zero verdicts --------------------------------
        log(f"[mesh_failover] losing device {nd_full - 1} mid-run...")
        inj.arm_device_lost(nd_full - 1)
        t0 = time.perf_counter()
        mask = B.verify_batch_jax(pubkeys, msgs, sigs)
        during = time.perf_counter() - t0
        lost_verdicts = int(n - int(np.asarray(mask).sum()))
        byte_identical = bool(
            (np.asarray(mask) == np.asarray(baseline_mask)).all()
        )
        surv_env = B._sharded_env()
        report["during"] = {
            "e2e_ms": round(during * 1e3, 3),
            "path": B.LAST_JAX_PATH[0],
            "mesh_replays": B.LAST_FLUSH_DETAIL.get("mesh_replays", 0),
            "lost_verdicts": lost_verdicts,
            "mask_byte_identical": byte_identical,
            "survivor_devices": surv_env[0] if surv_env else 0,
        }
        assert lost_verdicts == 0, f"{lost_verdicts} verdicts lost in failover"
        assert byte_identical, "failover mask diverged from the baseline"

        # -- degraded steady state: still SHARDED, on the survivor mesh ------
        degraded_best = _best_of(3)
        report["degraded"] = {
            "e2e_ms": round(degraded_best * 1e3, 3),
            "sigs_per_sec": round(n / degraded_best),
            "path": B.LAST_JAX_PATH[0],
            "ladder": B.mesh_ladder_state(),
        }
        assert B.LAST_JAX_PATH[0] == "rlc-sharded", (
            f"post-loss flushes CPU-degraded: {B.LAST_JAX_PATH[0]}"
        )
        stats = mesh_tm.mesh_stats()
        report["rebuild_s"] = (stats.get("last_rebuild") or {}).get("seconds")
        report["rebuilds"] = stats.get("rebuilds", 0)

        # -- AFTER: revive, clean probes, rejoin, full-mesh steady state -----
        log("[mesh_failover] reviving the lost device...")
        inj.revive_device()
        probes = 0
        while MESH_HEALTH.dead_count() and probes < 16:
            MESH_HEALTH.probe_round()
            probes += 1
        after = _best_of(3)
        after_env = B._sharded_env()
        report["after"] = {
            "e2e_ms": round(after * 1e3, 3),
            "sigs_per_sec": round(n / after),
            "n_devices": after_env[0] if after_env else 0,
            "rejoin_probes": probes,
            "ladder": B.mesh_ladder_state(),
        }
        # the ledger's mesh-degrade column: survivor-mesh throughput as a
        # fraction of the full mesh's on the SAME host (plus the final rung)
        report["degrade_ratio"] = round(before / degraded_best, 3)
        report["mesh_ladder"] = report["after"]["ladder"]
        report["mesh_telemetry"] = mesh_tm.mesh_stats()
        return report
    finally:
        inj.uninstall()
        inj.heal()
        MESH_HEALTH.reset()
        MESH_HEALTH._spawn_probe_thread = old_spawn
        B._MEMO = old_memo
        B.BREAKER.reset()
        os.environ.pop("TMTPU_SHARDED", None)
        B._SHARDED_RUNNER = None


def bench_mesh_failover_host(n: int = 2048):
    """CPU-fallback twin of mesh_failover: no mesh exists in the degraded
    child, so this measures the ladder's BOTTOM rung — the chunked host-RLC
    path the elastic mesh degrades to when every device is gone — and
    stamps the ladder state so the column never reads as a silent pass."""
    from tendermint_tpu.crypto import batch as B

    pubkeys, msgs, sigs, _ = make_batch(n)
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        mask = B.verify_batch(pubkeys, msgs, sigs)
        best = min(best, time.perf_counter() - t0)
        assert mask.all()
    return {
        "n": n,
        "e2e_ms": round(best * 1e3, 3),
        "sigs_per_sec": round(n / best),
        "host_rlc": bool(B.LAST_FLUSH_DETAIL.get("host_rlc")),
        "mesh_ladder": "host",
        "degraded": "cpu-fallback",
    }


def bench_tx_admission(
    flood_s: float = 8.0,
    batch_txs: int = 256,
    n_senders: int = 4,
    n_keys: int = 16,
):
    """Device-batched tx admission (ISSUE 11, the headline workload of the
    global verification scheduler): sustained tx-admissions/s under a
    signed-tx flood with live consensus running concurrently.

    Three phases on ONE live single-validator node running the
    signed_kvstore app with deferred vote verification (so the vote path
    rides the scheduler's VOTES lane):

      baseline   no flood — the vote path's per-flush wall, unloaded;
      serial     flood with sig_precheck OFF: every CheckTx pays the
                 app-side serial host verify (the pre-scheduler path);
      batched    flood with sig_precheck ON: envelopes batch-verify through
                 the ADMISSION lane, the app consumes verdicts.

    The flood is the gossip-reactor shape (check_tx_batch: one admission-
    lane submit per batch) from `n_senders` threads. Reports admissions/s
    per arm, their ratio as `speedup` (the perf-ledger matrix key), and the
    vote-lane p99 flush wait baseline-vs-flood (must stay flat: votes
    preempt)."""
    import asyncio
    import tempfile
    import threading

    from tendermint_tpu.abci.kvstore import SignedKVStoreApplication
    from tendermint_tpu.config.config import test_config
    from tendermint_tpu.crypto import gen_ed25519
    from tendermint_tpu.node.node import Node
    from tendermint_tpu.privval.file_pv import FilePV
    from tendermint_tpu.types.genesis import GenesisDoc, GenesisValidator
    from tendermint_tpu.types.signed_tx import encode_signed_tx

    import jax

    cfg = test_config()
    cfg.base.db_backend = "memdb"
    cfg.rpc.laddr = ""
    cfg.root_dir = ""
    cfg.consensus.wal_path = os.path.join(tempfile.mkdtemp(), "wal")
    cfg.consensus.defer_vote_verification = True
    cfg.mempool.size = 500_000
    cfg.mempool.cache_size = 1_000_000
    cfg.mempool.ttl_num_blocks = 2
    # the scenario measures ADMISSION throughput; post-commit rechecks are
    # their own (now also admission-lane-batched) axis and would otherwise
    # re-verify the whole resident pool every committed block in BOTH arms
    cfg.mempool.recheck = False
    if jax.default_backend() == "cpu":
        # XLA:CPU kernel compiles run MINUTES on small hosts; the host-RLC
        # combined check (crypto/batch.verify_batch_cpu) is the honest fast
        # path for this host class — and still an order of magnitude over
        # the serial per-tx loop
        cfg.scheduler.backend = "cpu"
    app = SignedKVStoreApplication()
    priv = FilePV(gen_ed25519(b"\x72" * 32))
    gen = GenesisDoc(
        chain_id="bench-tx-admission",
        validators=[GenesisValidator(priv.get_pub_key(), 10)],
    )
    node = Node(cfg, gen, priv_validator=priv, app=app)
    node._start_crypto_prewarm = lambda: None
    sched = node.scheduler
    assert sched is not None, "tx_admission needs [scheduler] enabled"

    # pre-signed tx corpus (signing is milliseconds per tx on wheel-less
    # hosts — it must not serialize the flood): n_keys signers, unique
    # payloads per phase so the dedup cache never collapses the flood
    log(f"[tx_admission] pre-signing tx corpus ({n_keys} keys)...")
    keys = [gen_ed25519(bytes([k + 1]) * 32) for k in range(n_keys)]

    def corpus(tag: str, count: int):
        txs = [
            encode_signed_tx(keys[i % n_keys], b"%s-%d=x" % (tag.encode(), i))
            for i in range(count)
        ]
        return [txs[i : i + batch_txs] for i in range(0, len(txs), batch_txs)]

    def vote_samples(t0: float, t1: float):
        """votes-lane per-flush WALLS inside a window, off the scheduler's
        flush journal — the vote path never queues (inline preemption), so
        the wall (verify incl. any GIL/device contention with bulk flushes)
        is the latency the vote path actually feels."""
        # list() first: the dispatch thread appends concurrently, and a
        # deque mutated mid-iteration raises (the snapshot is GIL-atomic)
        return [
            f["wall_s"]
            for f in list(sched.flush_log)
            if "votes" in f["rows"] and t0 <= f["t"] <= t1
        ]

    def flood(batches, stop_t):
        admitted = 0
        rejected = 0
        lock = threading.Lock()
        idx = {"i": 0}

        def worker():
            nonlocal admitted, rejected
            while True:
                with lock:
                    i = idx["i"]
                    idx["i"] += 1
                if i >= len(batches) or time.monotonic() >= stop_t:
                    return
                out = node.mempool.check_tx_batch(batches[i], sender="bench-%d" % (i % n_senders))
                ok = sum(1 for r in out if r is not None and r.code == 0)
                with lock:
                    admitted += ok
                    rejected += len(out) - ok

        threads = [threading.Thread(target=worker, daemon=True) for _ in range(n_senders)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return admitted, rejected, time.perf_counter() - t0

    def pct(xs, p):
        if not xs:
            return None
        xs = sorted(xs)
        return xs[min(len(xs) - 1, int(p * len(xs)))]

    async def run():
        await node.start()
        try:
            await node.wait_for_height(2, timeout=120)
            # -- baseline vote window (no flood) --
            tb0 = time.monotonic()
            h0 = node.block_store.height
            await node.wait_for_height(h0 + 6, timeout=180)
            tb1 = time.monotonic()
            base_votes = vote_samples(tb0, tb1)

            loop = asyncio.get_running_loop()
            # -- serial arm: the app pays per-tx host verifies (corpus
            # sized to the window: serial admits O(100s)/s) --
            node.mempool.sig_precheck = False
            batches = await loop.run_in_executor(None, corpus, "ser", 6_000)
            stop_t = time.monotonic() + flood_s
            serial = await loop.run_in_executor(
                None, flood, batches, stop_t
            )
            # -- batched arm: admission lane + verdict consumption (an
            # exhausted corpus just ends the arm early; rate = admitted/wall
            # either way) --
            node.mempool.sig_precheck = True
            batches = await loop.run_in_executor(None, corpus, "bat", 30_000)
            tf0 = time.monotonic()
            stop_t = time.monotonic() + flood_s
            batched = await loop.run_in_executor(
                None, flood, batches, stop_t
            )
            tf1 = time.monotonic()
            flood_votes = vote_samples(tf0, tf1)
            return base_votes, serial, batched, flood_votes
        finally:
            await node.stop()

    base_votes, serial, batched, flood_votes = asyncio.run(run())
    s_adm, s_rej, s_wall = serial
    b_adm, b_rej, b_wall = batched
    serial_rate = s_adm / s_wall if s_wall else 0.0
    batched_rate = b_adm / b_wall if b_wall else 0.0
    base_p99 = pct(base_votes, 0.99)
    flood_p99 = pct(flood_votes, 0.99)
    adm_flushes = [f for f in list(sched.flush_log) if "admission" in f["rows"]]
    out = {
        "flood_s": flood_s,
        "batch_txs": batch_txs,
        "senders": n_senders,
        "serial": {
            "admitted": s_adm, "rejected": s_rej,
            "admissions_per_sec": round(serial_rate, 1),
            "app_serial_verifies": app.serial_verifies,
        },
        "batched": {
            "admitted": b_adm, "rejected": b_rej,
            "admissions_per_sec": round(batched_rate, 1),
            "precheck_consumed": app.precheck_consumed,
            "admission_flushes": len(adm_flushes),
            "admission_rows_per_flush_max": max(
                (f["rows"]["admission"] for f in adm_flushes), default=0
            ),
        },
        "speedup": round(batched_rate / serial_rate, 2) if serial_rate else None,
        "vote_path": {
            "baseline_flushes": len(base_votes),
            "flood_flushes": len(flood_votes),
            "baseline_wall_p99_ms": round(base_p99 * 1e3, 3) if base_p99 is not None else None,
            "flood_wall_p99_ms": round(flood_p99 * 1e3, 3) if flood_p99 is not None else None,
            "p99_ratio": (
                round(flood_p99 / base_p99, 2)
                if base_p99 and flood_p99 is not None else None
            ),
            "preemptions": sched.preemptions,
            # on pure-CPU hosts the admission flushes are host compute and
            # contend with vote verification for the GIL; on a device
            # backend the flush releases the host while the device works
            "note": (
                "cpu host: flood arm contends for the GIL"
                if jax.default_backend() == "cpu" else "device backend"
            ),
        },
        "scheduler": {
            k: v for k, v in sched.stats().items()
            if k in ("flushes", "preemptions", "inline_fallbacks", "lane_wait_percentiles")
        },
    }
    log(
        f"[tx_admission] serial {serial_rate:,.0f}/s vs batched "
        f"{batched_rate:,.0f}/s ({out['speedup']}x); vote wall p99 "
        f"{out['vote_path']['baseline_wall_p99_ms']} -> "
        f"{out['vote_path']['flood_wall_p99_ms']} ms"
    )
    return out


def bench_poisoned_flush(n: int = 512, calls: int = 128):
    """Adversarial flush defense: vote-path flush p99 and recovery-flush
    counts under a sustained signature-poisoning flood at 0 / 0.1% / 1% /
    10% poison rates, measured through the REAL scheduler pipeline
    (provenance tags -> suspicion scorer -> quarantine-lane partition).

    Every call submits an n-row vote-shaped batch through the scheduler's
    VOTES lane with peer provenance; poisoned rows carry a REAL ed25519
    signature over the WRONG bytes (the host precheck passes, the RLC
    combined check fails, recovery runs for real). The defense story the
    numbers tell: the first poisoned flush pays bisection recovery, the
    scorer quarantines the poisoner, and every later flood call is
    partitioned — the poisoner's rows ride the quarantine lane, so the
    vote-path p99 over the whole flood stays at the clean baseline.

    `p99_ratio_1pct` = vote-lane p99 @ 1% poison over the clean p99 (the
    acceptance pins it under 2x). `speedup` = naive recovery wall
    (TMTPU_BISECT=0: whole-batch per-sig fallback) over bisection recovery
    wall for the contaminated flush at 1% — the perf-ledger matrix key."""
    import jax

    from tendermint_tpu.config.config import test_config
    from tendermint_tpu.crypto import batch
    from tendermint_tpu.crypto import provenance as prov
    from tendermint_tpu.crypto import scheduler as sched_mod
    from tendermint_tpu.libs.metrics import batch_metrics

    rates = (0.0, 0.001, 0.01, 0.10)
    pubkeys, msgs, sigs, _types = make_batch(n)
    rng = np.random.default_rng(20)

    def poisoned(rate: float):
        k = int(round(n * rate))
        bad_set = (
            {int(i) for i in rng.choice(n, size=k, replace=False)} if k else set()
        )
        # a REAL signature lifted from the next row: valid encoding, s < L
        # (precheck passes), wrong for this (pubkey, msg) (verify fails)
        psigs = [sigs[(i + 1) % n] if i in bad_set else sigs[i] for i in range(n)]
        srcs = [
            "peer:poisoner" if i in bad_set else f"peer:honest{i % 8}"
            for i in range(n)
        ]
        return psigs, srcs, bad_set

    def counter(m):
        return float(m._values.get((), 0.0))

    def p99(walls):
        if not walls:
            return None
        walls = sorted(walls)
        return walls[min(len(walls) - 1, int(0.99 * len(walls)))]

    cfg = test_config().scheduler
    if jax.default_backend() == "cpu":
        # XLA:CPU kernel compiles run MINUTES on small hosts; the host-RLC
        # path (+ its bisection twin) is this host class's honest fast path
        cfg.backend = "cpu"
    scorer = prov.SuspicionScorer()
    prev_scorer = prov.set_default(scorer)
    batch.configure_verified_memo(0)  # memo hits would hide the flush cost
    prev_bisect = os.environ.get("TMTPU_BISECT")
    sched = sched_mod.VerifyScheduler(cfg)
    bm = batch_metrics()

    def run_arm(rate: float, arm_calls: int):
        scorer.reset()
        psigs, srcs, bad_set = poisoned(rate)
        log_mark = len(sched.flush_log)
        recov0 = counter(bm.recovery_flushes)
        quar0 = counter(bm.quarantined_rows)
        for _ in range(arm_calls):
            mask = sched.verify_rows("votes", pubkeys, msgs, psigs, None, srcs)
            assert all(bool(mask[i]) != (i in bad_set) for i in range(n))
        flushes = list(sched.flush_log)[log_mark:]
        vote_walls = [f["wall_s"] for f in flushes if "votes" in f["rows"]]
        return {
            "poisoned_rows": len(bad_set),
            "vote_flushes": len(vote_walls),
            "vote_wall_p50_ms": round(sorted(vote_walls)[len(vote_walls) // 2] * 1e3, 3),
            "vote_wall_p99_ms": round(p99(vote_walls) * 1e3, 3),
            "vote_wall_max_ms": round(max(vote_walls) * 1e3, 3),
            "quarantine_flushes": sum(1 for f in flushes if "quarantine" in f["rows"]),
            "recovery_flushes": int(counter(bm.recovery_flushes) - recov0),
            "quarantined_rows": int(counter(bm.quarantined_rows) - quar0),
            "quarantined_sources": scorer.stats()["quarantined"],
        }

    try:
        # warm the buckets once so no arm pays first-call compile
        batch.verify_batch(pubkeys, msgs, sigs, backend=cfg.backend or None)
        out_rates = {}
        for rate in rates:
            out_rates[f"{rate:g}"] = run_arm(rate, calls)
        # naive-recovery twin at 1%: same contaminated first flush, straight
        # whole-batch per-sig fallback instead of bisection
        os.environ["TMTPU_BISECT"] = "0"
        naive_1pct = run_arm(0.01, max(4, calls // 16))
    finally:
        if prev_bisect is None:
            os.environ.pop("TMTPU_BISECT", None)
        else:
            os.environ["TMTPU_BISECT"] = prev_bisect
        sched.close()
        batch.configure_verified_memo(batch._memo_env_rows())
        prov.set_default(prev_scorer)

    clean_p99 = out_rates["0"]["vote_wall_p99_ms"]
    one_pct = out_rates["0.01"]
    out = {
        "n": n,
        "calls_per_rate": calls,
        "backend": cfg.backend or "jax",
        "rates": out_rates,
        "naive_1pct": naive_1pct,
        "p99_ratio_1pct": (
            round(one_pct["vote_wall_p99_ms"] / clean_p99, 2) if clean_p99 else None
        ),
        # recovery cost, contaminated flush only: naive per-sig vs bisection
        "speedup": (
            round(naive_1pct["vote_wall_max_ms"] / one_pct["vote_wall_max_ms"], 2)
            if one_pct["vote_wall_max_ms"] else None
        ),
        "quarantine_isolated": all(
            out_rates[k]["quarantined_sources"] == ["peer:poisoner"]
            for k in ("0.001", "0.01", "0.1")
        ),
    }
    log(
        f"[poisoned_flush] clean vote p99 {clean_p99} ms; 1% poison p99 "
        f"{one_pct['vote_wall_p99_ms']} ms (x{out['p99_ratio_1pct']}), recovery "
        f"bisect {one_pct['vote_wall_max_ms']} ms vs naive "
        f"{naive_1pct['vote_wall_max_ms']} ms ({out['speedup']}x)"
    )
    return out


@contextlib.contextmanager
def watchdog(seconds: float):
    """Abort a stage if it stalls: a device call has been observed to
    hang INDEFINITELY (even a tiny jit never returns) — without a watchdog
    one stalled config would hang the whole bench past the driver's
    timeout and lose every completed result. SIGALRM interrupts the
    blocking waits inside the device client; the per-config
    try/except in main() turns the raise into a logged FAILURE and the
    final JSON still prints."""
    import signal

    def _fire(signum, frame):
        try:
            # a stage timeout is exactly when the diagnosis matters: write
            # FORENSICS_*.json (wedged phase from the heartbeat, thread
            # stacks, breaker/device state) before unwinding
            from tendermint_tpu.libs import forensics as _forensics

            _forensics.capture(
                f"bench stage exceeded {seconds:.0f}s watchdog",
                kind="timeout",
            )
        except Exception:
            pass
        raise TimeoutError(f"bench stage exceeded {seconds:.0f}s watchdog")

    prev = signal.signal(signal.SIGALRM, _fire)
    signal.alarm(max(1, int(seconds)))
    try:
        yield
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, prev)


def _configure_caches():
    """Per-process compile-cache configuration: each scenario child (never
    the parent, which imports no jax) applies the package's one cache rule
    before its first compile (ops/aot_cache.configure_compile_cache)."""
    if os.environ.get("TMTPU_BENCH_INPROC") == "1":
        return  # in-proc harness tests: never rewire the host's cache config
    from tendermint_tpu.ops.aot_cache import configure_compile_cache

    configure_compile_cache()


# ---------------------------------------------------------------------------
# Scenario registry. Every scenario runs in its OWN subprocess (scenario
# child) with a per-stage watchdog inside and a hard process-group deadline
# outside, so one stalled device degrades ONE scenario — to
# clearly-marked CPU numbers — instead of costing the whole run its
# datapoint (BENCH_r05 lost round 5 entirely to a device-init stall).

FLEET_GATE_FLOOR_HPS = 0.2  # heights/s a healthy ~10-node CPU fleet must beat


def bench_fleet_soak(
    n_nodes: int = 10, min_heights: int = 12, deadline_s: float = 330.0
):
    """Fleet-gate scenario (ISSUE 17): a scaled-down seeded heterogeneous
    fleet — validators, staged blocksync joiners, light edges — under
    composed chaos, a signed-tx flood, Zipfian light traffic and RPC
    bursts, refereed end-to-end by tools/fleet_referee.py. The ledger's
    fleet-gate column reads verdict/heights/violations straight from this
    blob, and `speedup` = heights_per_sec / FLEET_GATE_FLOOR_HPS so >=1.0
    reads as a pass in the trajectory matrix."""
    import asyncio
    import tempfile

    from tendermint_tpu.chaos.fleet import FleetSpec, run_fleet_soak

    seed = int(os.environ.get("TMTPU_FLEET_SEED", "20260807"))
    spec = FleetSpec.generate(
        seed,
        n_nodes,
        # live BLS votes cost ~0.4 s/verify/node on the pure-python pairing
        # backend — the mixed-key path is proven in tests/test_fleet_soak.py
        bls_validators=0,
        episodes=3,
        min_episode=1.0,
        max_episode=2.5,
        join_window=(3.0, 6.0),
    )
    with tempfile.TemporaryDirectory(prefix="fleet_bench_") as tmp:
        res = asyncio.run(
            run_fleet_soak(spec, tmp, min_heights=min_heights, deadline_s=deadline_s)
        )
    hps = res["heights"] / max(res["elapsed_s"], 1e-9)
    report = res.get("report") or {}
    slo = {
        role: ent["verdict"]
        for role, ent in (report.get("role_slo") or {}).items()
    }
    w = res["workload"]
    return {
        "n_nodes": res["n_nodes"],
        "seed": seed,
        "fingerprint": res["fingerprint"],
        "heights": res["heights"],
        "elapsed_s": res["elapsed_s"],
        "heights_per_sec": round(hps, 3),
        "verdict": res.get("verdict"),
        "safety_violations": res.get("safety_violations", 0),
        "slo_verdicts": slo,
        "sheds": w["light_shed"] + w["rpc_shed"],
        "tx_submitted": w["tx_submitted"],
        "terminals": report.get("terminals") or {},
        "chaos_applied": res["chaos_applied"],
        "speedup": round(hps / FLEET_GATE_FLOOR_HPS, 2),
    }


# (name, pre-check budget s, child deadline s)
_SCENARIO_PLAN = [
    ("batch128", 0.0, 700.0),
    ("verify_commit_1k", 420.0, 700.0),
    ("light_trusting_4k", 420.0, 700.0),
    ("verify_commit_10k", 420.0, 800.0),
    ("verify_commit_100k", 120.0, 700.0),
    ("super_batch", 90.0, 500.0),
    ("streaming", 120.0, 400.0),
    ("fastsync_replay", 240.0, 500.0),
    ("catchup", 90.0, 400.0),
    ("mixed_streaming", 180.0, 450.0),
    ("vote_storm", 120.0, 400.0),
    ("chaos_recovery", 90.0, 300.0),
    ("fleet_soak", 0.0, 420.0),
    ("overload", 90.0, 400.0),
    ("light_serve", 60.0, 300.0),
    ("tx_admission", 120.0, 500.0),
    ("poisoned_flush", 60.0, 400.0),
    ("multichip", 240.0, 700.0),
    ("mesh_failover", 240.0, 700.0),
    ("live_consensus", 240.0, 500.0),
    ("aggregate_verify", 60.0, 500.0),
]

_CONFIG_SIZES = {
    "batch128": (128, None),
    "verify_commit_1k": (1000, None),
    "light_trusting_4k": (4096, 1024),
    "verify_commit_10k": (10000, 1024),
}


def _scenario_fns() -> dict:
    from tendermint_tpu.crypto.batch import RLC_MIN

    fns = {}
    for name, (n, sn) in _CONFIG_SIZES.items():
        fns[name] = (
            lambda name=name, n=n, sn=sn: bench_config(
                name, n, serial_n=sn, rlc=n >= RLC_MIN
            )
        )
    stream_n = int(os.environ.get("TMTPU_BENCH_STREAM_N", "10000"))
    fns["streaming"] = lambda: {
        "n": stream_n,
        "sigs_per_sec": round(bench_streaming(stream_n)),
    }
    fns["verify_commit_100k"] = bench_verify_commit_100k
    fns["super_batch"] = bench_super_batch
    fns["fastsync_replay"] = bench_fastsync_replay
    fns["catchup"] = bench_catchup
    fns["mixed_streaming"] = bench_mixed_streaming
    fns["vote_storm"] = bench_vote_storm
    fns["chaos_recovery"] = bench_chaos_recovery
    fns["fleet_soak"] = bench_fleet_soak
    fns["overload"] = bench_overload
    fns["light_serve"] = bench_light_serve
    fns["tx_admission"] = bench_tx_admission
    fns["poisoned_flush"] = bench_poisoned_flush
    fns["multichip"] = bench_multichip
    fns["mesh_failover"] = bench_mesh_failover
    fns["live_consensus"] = bench_live_consensus
    fns["aggregate_verify"] = bench_aggregate_verify
    # harness self-test scenarios (tests/test_bench_guard.py): cheap,
    # host-only, never in the default plan
    fns["selftest_fast"] = lambda: {"marker": "selftest", "value_ms": 1.0}
    fns["selftest_slow"] = lambda: time.sleep(3600)
    return fns


def _cpu_fallback_fns() -> dict:
    """Clearly-marked CPU fallback measurements, run in a JAX_PLATFORMS=cpu
    + TMTPU_CRYPTO_BACKEND=cpu child when the device scenario failed: small
    host-loop samples, linear extrapolation, ZERO device work or compiles."""

    def config_fallback(name):
        n, _sn = _CONFIG_SIZES[name]
        sn = min(n, 512)
        pubkeys, msgs, sigs, _ = make_batch(sn)
        cpu_s = time_cpu_serial(pubkeys, msgs, sigs) * (n / sn)
        return {
            "n": n,
            "cpu_serial_ms": round(cpu_s * 1e3, 3),
            "tpu_e2e_ms": round(cpu_s * 1e3, 3),  # the host loop IS the path
            "speedup_e2e": 1.0,
            "sample_n": sn,
        }

    def streaming_fallback():
        pubkeys, msgs, sigs, _ = make_batch(512)
        t0 = time.perf_counter()
        from tendermint_tpu.crypto.batch import verify_batch_cpu

        assert verify_batch_cpu(pubkeys, msgs, sigs).all()
        return {"sigs_per_sec": round(512 / (time.perf_counter() - t0))}

    def commit_10k_fallback():
        """ISSUE 18 acceptance datapoint on accelerator-less hosts: a REAL
        10k-row flush through the STRIPED host-RLC path (stripe k+1's
        hashing/scalar prep on the prep pool while stripe k's host MSM
        runs on this thread) vs the same rows with striping off —
        prep_wall_hidden is measured from the flush, not extrapolated.
        On a 1-core host the overlap is time-sliced concurrency, not
        parallel speedup (host_stripe defaults to "auto" = off there);
        the bench forces striping ON for the measurement arm and times
        the serial twin beside it. PERF.md round 10 has the numbers."""
        from tendermint_tpu.crypto import batch as B

        n = 10000
        pubkeys, msgs, sigs, pk_b, msg_b, sig_b = _tiled_batch(n, 2048)
        sn = min(512, len(pk_b))
        cpu_s = time_cpu_serial(pk_b[:sn], msg_b[:sn], sig_b[:sn]) * (n / sn)
        prev_stripe = B._PREP_CFG["host_stripe"]
        best = float("inf")
        det: dict = {}
        try:
            B.configure_prep(host_stripe=True)
            for _ in range(2):
                t0 = time.perf_counter()
                assert B.verify_batch_cpu(pubkeys, msgs, sigs).all()
                dt = time.perf_counter() - t0
                if dt < best:
                    best, det = dt, dict(B.LAST_FLUSH_DETAIL)
            # serial-prep reference arm: identical rows, striping off — the
            # byte-identity twin the prep-pipeline tests pin, timed here so
            # the ledger sees what the overlap arm costs or saves
            B.configure_prep(host_stripe=False)
            t0 = time.perf_counter()
            assert B.verify_batch_cpu(pubkeys, msgs, sigs).all()
            serial_flush_s = time.perf_counter() - t0
        finally:
            B.configure_prep(host_stripe=prev_stripe)
        out = {
            "n": n,
            "tiled_from": len(pk_b),
            "cpu_serial_ms": round(cpu_s * 1e3, 3),
            # the striped host-RLC flush IS this host's production path
            "tpu_e2e_ms": round(best * 1e3, 3),
            "serial_prep_e2e_ms": round(serial_flush_s * 1e3, 3),
            "speedup_e2e": round(cpu_s / best, 2),
            "chunks": det.get("chunks"),
            "chunk_lanes": det.get("chunk_lanes"),
            "host_rlc": bool(det.get("host_rlc")),
        }
        out.update(_prep_hidden_extra(det))
        return out

    fns = {name: (lambda name=name: config_fallback(name)) for name in _CONFIG_SIZES}
    fns["verify_commit_10k"] = commit_10k_fallback
    fns["streaming"] = streaming_fallback
    fns["mixed_streaming"] = streaming_fallback
    fns["fastsync_replay"] = streaming_fallback
    # catchup's real body is backend-agnostic (verify_batch routes to the
    # CPU host-RLC path in the fallback child): smaller sizes, same arms
    fns["catchup"] = lambda: bench_catchup(n_blocks=32, n_vals=128, super_batch=16)
    # the planner scenarios run their real bodies on the chunked host-RLC
    # path (this container's fast path): smaller samples, linear
    # extrapolation marked via sample_n / tiled_from
    fns["verify_commit_100k"] = lambda: bench_verify_commit_100k(
        base=1024, sample=16384, backend=None
    )
    fns["super_batch"] = lambda: bench_super_batch(
        n_blocks=8, n_vals=2048, base_blocks=1, backend=None
    )
    # host-side scenarios run their real body on the CPU backend
    fns["vote_storm"] = lambda: bench_vote_storm(n_vals=256, heights=2)
    fns["overload"] = bench_overload
    # the fleet soak is consensus-bound, not device-bound: the fallback is
    # the same harness at reduced scale, clearly marked by the degraded flag
    fns["fleet_soak"] = lambda: bench_fleet_soak(n_nodes=6, min_heights=8)
    fns["light_serve"] = lambda: bench_light_serve(
        heights=8, n_vals=8, clients=8, requests=120
    )
    # the aggregate path's host twin IS this container's production path;
    # smaller sizes, same arms, clearly marked by the degraded flag
    fns["aggregate_verify"] = lambda: bench_aggregate_verify(
        sizes=(1000, 10000), persig_sample=2
    )
    # no mesh exists in the degraded child: measure the ladder's bottom
    # rung (chunked host-RLC) instead, clearly stamped mesh_ladder=host
    fns["mesh_failover"] = bench_mesh_failover_host
    # the poisoning defense is backend-agnostic (host-RLC bisection twin):
    # same arms at reduced scale, clearly marked by the degraded flag
    fns["poisoned_flush"] = lambda: bench_poisoned_flush(n=512, calls=112)
    return fns


def _apply_bench_fault(name: str) -> None:
    """Deterministic fault hook for harness tests (and chaos drills):
    TMTPU_BENCH_FAULT="<scenario>[:raise|:hang]" makes THAT scenario's
    device child fail the way a sick device does."""
    spec = os.environ.get("TMTPU_BENCH_FAULT", "")
    if not spec:
        return
    target, _, mode = spec.partition(":")
    if target != name:
        return
    if (mode or "raise") == "hang":
        time.sleep(3600)
    raise RuntimeError(f"injected bench fault for scenario {name!r}")


def scenario_main(name: str) -> None:
    """Scenario-child entry: run ONE scenario, print ONE JSON line
    ({"scenario", "ok", "result"|"error", "degraded"}), never hang past the
    in-process watchdogs (the parent's process-group deadline covers hard
    hangs)."""
    from tendermint_tpu.libs import forensics as _forensics
    from tendermint_tpu.libs import trace as _trace

    degraded = os.environ.get("TMTPU_BENCH_DEGRADED") == "1"
    out = {"scenario": name, "degraded": degraded, "host": _host_stamp()}
    budget = float(os.environ.get("TMTPU_BENCH_SCENARIO_BUDGET_S", "600"))
    # Stall forensics: heartbeat the device entry points + arm a watchdog
    # THREAD that fires before the parent's hard process-group kill — a hard
    # hang (SIGALRM unserviced, the BENCH_r05 mode) still leaves a
    # FORENSICS_*.json naming the wedged phase for the parent to attach.
    try:
        # fallback is the forensics runtime dir, NEVER the cwd: an unset
        # TMTPU_FORENSICS_DIR used to open heartbeat_<pid>.bin rings in the
        # repo root (the ISSUE 10 strays), bypassing the PR 8 dir resolution
        _forensics.configure(
            os.environ.get("TMTPU_FORENSICS_DIR") or _forensics.DEFAULT_DIR
        )
        _forensics.install_signal_handler()
    except Exception:
        pass
    # budget is parent deadline minus 90 (_run_scenario_child), so +45 still
    # fires 45 s BEFORE the parent's hard process-group kill — device init
    # shares the window, it has no extra allowance here
    hard_wd = _forensics.Watchdog(
        budget + 45.0,
        f"bench scenario {name!r} wedged past its {budget:.0f}s budget",
        extra={"scenario": name},
    ).start()
    try:
        # The cross-flush verified-row memo (ISSUE 18) would turn every
        # repeat iteration of a timed loop into a host-side dict lookup —
        # iteration 2+ of time_rlc/super_batch would measure nothing.
        # Benchmarks always measure the real flush path.
        from tendermint_tpu.crypto.batch import configure_verified_memo

        configure_verified_memo(0)
        import jax

        t_init = time.perf_counter()
        with watchdog(180.0):
            _configure_caches()
            if not degraded:
                _apply_bench_fault(name)
            log(f"[{name}] devices:", jax.devices())
            _trace.record_device_init(time.perf_counter() - t_init, ok=True)
        fns = _cpu_fallback_fns() if degraded else _scenario_fns()
        if degraded and name not in fns:
            out["ok"] = True
            out["result"] = {"note": "no CPU fallback measurement for this scenario"}
        else:
            with watchdog(budget):
                out["result"] = fns[name]()
            out["ok"] = True
    except BaseException as e:  # noqa: BLE001 — the child must still report
        out["ok"] = False
        out["error"] = f"{type(e).__name__}: {e}"
    hard_wd.cancel()
    out["flight"] = _flight_recorder_extra()
    print(json.dumps(out), flush=True)


def _parse_scenario_json(out: str, name: str):
    for line in reversed(out.strip().splitlines()):
        try:
            rep = json.loads(line)
        except ValueError:
            continue
        if isinstance(rep, dict) and rep.get("scenario") == name:
            return rep
    return None


def _forensics_for_kill(t_child_start: float) -> dict:
    """Attach a killed scenario child's stall diagnosis to the parent's
    report: FORENSICS_*.json files written since the child started (by its
    in-child watchdog thread or the parent's SIGUSR1 request), plus the
    wedged phase named by the newest one — so a hard-deadline kill reports
    WHICH device phase wedged instead of a bare timeout."""
    from tendermint_tpu.libs import forensics as _forensics

    out: dict = {}
    try:
        # must mirror scenario_main's configure fallback (the runtime dir,
        # not cwd) or the parent reads an empty directory
        d = os.environ.get("TMTPU_FORENSICS_DIR") or _forensics.DEFAULT_DIR
        # small rewind: the capture's mtime can predate communicate()'s
        # timeout bookkeeping by the watchdog margin
        paths = _forensics.find_captures(d, since_ts=t_child_start - 1.0)
    except Exception:
        return out
    if not paths:
        return out
    out["forensics"] = paths
    try:
        with open(paths[-1]) as f:
            doc = json.load(f)
        out["wedged_phase"] = doc.get("wedged_phase")
        out["forensics_kind"] = doc.get("kind")
    except (OSError, ValueError):
        pass
    return out


def _run_scenario_child(name: str, deadline_s: float, degraded: bool = False,
                        stream_n: int | None = None) -> dict:
    """Run one scenario in an isolated subprocess (own process GROUP — jax
    helper processes inherit the stdout pipe, so the whole group dies on
    timeout) and return its report dict."""
    import signal as _signal
    import subprocess

    if not degraded and os.environ.get("TMTPU_BENCH_NO_DEVICE") == "1":
        # accelerator-less host, declared up front: skip the doomed device
        # child (XLA:CPU pays multi-minute compiles per shape just to time
        # out) and let the caller degrade straight to the clearly-marked
        # CPU fallback — every scenario still lands a parseable datapoint
        return {
            "scenario": name,
            "ok": False,
            "error": "device attempt skipped (TMTPU_BENCH_NO_DEVICE=1)",
        }

    if os.environ.get("TMTPU_BENCH_INPROC") == "1":
        # test/debug escape hatch: no isolation, same protocol
        import contextlib
        import io

        buf = io.StringIO()
        os.environ["TMTPU_BENCH_SCENARIO_BUDGET_S"] = str(max(30, int(deadline_s - 30)))
        with contextlib.redirect_stdout(buf):
            prev = os.environ.get("TMTPU_BENCH_DEGRADED")
            if degraded:
                os.environ["TMTPU_BENCH_DEGRADED"] = "1"
            try:
                scenario_main(name)
            finally:
                if degraded:
                    if prev is None:
                        os.environ.pop("TMTPU_BENCH_DEGRADED", None)
                    else:
                        os.environ["TMTPU_BENCH_DEGRADED"] = prev
        rep = _parse_scenario_json(buf.getvalue(), name)
        return rep or {"scenario": name, "ok": False, "error": "no JSON from in-proc run"}

    env = dict(os.environ, TMTPU_BENCH_SCENARIO=name)
    env["TMTPU_BENCH_SCENARIO_BUDGET_S"] = str(max(60, int(deadline_s - 90)))
    if name in ("multichip", "mesh_failover"):
        # the sharded arm needs a mesh: on hosts without 8 real chips, 8
        # VIRTUAL CPU devices (flag only affects the CPU platform — a real
        # TPU host's devices win). Must land BEFORE the child imports jax.
        flags = env.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            env["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8"
            ).strip()
    if stream_n is not None:
        env["TMTPU_BENCH_STREAM_N"] = str(stream_n)
    if degraded:
        # the CPU-fallback child must never touch the (failing) device
        env.update(
            TMTPU_BENCH_DEGRADED="1",
            JAX_PLATFORMS="cpu",
            TMTPU_CRYPTO_BACKEND="cpu",
            TMTPU_SHARDED="0",
        )
    t_child_start = time.time()
    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__)],
        env=env,
        stdout=subprocess.PIPE,
        start_new_session=True,
    )
    try:
        raw, _ = proc.communicate(timeout=deadline_s)
    except subprocess.TimeoutExpired:
        # last-chance diagnosis request before the kill: SIGUSR1 triggers
        # the child's forensics dump IF its interpreter still runs Python
        # (the in-child watchdog thread covers the hard-hang case)
        try:
            os.killpg(proc.pid, _signal.SIGUSR1)
            # grace must exceed the signal capture's worst case (stack dump
            # + fingerprint + JSON write; it skips the 2 s device probe)
            time.sleep(3.0)
        except (OSError, AttributeError):
            pass
        try:
            os.killpg(proc.pid, _signal.SIGKILL)
        except OSError:
            pass
        try:
            raw, _ = proc.communicate(timeout=30.0)
        except Exception:
            raw = b""
        rep = _parse_scenario_json(raw.decode(errors="replace"), name)
        if rep is not None:
            return rep  # printed its result, then hung in teardown
        rep = {
            "scenario": name,
            "ok": False,
            "error": f"scenario child exceeded {deadline_s:.0f}s hard deadline",
        }
        rep.update(_forensics_for_kill(t_child_start))
        return rep
    rep = _parse_scenario_json(raw.decode(errors="replace"), name)
    if rep is None:
        return {
            "scenario": name,
            "ok": False,
            "error": f"scenario child exited rc={proc.returncode} with no JSON",
        }
    return rep


def _headline_scenario():
    """The config whose latency is the round's headline metric. ONE source
    of truth with the ledger's headline-missing flag
    (tendermint_tpu/tools/perf_ledger.HEADLINE_SCENARIO) — two independent
    notions of 'the headline' would re-open the silent-gap failure this
    exists to close. Falls back to the largest _CONFIG_SIZES entry when
    the registry doesn't carry the production headline (harness tests
    monkeypatch _CONFIG_SIZES)."""
    try:
        from tendermint_tpu.tools.perf_ledger import HEADLINE_SCENARIO

        if HEADLINE_SCENARIO in _CONFIG_SIZES:
            return HEADLINE_SCENARIO
    except Exception:
        pass
    names = list(_CONFIG_SIZES)
    return names[-1] if names else None


def _plan() -> list:
    names = os.environ.get("TMTPU_BENCH_SCENARIOS")
    if not names:
        return list(_SCENARIO_PLAN)
    by_name = {n: (n, need, dl) for n, need, dl in _SCENARIO_PLAN}
    plan = [by_name.get(n, (n, 0.0, 120.0)) for n in names.split(",") if n]
    # The HEADLINE config rides EVERY plan: BENCH_r06 was a catchup-scoped
    # round that silently lost the verify_commit_10k trajectory point; a
    # scenario-scoped override now prepends the headline instead of
    # dropping it (tools/perf_ledger.py flags any round that still lacks
    # it — belt and braces).
    head = _headline_scenario()
    if head is not None and not any(p[0] == head for p in plan):
        plan.insert(0, by_name.get(head, (head, 0.0, 800.0)))
    return plan


def main():
    """Per-scenario-isolated, time-budgeted bench: every scenario in the
    plan emits a parseable datapoint — a device result, a clearly-marked
    CPU-fallback result, or a structured error — and the final JSON ALWAYS
    prints with the largest completed config as the headline. Budget via
    TMTPU_BENCH_BUDGET_S; plan override via TMTPU_BENCH_SCENARIOS."""
    budget = float(os.environ.get("TMTPU_BENCH_BUDGET_S", "1500"))
    t_start = time.perf_counter()

    def remaining():
        return budget - (time.perf_counter() - t_start)

    extra = {}
    head = None
    head_flight = None
    stream_n = None
    for name, need, deadline in _plan():
        is_config = name in _CONFIG_SIZES
        if (need and remaining() < need) or remaining() < 90:
            log(f"[{name}] skipped: {remaining():.0f}s left < {need:.0f}s budget")
            extra[name] = {"skipped": f"budget ({remaining():.0f}s left)"}
            continue
        # leave room for a CPU fallback + the final JSON inside the hard
        # deadline even if this child burns its whole allowance
        deadline = min(deadline, max(90.0, remaining() - 120.0))
        rep = _run_scenario_child(name, deadline, stream_n=stream_n)
        if not rep.get("ok"):
            # transient device/compile errors: retry the device child once
            # before degrading to CPU numbers. A stall is NOT transient —
            # retrying a dead device just burns the other scenarios' budget.
            err0 = rep.get("error", "")
            stalled = "hard deadline" in err0 or "TimeoutError" in err0
            if not stalled and remaining() > max(need, 150.0):
                log(f"[{name}] attempt 1 FAILED ({err0}); retrying once")
                deadline = min(deadline, max(90.0, remaining() - 120.0))
                rep = _run_scenario_child(name, deadline, stream_n=stream_n)
        if rep.get("ok"):
            res = rep.get("result", {})
            extra[name] = res
            if is_config:
                head = (name, res)
                head_flight = rep.get("flight")
                stream_n = res.get("n", stream_n)
            log(f"[{name}] ok")
            continue
        # device scenario failed: one CPU-fallback attempt so the round
        # still gets a clearly-marked datapoint for this scenario
        err = rep.get("error", "unknown failure")
        if remaining() > 60:
            log(f"[{name}] FAILED ({err}); attempting CPU fallback")
            fb = _run_scenario_child(
                name, max(60.0, min(300.0, remaining() - 30.0)), degraded=True
            )
        else:
            fb = {"ok": False, "error": "no budget left for CPU fallback"}
        res = fb.get("result") if fb.get("ok") else {"error": fb.get("error")}
        res = dict(res or {})
        res["degraded"] = "cpu-fallback"
        res["degrade_reason"] = err
        extra[name] = res

    # headline: the largest config with a real (non-degraded) device result
    head_degraded = False
    if head is None:
        for name in reversed(list(_CONFIG_SIZES)):
            res = extra.get(name)
            if isinstance(res, dict) and "tpu_e2e_ms" in res:
                head = (name, res)
                # a CPU-fallback headline must be marked at the TOP level
                # too: its "latency" is the host loop, and a consumer
                # tracking metric/value across rounds must never record it
                # as a device datapoint
                head_degraded = res.get("degraded") == "cpu-fallback"
                break
    if head is None:
        # no headline — but every scenario's datapoint still ships
        _emit_fallback("no config completed", extra)
        return
    name, res = head
    if isinstance(head_flight, dict):
        extra.update(head_flight)
    else:
        extra.update(_flight_recorder_extra())
    if "streaming" in extra and isinstance(extra["streaming"], dict):
        sps = extra["streaming"].get("sigs_per_sec")
        sn = extra["streaming"].get("n")
        if sps is not None and sn is not None:
            extra[f"streaming_{sn}_sigs_per_sec"] = sps
    extra["host"] = _host_stamp()
    rep = {
        "metric": f"{name}_latency",
        "value": res["tpu_e2e_ms"],
        "unit": "ms",
        "vs_baseline": res.get("speedup_e2e", 0),
        "extra": extra,
    }
    if head_degraded:
        rep["degraded"] = "cpu-fallback"
        rep["degrade_reason"] = res.get("degrade_reason")
    print(json.dumps(rep))


def _flight_recorder_extra() -> dict:
    """The per-stage breakdown attached to every result's `extra` (see the
    module docstring / --help): future BENCH_r*.json files localise a
    regression to prep vs compile vs transfer vs path choice instead of
    reporting one opaque latency."""
    out = {}
    try:
        from tendermint_tpu.libs import trace as _trace

        stats = _trace.verify_stats()
        device = stats.pop("device", None)
        out["verify_stats"] = stats
        out["device_health"] = device
    except Exception as e:  # never lose the bench result to telemetry
        out["verify_stats"] = {"error": repr(e)}
    try:  # independent of the trace read above — a tracer failure must not
        # also cost the chain-side snapshot
        from tendermint_tpu.libs.metrics import NodeMetrics

        nm = NodeMetrics.latest()
        out["node_metrics"] = nm.snapshot() if nm is not None else None
    except Exception as e:
        out["node_metrics"] = {"error": repr(e)}
    return out


def _emit_fallback(err: str, scenario_extra: dict | None = None) -> None:
    extra = dict(scenario_extra or {})
    extra["error"] = err
    extra.update(_flight_recorder_extra())
    try:  # a lost datapoint still names the host it was lost on
        extra["host"] = _host_stamp()
    except Exception:
        pass
    print(json.dumps({"metric": "verify_commit_latency", "value": -1,
                      "unit": "ms", "vs_baseline": 0, "extra": extra}))


def _salvage_json(out: str) -> bool:
    """Forward the LAST parseable JSON line from child output, if any — a
    child can print its complete result and THEN crash or hang in teardown
    (the device client's threads); that result must not be lost."""
    for line in reversed(out.strip().splitlines()):
        try:
            json.loads(line)
        except ValueError:
            continue
        print(line)
        return True
    return False


def _profile_main(name: str, base_dir: str | None = None, top: int = 25) -> int:
    """`bench.py --profile <scenario>`: run ONE scenario in-process inside a
    device profiler capture (libs/profiler.py) and render the per-stage /
    per-kernel attribution table (tools/profile_report.py) on stdout — the
    PERF.md round-4 afternoon of perfetto spelunking as one command. This is
    an interactive attribution tool, not a datapoint emitter: the one-JSON-
    line contract does not apply, and nothing here runs under the scenario
    watchdogs (a profile of a wedge is best taken with --profile + ctrl-C
    anyway, the partial capture survives in the run dir)."""
    from tendermint_tpu.libs import profiler
    from tendermint_tpu.tools import profile_report

    _configure_caches()
    fns = _scenario_fns()
    if name not in fns:
        log(f"--profile: unknown scenario {name!r}; choose from: "
            + ", ".join(sorted(fns)))
        return 2
    import jax

    log(f"[profile:{name}] devices: {jax.devices()}")
    info = profiler.start(base_dir)
    log(f"[profile:{name}] capturing into {info['dir']}")
    try:
        result = fns[name]()
    finally:
        cap = profiler.stop()
    log(f"[profile:{name}] {len(cap['artifacts'])} artifact(s), "
        f"{cap['duration_s']}s captured")
    rep = profile_report.report(cap["dir"], top=top)
    rep["scenario"] = {"name": name, "result": result, "host": _host_stamp()}
    sys.stdout.write(profile_report.render_markdown(rep))
    print(f"\ncapture dir: {cap['dir']}")
    return 0


def guarded_main():
    """Run main() in a CHILD process under a hard deadline, so stdout gets
    exactly one JSON line even when the device hangs in a way no
    in-process watchdog can interrupt (observed: jax.devices() blocks in C
    without servicing SIGALRM). The per-stage watchdogs inside main() still
    salvage partial results from soft stalls; this parent guard covers the
    hard ones. The child runs in its own process GROUP and the whole group
    is killed on timeout — jax helper processes inherit the stdout pipe,
    and killing only the direct child would leave the parent blocked on
    pipe EOF forever."""
    import signal as _signal
    import subprocess

    scen = os.environ.get("TMTPU_BENCH_SCENARIO")
    if scen:
        scenario_main(scen)  # scenario grandchild (also sees BENCH_CHILD=1)
        return
    if os.environ.get("TMTPU_BENCH_CHILD") == "1":
        main()
        return
    budget = float(os.environ.get("TMTPU_BENCH_BUDGET_S", "1500"))
    margin = float(os.environ.get("TMTPU_BENCH_HARD_MARGIN_S", "180"))
    env = dict(os.environ, TMTPU_BENCH_CHILD="1")
    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__)],
        env=env,
        stdout=subprocess.PIPE,
        start_new_session=True,
    )
    try:
        out, _ = proc.communicate(timeout=budget + margin)
        out = out.decode()
        if _salvage_json(out):
            return  # child's result forwarded, even if its rc != 0
        _emit_fallback(f"bench child exited rc={proc.returncode} with no JSON")
    except subprocess.TimeoutExpired:
        try:
            os.killpg(proc.pid, _signal.SIGKILL)
        except OSError:
            pass
        try:
            out, _ = proc.communicate(timeout=30.0)
            if _salvage_json(out.decode()):
                return  # result printed before the hang: keep it
        except Exception:
            pass
        _emit_fallback("bench child exceeded hard deadline (device hung?)")


if __name__ == "__main__":
    import argparse

    # --help carries the full module docstring, including the per-stage
    # `extra.verify_stats` / `extra.device_health` breakdown contract.
    # parse_known_args: unknown argv must not exit(2) before the one-JSON-
    # line contract (guarded_main/_emit_fallback) can be honored.
    _ap = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    _ap.add_argument(
        "--profile", metavar="SCENARIO",
        help="run ONE scenario inside a device profiler capture and print "
             "the per-stage attribution table (tools/profile_report.py) "
             "instead of the bench JSON line",
    )
    _ap.add_argument(
        "--profile-dir", metavar="DIR",
        help="capture base directory (default: tmtpu_profiles under tmp)",
    )
    _ap.add_argument(
        "--profile-top", type=int, default=25, metavar="N",
        help="top-N ops in the --profile table (default 25)",
    )
    _args, _ = _ap.parse_known_args()
    if _args.profile:
        raise SystemExit(
            _profile_main(_args.profile, _args.profile_dir, _args.profile_top)
        )
    guarded_main()
