#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the main path still starts on the
chip: a 10,000-validator commit from vote-add to VerifyCommit, on one TPU,
through the library's normal entry points, checked row by row against the
serial host loop.

    python chip_smoke.py            # one chip (what the driver runs)
    python chip_smoke.py --chips 4  # the sharded route only, on a 4-chip host

One process, no child that needs the chip, no network. It fails (exit != 0,
no result line) when JAX finds no TPU, when any phase's verdict differs from
the reference, when a flush ran anywhere but where it claims (host fallback,
unfused retry, per-signature fallback on a valid batch, open breaker, Python
prep), or when anything under the `tendermint_tpu` logger warns during the
device phases. Every line before the last is a smoke reading of a SINGLE
run, not a metric; the last line is the fixed result object.

Compiled programs bound the run (each is minutes cold, see PERF.md):
  steps 2-3  every valid 10,000-row flush is verify_batch's chunk-bucket
             flush (path rlc-pipelined): one chunk on the planner's one
             chunk bucket (rlc_partial_f @ 24,576 lanes + partial_ident).
             verify_commit_light / _light_trusting run inside
             crypto.batch.accumulate_flushes(), the scope light/service.py
             runs them in, so their rows take that same route; outside a
             scope they take the submit/finish pair, two more whole-flush
             programs at 10k rows (tools/compile_rehearsal.py `async`).
  step 4     a tampered 10k commit walks _bisect_recover over sub-ranges
             on other lane buckets, each a new program; so step 4 runs on
             its own N_TAMPER-validator set (>= RLC_MIN, < 2*RLC_MIN): one
             combined check (rlc_plain_f, then rlc_cached_f once the first
             flush has decoded the keys), then straight to the
             per-signature leaf (persig @ 1,024) — the RLC path and its
             recovery both run, on three more programs instead of six.
"""

from __future__ import annotations

import argparse
import json
import logging
import sys
import time
from fractions import Fraction

N_VALIDATORS = 10_000
N_TAMPER = 640  # step 4's set: RLC_MIN <= n < 2*RLC_MIN (see docstring)
CHAIN_ID = "chip-smoke"
HEIGHT = 5


def emit(**obj) -> None:
    print(json.dumps(obj), flush=True)


def check(cond, what: str) -> None:
    if not cond:
        raise AssertionError(what)


def require_tpu(chips: int) -> dict:
    """First thing, before any crypto work: the device JAX picked."""
    import jax

    devs = jax.devices()
    dev = {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
    }
    if dev["platform"] != "tpu":
        sys.exit(f"chip_smoke: JAX picked platform {dev['platform']!r}, not a TPU")
    if dev["count"] != chips:
        sys.exit(
            f"chip_smoke: {dev['count']} device(s) visible, this run needs "
            f"{chips} (--chips)"
        )
    return dev


class _Alarm(logging.Handler):
    """Any WARNING+ under `tendermint_tpu` during the device phases fails
    the run: fused disabled, RLC fell back, flush degraded, AOT export
    failed, native build failed all log there — one net over every rung of
    the degrade ladder, without touching the ladder."""

    def __init__(self):
        super().__init__(level=logging.WARNING)
        self.records: list = []

    def emit(self, record):
        self.records.append(f"{record.name}: {record.getMessage()}")


ALARM = _Alarm()


# ---------------------------------------------------------------- the data


def make_signed_set(seed: int, n: int, chain_id: str, block_id):
    """n ed25519 validators and their n signed precommits for block_id,
    from `seed`. Returns (ValidatorSet, votes in validator order)."""
    import numpy as np

    from tendermint_tpu.crypto.keys import gen_ed25519
    from tendermint_tpu.types.basic import SignedMsgType
    from tendermint_tpu.types.validator_set import Validator, ValidatorSet
    from tendermint_tpu.types.vote import Vote

    rng = np.random.default_rng(seed)
    key_seeds = rng.integers(0, 256, (n, 32), dtype=np.uint8)
    stamps = rng.integers(1, 10**9, n)
    privs = [gen_ed25519(key_seeds[i].tobytes()) for i in range(n)]
    vals = ValidatorSet([Validator(p.pub_key(), 10) for p in privs])
    by_addr = {p.pub_key().address(): p for p in privs}
    votes = []
    for i, val in enumerate(vals.validators):
        v = Vote(
            type=SignedMsgType.PRECOMMIT,
            height=HEIGHT,
            round=0,
            block_id=block_id,
            timestamp_ns=1_700_000_000_000_000_000 + int(stamps[i]),
            validator_address=val.address,
            validator_index=i,
        )
        votes.append(v.with_signature(by_addr[val.address].sign(v.sign_bytes(chain_id))))
    return vals, votes


def commit_from_votes(votes, sigs, block_id):
    """The commit these precommits make, carrying `sigs` (a copy of a valid
    commit with some signature replaced, when sigs differ from the votes')."""
    from tendermint_tpu.types.basic import BlockIDFlag
    from tendermint_tpu.types.block import Commit, CommitSig

    return Commit(
        HEIGHT,
        0,
        block_id,
        [
            CommitSig(BlockIDFlag.COMMIT, v.validator_address, v.timestamp_ns, sig)
            for v, sig in zip(votes, sigs)
        ],
    )


def rows_of(vals, votes):
    """(pubkeys, msgs) as verify_batch takes them, in validator order."""
    return (
        [val.pub_key.bytes() for val in vals.validators],
        [v.sign_bytes(CHAIN_ID) for v in votes],
    )


def flip_one(sigs, i: int) -> list:
    """A copy of sigs with one bit of signature i flipped."""
    out = list(sigs)
    out[i] = out[i][:7] + bytes([out[i][7] ^ 0x20]) + out[i][8:]
    return out


def reference_mask(vals, votes, sigs):
    """The plain reference: the serial host loop, one pub_key.verify per row
    through crypto/keys.py (OpenSSL), over per-vote sign bytes — no batch
    code, no device."""
    import numpy as np

    return np.array(
        [
            val.pub_key.verify(v.sign_bytes(CHAIN_ID), sig)
            for val, v, sig in zip(vals.validators, votes, sigs)
        ],
        dtype=bool,
    )


# ------------------------------------------------------- reading a flush


def flush_reading(phase: str, wall_s: float) -> dict:
    """What the last flush says about itself, from the surfaces that exist:
    the flight recorder's last_flush, LAST_JAX_PATH, LAST_FLUSH_DETAIL,
    LAST_RLC_TIMINGS."""
    from tendermint_tpu.crypto import batch
    from tendermint_tpu.libs import trace

    last = trace.verify_stats()["last_flush"]
    if last.get("backend") == "memo":  # no device rows: nothing else applies
        return {"smoke": "flush", "phase": phase, "rows": last.get("n"),
                "backend": "memo", "path": last.get("path"),
                "memo_hits": last.get("memo_hits")}
    return {
        "smoke": "flush",
        "phase": phase,
        "rows": last.get("n"),
        "backend": last.get("backend"),
        "path": last.get("path"),
        "jax_path": batch.LAST_JAX_PATH[0],
        "mode": batch.LAST_RLC_TIMINGS.get("mode"),
        "lane_bucket": last.get("jit_bucket"),
        "chunks": last.get("chunks"),
        "fused": batch.LAST_FLUSH_DETAIL.get("fused"),
        "rlc_fallback": bool(last.get("rlc_fallback", False)),
        "recovery_flushes": last.get("recovery_flushes"),
        "compile_s_inside": round(last.get("compile_ms", 0.0) / 1e3, 3),
        "wall_s_single_run": round(wall_s, 4),
    }


def check_device_flush(r: dict, rows: int, paths, fallback: bool,
                       fused_reported: bool = True) -> None:
    """The flush ran where it claims: on the device, fused, on an expected
    path, with (tampered) or without (valid) the exact-recovery ladder. The
    sharded route reports no per-flush fused flag (fused_reported=False);
    the sticky disable is checked either way."""
    from tendermint_tpu.crypto import batch
    from tendermint_tpu.ops import msm_jax

    emit(**r)
    ph = r["phase"]
    check(not ALARM.records, f"{ph}: tendermint_tpu warned: {ALARM.records}")
    check(r["backend"] == "jax", f"{ph}: backend {r['backend']!r}, not jax")
    check(r["path"] in paths and r["jax_path"] == r["path"],
          f"{ph}: path {r['path']!r}/{r['jax_path']!r} not in {sorted(paths)}")
    check(r["rows"] == rows, f"{ph}: flushed {r['rows']} rows, not {rows}")
    check(r["fused"] is True or not fused_reported,
          f"{ph}: the flush did not build the fused pipeline")
    check(msm_jax._FUSED_DISABLED[0] is None,
          f"{ph}: fused pipeline disabled: {msm_jax._FUSED_DISABLED[0]}")
    check(r["rlc_fallback"] is fallback,
          f"{ph}: rlc_fallback is {r['rlc_fallback']}, expected {fallback}")
    snap = batch.BREAKER.snapshot()
    check(snap["state"] == "closed" and snap["consecutive_failures"] == 0
          and not any(snap["trips"].values()),
          f"{ph}: breaker {snap['state']}, failures "
          f"{snap['consecutive_failures']}, trips {snap['trips']}")


class CompileLog:
    """Per compiled program its name and seconds (libs/trace.record_compile
    emits aot.export / aot.first_call / aot.deserialize events from
    ops/aot_cache.py), plus JAX's own trace / lower / backend-compile
    seconds per phase and in total — which also see the programs that do
    not go through the AOT cache (decompress_rows, the sharded route)."""

    def __init__(self):
        import jax.monitoring

        self._seen = 0.0
        self.programs = 0
        self.aot_seconds = 0.0
        self.backend_compiles = 0
        # JAX's own split of every jit's cold cost: trace, lower, compile
        self.jax_seconds = {"jaxpr_trace": 0.0, "jaxpr_to_mlir_module": 0.0,
                            "backend_compile": 0.0}
        self._drained = dict(self.jax_seconds)
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)

    def _on_duration(self, event: str, secs: float, **_kw) -> None:
        stage = event.rsplit("/", 1)[-1].removesuffix("_duration")
        if stage in self.jax_seconds:
            self.jax_seconds[stage] += secs
            self.backend_compiles += stage == "backend_compile"

    def drain(self, phase: str) -> None:
        from tendermint_tpu.libs import trace

        per: dict = {}
        for e in trace.tracer.dump():
            if e["name"].startswith("aot.") and e["ts"] > self._seen:
                self._seen = e["ts"]
                a = e["attrs"]
                per.setdefault(a["kernel"], {})[e["name"][4:] + "_s"] = a["seconds"]
                self.aot_seconds += a["seconds"]
        for kernel, secs in per.items():
            self.programs += 1
            emit(smoke="compile", phase=phase, program=kernel, **secs)
        delta = {k: round(v - self._drained[k], 1) for k, v in self.jax_seconds.items()}
        self._drained = dict(self.jax_seconds)
        if any(delta.values()):  # this phase's share of JAX's trace/lower/compile
            emit(smoke="jax_cold", phase=phase, **{f"{k}_s": v for k, v in delta.items()})

    def summary(self) -> None:
        emit(
            smoke="compile_total",
            aot_programs=self.programs,
            aot_seconds_single_run=round(self.aot_seconds, 1),
            jax_backend_compiles=self.backend_compiles,
            **{f"jax_{k}_seconds": round(v, 1) for k, v in self.jax_seconds.items()},
        )


# ------------------------------------------------------------- one chip


def run_one_chip(seed: int, compiles: CompileLog) -> None:
    import numpy as np

    from tendermint_tpu.crypto import batch
    from tendermint_tpu.types.basic import BlockID, PartSetHeader, SignedMsgType
    from tendermint_tpu.types.validator_set import CommitVerifyError
    from tendermint_tpu.types.vote_set import VoteSet

    n = N_VALIDATORS
    valid_paths = {"rlc", "rlc-pipelined"}
    rng = np.random.default_rng(seed)
    block_id = BlockID(
        rng.bytes(32), PartSetHeader(int(rng.integers(1, 64)), rng.bytes(32))
    )

    # step 1: the data, from the seed (host signing)
    t0 = time.perf_counter()
    vals, votes = make_signed_set(seed, n, CHAIN_ID, block_id)
    sigs = [v.signature for v in votes]
    ref = reference_mask(vals, votes, sigs)
    check(ref.all(), "step 1: the reference rejects a freshly signed vote")
    emit(smoke="data", validators=n, seed=seed,
         build_and_reference_s=round(time.perf_counter() - t0, 2))

    # step 2: vote-add, ONE deferred device flush, commit
    vote_set = VoteSet(CHAIN_ID, HEIGHT, 0, SignedMsgType.PRECOMMIT, vals,
                       defer_verification=True)
    for v in votes:
        check(vote_set.add_vote(v) == "pending", "step 2: add_vote did not defer")
    t0 = time.perf_counter()
    committed, failed = vote_set.flush()
    wall = time.perf_counter() - t0
    compiles.drain("step2.vote_flush")
    check_device_flush(flush_reading("step2.vote_flush", wall), n, valid_paths, False)
    check(sorted(failed) == list(np.flatnonzero(~ref)) and len(committed) == int(ref.sum()),
          f"step 2: flush failed rows {failed[:8]}, the reference fails none")
    check(vote_set.has_two_thirds_majority(), "step 2: no +2/3 after the flush")
    commit = vote_set.make_commit()
    check(commit.size() == n and all(cs.for_block() for cs in commit.signatures),
          "step 2: the commit does not carry every precommit")

    # step 3a: what a node does next — the commit of just-flushed votes
    # answers from the verified-row memo, with no device rows at all
    hits0 = batch.verified_memo_stats()["hits"]
    vals.verify_commit(CHAIN_ID, block_id, HEIGHT, commit)
    r = flush_reading("step3a.verify_commit_memo", 0.0)
    emit(**r)
    check(r["path"] == "memo" and r.get("memo_hits") == n
          and batch.verified_memo_stats()["hits"] - hits0 == n,
          f"step 3a: expected {n} memo hits, got {r}")

    # step 3b: memo off ([crypto] verified_memo_rows = 0) — the three
    # Verify* calls and a bare verify_batch on the device, one flush shape
    batch.configure_verified_memo(0)
    pubkeys, msgs = rows_of(vals, votes)

    def in_scope(begin):
        # light/service.py's shape: submit inside the scope, one flush, finish
        with batch.accumulate_flushes() as acc:
            finish = begin()
        acc.flush()
        finish()

    def light():
        in_scope(lambda: vals.begin_verify_commit_light(
            CHAIN_ID, block_id, HEIGHT, commit))

    def light_trusting():
        in_scope(lambda: vals.begin_verify_commit_light_trusting(
            CHAIN_ID, commit, Fraction(1, 3)))

    masks = {}
    for name, call in [
        ("verify_commit", lambda: vals.verify_commit(CHAIN_ID, block_id, HEIGHT, commit)),
        ("verify_commit_light", light),
        ("verify_commit_light_trusting", light_trusting),
        ("verify_batch", lambda: masks.update(m=batch.verify_batch(pubkeys, msgs, sigs))),
    ]:
        t0 = time.perf_counter()
        call()  # raises on a verdict that differs from the all-valid reference
        wall = time.perf_counter() - t0
        compiles.drain(f"step3b.{name}")
        check_device_flush(flush_reading(f"step3b.{name}", wall), n, valid_paths, False)
    check(np.array_equal(masks["m"], ref), "step 3b: verify_batch mask != reference")

    # step 4: reject. One tampered signature in a set of its own size (see
    # the module docstring for why not the 10k set).
    k = N_TAMPER
    check(batch.RLC_MIN <= k < 2 * batch.RLC_MIN, "step 4: N_TAMPER outside the one-leaf range")
    vals_t, votes_t = make_signed_set(seed + 1, k, CHAIN_ID, block_id)
    bad = int(rng.integers(0, k))
    sigs_t = flip_one([v.signature for v in votes_t], bad)
    ref_t = reference_mask(vals_t, votes_t, sigs_t)
    check(list(np.flatnonzero(~ref_t)) == [bad], "step 4: the reference does not single out the tampered row")
    emit(smoke="tamper", validators=k, tampered_index=bad,
         why="n < 2*RLC_MIN: one combined check, then the per-signature leaf; "
             "a tampered 10k commit would bisect over three more lane buckets")
    pubkeys_t, msgs_t = rows_of(vals_t, votes_t)
    recovery_paths = {"persig", "rlc-bisect"}

    t0 = time.perf_counter()
    mask_t = batch.verify_batch(pubkeys_t, msgs_t, sigs_t)
    wall = time.perf_counter() - t0
    compiles.drain("step4.verify_batch_tampered")
    check_device_flush(flush_reading("step4.verify_batch_tampered", wall), k,
                       recovery_paths, True)
    check(np.array_equal(mask_t, ref_t), "step 4: tampered verify_batch mask != reference")

    bad_commit = commit_from_votes(votes_t, sigs_t, block_id)
    t0 = time.perf_counter()
    try:
        vals_t.verify_commit(CHAIN_ID, block_id, HEIGHT, bad_commit)
    except CommitVerifyError as e:
        verdict = str(e)
    else:
        verdict = "accepted"
    wall = time.perf_counter() - t0
    compiles.drain("step4.verify_commit_tampered")
    check_device_flush(flush_reading("step4.verify_commit_tampered", wall), k,
                       recovery_paths, True)
    check(verdict == f"wrong signature (#{bad})",
          f"step 4: tampered commit verdict {verdict!r}, expected wrong signature (#{bad})")
    emit(smoke="verdicts", step2="all valid == reference", step3="accepted == reference",
         step4=verdict)


# ----------------------------------------------------------- four chips


def run_four_chips(seed: int, compiles: CompileLog) -> None:
    """Only the sharded route (crypto/batch._sharded_env shards every RLC
    flush over the largest power of two of the visible devices): the
    10,000-row verify_batch, valid then one tampered row, against the host
    reference, and a check that every device took part."""
    import jax
    import numpy as np

    from tendermint_tpu.crypto import batch
    from tendermint_tpu.parallel import telemetry
    from tendermint_tpu.types.basic import BlockID, PartSetHeader

    n = N_VALIDATORS
    rng = np.random.default_rng(seed)
    block_id = BlockID(rng.bytes(32), PartSetHeader(1, rng.bytes(32)))
    vals, votes = make_signed_set(seed, n, CHAIN_ID, block_id)
    pubkeys, msgs = rows_of(vals, votes)
    sigs = [v.signature for v in votes]
    batch.configure_verified_memo(0)  # the second call must reach the device
    ndev = len(jax.devices())

    def flush(phase, sigs_, paths, fallback):
        ref = reference_mask(vals, votes, sigs_)
        t0 = time.perf_counter()
        mask = batch.verify_batch(pubkeys, msgs, sigs_)
        wall = time.perf_counter() - t0
        compiles.drain(phase)
        check_device_flush(flush_reading(phase, wall), n, paths, fallback,
                           fused_reported=False)
        check(np.array_equal(mask, ref), f"{phase}: mask != reference")
        return ref

    flush("chips4.valid", sigs, {"rlc-sharded"}, False)
    mesh = telemetry.mesh_stats()
    emit(smoke="mesh", mesh=mesh["mesh"], last_flush=mesh["last_flush"],
         last_pad=mesh["last_pad"], totals=mesh["totals"])
    # every device held a lane shard: the devices of the flush output's
    # addressable_shards (parallel/sharded.py records them), and memory
    # the allocator saw on each
    held = set(mesh["last_flush"]["devices"] or [])
    check(held == {str(d) for d in jax.devices()} and len(held) == ndev,
          f"lane shards lived on {sorted(held)}, not on all {ndev} devices")
    peaks = {str(d): (d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()}
    check(all(p is None or p > 0 for p in peaks.values()),
          f"a device allocated nothing during the sharded flush: {peaks}")

    # _bisect_recover splits at the largest power of two below the range
    # and, when the first half passes, descends into the second: at 10,000
    # rows the splits are 8,192 then 9,216. A bad row in the trailing leaf
    # costs one combined check per split, each first half on a shape of
    # its own (the first one pads to the valid flush's); anywhere else the
    # recursion meets more lane buckets, each a new program.
    tail, m = 0, n
    while m > 256 and m >= 2 * batch.RLC_MIN:
        half = 1 << ((m - 1).bit_length() - 1)
        tail, m = tail + half, m - half
    bad = int(rng.integers(tail, n))
    sigs_t = flip_one(sigs, bad)
    emit(smoke="tamper", validators=n, tampered_index=bad,
         why=f"in the trailing leaf [{tail}, {n}): every earlier sub-range "
             "passes its combined check, the leaf recovers per-signature")
    ref_t = flush("chips4.tampered", sigs_t, {"rlc-bisect"}, True)
    check(list(np.flatnonzero(~ref_t)) == [bad], "the reference does not single out the tampered row")
    # submit (trace + compile + dispatch) against finish (sync) seconds over
    # every sharded flush of the run: where a long tampered wall went
    mesh = telemetry.mesh_stats()
    emit(smoke="mesh_totals", flushes=mesh["flushes"], totals=mesh["totals"])


# ------------------------------------------------------------------ main


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded route, on a four-chip host")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    device = require_tpu(args.chips)

    from importlib import metadata

    import jax

    from tendermint_tpu import native
    from tendermint_tpu.ops.aot_cache import configure_compile_cache

    logging.getLogger("tendermint_tpu").addHandler(ALARM)
    cache_dir = configure_compile_cache()
    emit(smoke="env", device=device, jax=jax.__version__,
         jaxlib=metadata.version("jaxlib"), libtpu=metadata.version("libtpu"),
         compile_cache_dir=cache_dir)
    check(native.available(), "the native prep library did not build/load")
    compiles = CompileLog()
    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            run_four_chips(args.seed, compiles)
        else:
            run_one_chip(args.seed, compiles)
        check(not ALARM.records, f"tendermint_tpu warned: {ALARM.records}")
    finally:
        compiles.summary()
        emit(smoke="device_memory", peak_bytes_in_use={
            str(d): (d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in jax.devices()
        }, phases_wall_s=round(time.perf_counter() - t0, 1))
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
