"""Entry driver `verify_commit`: one operation is one call of
ValidatorSet.verify_commit(chain_id, block_id, height, commit), the call a
node, a syncing node or a light client waits on. This file is all the
benchmark knows of the program: how to hand it the generated bytes, how to
call it, what its flush record says, and where its combined check can be
asked about a tampered commit without the recovery ladder behind it."""

from __future__ import annotations

import contextlib

import numpy as np

from reference import FLAG_ABSENT

# The function that runs the timed programs' combined check and nothing
# after it, by the path the flush reports: it gives the mask, or None for
# "some row is bad". Through the public verify_batch a tampered commit goes
# on into the recovery ladder (_bisect_recover), which at 10,000 rows is
# five more programs of minutes each that no timed call runs. Where a later
# program has no such function, rejects() asks verify_batch and pays that.
COMBINED_CHECK = {"rlc-pipelined": "_verify_batch_pipelined", "rlc": "_verify_batch_rlc",
                  "cpu": "_verify_batch_cpu_rlc"}  # the last: the host backend, in rehearsals
_seams_off = [False]


def configure(traffic: dict) -> None:
    """What the mix states about the process: [crypto] verified_memo_rows."""
    from tendermint_tpu.crypto import batch

    batch.configure_verified_memo(int(traffic["verified_memo_rows"]))


class State:
    def __init__(self, config, vals, ring):
        from tendermint_tpu.crypto.keys import Ed25519PubKey
        from tendermint_tpu.types.basic import BlockID, BlockIDFlag, PartSetHeader
        from tendermint_tpu.types.block import Commit, CommitSig
        from tendermint_tpu.types.validator_set import Validator, ValidatorSet

        self.chain_id = config["chain_id"]
        self.vals = ValidatorSet(
            [Validator(Ed25519PubKey(pk), p) for pk, p in zip(vals.pubkeys, vals.powers)]
        )
        got = [v.pub_key.bytes() for v in self.vals.validators]
        if got != list(vals.pubkeys):
            raise SystemExit("verify_commit: the program orders the validator "
                             "set otherwise than power, then address")
        addrs = [v.address for v in self.vals.validators]
        self.commits = []
        for c in ring:
            block_id = BlockID(c.block_hash, PartSetHeader(c.parts_total, c.parts_hash))
            sigs = [
                CommitSig.absent_sig() if f == FLAG_ABSENT
                else CommitSig(BlockIDFlag.COMMIT, addrs[i], c.timestamps[i], c.sigs[i])
                for i, f in enumerate(c.flags)
            ]
            self.commits.append((block_id, c.height, Commit(c.height, c.round, block_id, sigs)))


def build(config, vals, ring) -> State:
    return State(config, vals, ring)


def call(state: State, i: int) -> str:
    """The timed call. Returns the verdict in the reference's words."""
    from tendermint_tpu.types.validator_set import (
        CommitVerifyError,
        NotEnoughVotingPowerError,
    )

    block_id, height, commit = state.commits[i]
    try:
        state.vals.verify_commit(state.chain_id, block_id, height, commit)
    except NotEnoughVotingPowerError:
        return "not enough voting power"
    except CommitVerifyError as e:
        return str(e)
    return "accepted"


def flush_reading() -> dict:
    """What the last flush says about itself (host-clock spans and counts,
    none of it device time)."""
    from tendermint_tpu.crypto import batch
    from tendermint_tpu.libs import trace

    last = trace.verify_stats()["last_flush"]
    return {
        "backend": last.get("backend"),
        "path": last.get("path"),
        "jax_path": batch.LAST_JAX_PATH[0],
        "rows": last.get("n"),
        "rows_valid": last.get("n_valid"),
        "total_ms": last.get("total_ms"),
        "prep_ms": last.get("prep_ms"),
        "prep_overlap_ms": last.get("prep_overlap_ms"),
        "transfer_ms": last.get("transfer_ms"),
        "compile_ms": last.get("compile_ms", 0.0),
        "lane_bucket": last.get("jit_bucket"),
        "padding_lanes": last.get("padding_lanes"),
        "chunks": last.get("chunks"),
        "chunk_lanes": last.get("chunk_lanes"),
        "h2d_bytes": last.get("h2d_bytes"),
        "device_dispatches": last.get("device_dispatches"),
        "fused": last.get("fused"),
        "rlc_fallback": bool(last.get("rlc_fallback", False)),
        "recovery_flushes": last.get("recovery_flushes"),
        # the host backend's combined check answered the whole flush (a row of
        # an invalid encoding drops out of it with its verdict, and no ladder)
        "host_rlc": bool(batch.LAST_FLUSH_DETAIL.get("host_rlc", False)),
    }


def flush_fault(r: dict, expect: dict, rows: int) -> str | None:
    """None where the flush ran where the configuration says; else why not."""
    if r["backend"] != expect["backend"]:
        return f"backend {r['backend']!r}"
    if r["path"] not in expect["paths"]:
        return f"path {r['path']!r}"
    if expect["backend"] == "jax":
        if r["jax_path"] != r["path"]:
            return f"jax path {r['jax_path']!r} under {r['path']!r}"
        if r["fused"] is not True:
            return "not fused"
    if r["rlc_fallback"] or r["recovery_flushes"]:
        return "fell back to recovery"
    if r["rows"] != rows:
        return f"{r['rows']} rows flushed"
    return None


def recovery_fault(r: dict, expect_recovery: dict, rows: int) -> str | None:
    """None where a call on a forged item recovered where the configuration's
    `expect_recovery` says: on its backend and one of its paths, by a ladder
    of at least one and at most `max_recovery_flushes` flushes after the
    failed combined check, with nothing compiled and all `rows` of the item;
    else why not. A straight per-signature pass writes no ladder, and a
    recovery on the host names the host's backend. On the host backend a bad
    row whose encoding is invalid drops out of the combined check, which then
    answers with no ladder (`host_rlc`); on the device it fails its lane and
    the ladder runs."""
    if r["backend"] != expect_recovery["backend"]:
        return f"recovery on backend {r['backend']!r}"
    if r["path"] not in expect_recovery["paths"]:
        return f"recovery on path {r['path']!r}"
    if expect_recovery["backend"] == "jax" and r["jax_path"] != r["path"]:
        return f"recovery jax path {r['jax_path']!r} under {r['path']!r}"
    walked = r["recovery_flushes"]
    if not walked and not (r.get("host_rlc") and not r["rlc_fallback"]):
        return "no recovery ladder walked"
    if (walked or 0) > int(expect_recovery["max_recovery_flushes"]):
        return f"{walked} recovery flushes"
    if r["compile_ms"]:
        return f"compiled {r['compile_ms']:.0f} ms in the recovery"
    if r["rows"] != rows:
        return f"{r['rows']} rows flushed"
    return None


def process_faults() -> list:
    """Sticky faults of the process: breaker opened, fused pipeline off."""
    from tendermint_tpu.crypto import batch
    from tendermint_tpu.ops import msm_jax

    out = []
    snap = batch.BREAKER.snapshot()
    if snap["state"] != "closed" or snap["consecutive_failures"] or any(snap["trips"].values()):
        out.append(f"breaker {snap['state']}, trips {snap['trips']}")
    disabled = getattr(msm_jax, "_FUSED_DISABLED", [None])[0]
    if disabled is not None:
        out.append(f"fused pipeline disabled: {disabled}")
    return out


def native_ready() -> bool:
    from tendermint_tpu import native

    return native.available()


def mask(pubkeys, msgs, sigs) -> list:
    """crypto/batch's row mask on these rows, through its public call."""
    from tendermint_tpu.crypto import batch

    return [bool(x) for x in batch.verify_batch(pubkeys, msgs, sigs)]


def _seam(path: str):
    from tendermint_tpu.crypto import batch

    return None if _seams_off[0] else getattr(batch, COMBINED_CHECK.get(path, ""), None)


def passes_clean(path: str, pubkeys, msgs, sigs) -> bool:
    """The combined check, asked about rows that are all valid, gives the
    whole mask. It answers None for a tampered row and also, with no log,
    for a geometry it cannot hold: only where it has passed the clean rows
    does None on their tampered copy mean "refused"."""
    seam = _seam(path)
    if seam is None:
        return True  # rejects() asks verify_batch, whose mask names the row
    got = seam(pubkeys, msgs, sigs)
    return got is not None and len(got) == len(pubkeys) and bool(np.all(got))


def rejects(path: str, pubkeys, msgs, sigs, bad: int) -> bool:
    """Does the program refuse these rows, of which row `bad` is tampered?"""
    from tendermint_tpu.crypto import batch

    seam = _seam(path)
    if seam is not None:  # None: some row is bad; or a mask that says which
        got = seam(pubkeys, msgs, sigs)
        return got is None or not bool(got[bad])
    got = np.asarray(batch.verify_batch(pubkeys, msgs, sigs), dtype=bool)
    return not got[bad] and int(got.sum()) == len(got) - 1


@contextlib.contextmanager
def flush_spans(annotate):
    """While tracing: the flush as the entry sees it, under a span of the
    benchmark's own (`annotate(name)` gives a context manager)."""
    from tendermint_tpu.types import validator_set

    inner = validator_set.verify_batch

    def spanned(*a, **kw):
        with annotate("bench:flush"):
            return inner(*a, **kw)

    validator_set.verify_batch = spanned
    try:
        yield
    finally:
        validator_set.verify_batch = inner


def install_verifier(fn) -> None:
    """Puts `fn(pubkeys, msgs, sigs) -> bool mask` in the program's place
    under the entry: the control and the planted faults (tests, --control)."""
    import time

    from tendermint_tpu.crypto import batch
    from tendermint_tpu.libs import trace
    from tendermint_tpu.types import validator_set

    def verify_batch(pubkeys, msgs, sigs, *a, **kw):
        t0 = time.perf_counter()
        got = np.asarray(fn(pubkeys, msgs, sigs), dtype=bool)
        # a flush record as the host path writes it, so that only what the
        # stand-in gets wrong comes out wrong
        trace.record_flush(backend="cpu", path="cpu", n=len(got),
                           total_s=time.perf_counter() - t0, n_valid=int(got.sum()))
        return got

    batch.verify_batch = validator_set.verify_batch = verify_batch
    _seams_off[0] = True


def unsent_third() -> None:
    """The control on the program's own path: "every signature checked"
    broken inside the program. Each combined check gets a copy of row 0 in
    the place of every row past the first two thirds, so the timed programs
    run at the timed shapes and the last third of the rows goes unseen: what
    VerifyCommitLight's rule would make of VerifyCommit."""
    from tendermint_tpu.crypto import batch

    def wrap(inner):
        def combined(pubkeys, msgs, sigs, *a, **kw):
            cut = len(pubkeys) * 2 // 3 + 1

            def seen(xs):
                return list(xs[:cut]) + [xs[0]] * (len(xs) - cut)

            return inner(seen(pubkeys), seen(msgs), seen(sigs), *a, **kw)

        return combined

    for attr in sorted(set(COMBINED_CHECK.values())):
        if hasattr(batch, attr):
            setattr(batch, attr, wrap(getattr(batch, attr)))


def declines() -> None:
    """Planted fault, for the tests: a combined check that answers None to
    whatever it is asked. `correct` may not take that for refusing."""
    from tendermint_tpu.crypto import batch

    for attr in sorted(set(COMBINED_CHECK.values())):
        if hasattr(batch, attr):
            setattr(batch, attr, lambda *a, **kw: None)


def recovery_off_ladder() -> None:
    """Planted fault, for the tests: a forged flush answered by a straight
    per-signature pass on the host in the place of the recovery ladder (the
    device's and the host backend's). It names the right index, so only the
    recovery contract (`expect_recovery`) can find it."""
    from tendermint_tpu.crypto import batch

    def straight(pubkeys, msgs, sigs, *a, **kw):
        return batch._verify_serial_host(pubkeys, msgs, sigs)

    batch._bisect_recover = batch._bisect_recover_host = straight


PROGRAM_CONTROLS = {"unsent_third": unsent_third, "declines": declines,
                    "recovery_off_ladder": recovery_off_ladder}
