"""Light client: trust-minimized header tracking.

reference: light/client.go — NewClient (:113), initializeWithTrustOptions
(:292), VerifyLightBlockAtHeight (:415), verifySequential (:553),
verifySkipping (:643, bisection), backwards (:860), detectDivergence (:898
light/detector.go), replacePrimaryWithWitness (:1018).

All commit verification inside is batched over the validator axis (see
light/verifier.py) — a bisection over a 10k-validator chain is a handful of
device batches, not hundreds of thousands of serial verifies. Sequential
verification batches over heights as well: the headers between the trusted
one and the target are fetched and verified in runs, every signature of a
run in one flush (_verify_sequential).
"""

from __future__ import annotations

import asyncio
import logging
import time
from dataclasses import dataclass
from typing import List, Optional

from tendermint_tpu.crypto import batch as _batch
from tendermint_tpu.crypto import scheduler as _scheduler
from tendermint_tpu.libs import trace as _trace
from tendermint_tpu.light import verifier
from tendermint_tpu.light.provider import Provider, ProviderError
from tendermint_tpu.light.store import LightStore
from tendermint_tpu.light.verifier import (
    DEFAULT_TRUST_LEVEL,
    ErrNewValSetCantBeTrusted,
    LightError,
)
from tendermint_tpu.types.basic import NANOS
from tendermint_tpu.types.light import LightBlock
from tendermint_tpu.types.validator_set import Fraction

logger = logging.getLogger("tmtpu.light")

SEQUENTIAL = "sequential"
SKIPPING = "skipping"

DEFAULT_MAX_CLOCK_DRIFT_NS = 10 * NANOS  # reference: light/client.go:40
DEFAULT_PRUNING_SIZE = 1000  # reference: light/client.go:36

# Sequential verification gathers the headers between the trusted one and the
# target in runs and verifies a run's signatures in ONE flush. A run is
# bounded in rows, at this many of the flush planner's chunks (36,861 rows on
# the planner's default budget: 368 headers of a 100-validator chain): over
# the budget, so that the flush is streamed and a chunk's host prep can run
# while the chunk before it is on the device (two fifths of it did, PERF.md
# section 5), with a run's rows and fetched light blocks still a few MB. Three
# is the least that holds the 333 headers light-seq-100.sequence verifies a
# call; no other value has been tried on the chip (PERF.md section 7).
# blocksync/reactor.py's VERIFY_BATCH_BLOCKS is the same bound for blocks.
VERIFY_RUN_CHUNKS = 3


def verify_run_rows() -> int:
    """The most signature rows one run of sequential verification gathers."""
    return VERIFY_RUN_CHUNKS * _batch.planner_chunk_rows()


class ErrConflictingHeaders(LightError):
    """A witness reported a different header for a verified height —
    possible attack (reference: light/errors.go ErrConflictingHeaders)."""

    def __init__(self, witness_index: int, height: int):
        self.witness_index = witness_index
        self.height = height
        self.conflicting_blocks: list = []
        super().__init__(f"witness #{witness_index} has a different header at height {height}")


class ErrNoWitnesses(LightError):
    """reference: light/errors.go errNoWitnesses."""


@dataclass
class TrustOptions:
    """Subjective initialization root (reference: light/trust_options.go)."""

    period_ns: int
    height: int
    hash: bytes

    def validate(self) -> None:
        if self.period_ns <= 0:
            raise ValueError("negative or zero trusting period")
        if self.height <= 0:
            raise ValueError("negative or zero height")
        if len(self.hash) != 32:
            raise ValueError(f"expected hash size to be 32 bytes, got {len(self.hash)}")


def _now_ns() -> int:
    return time.time_ns()


class Client:
    """reference: light/client.go:113."""

    def __init__(
        self,
        chain_id: str,
        trust_options: TrustOptions,
        primary: Provider,
        witnesses: List[Provider],
        trusted_store: LightStore,
        verification_mode: str = SKIPPING,
        trust_level: Fraction = DEFAULT_TRUST_LEVEL,
        max_clock_drift_ns: int = DEFAULT_MAX_CLOCK_DRIFT_NS,
        pruning_size: int = DEFAULT_PRUNING_SIZE,
    ):
        trust_options.validate()
        if verification_mode == SKIPPING:
            verifier.validate_trust_level(trust_level)
        elif verification_mode != SEQUENTIAL:
            raise ValueError(f"unknown verification mode {verification_mode!r}")
        self.chain_id = chain_id
        self.trust_options = trust_options
        self.primary = primary
        self.witnesses = list(witnesses)
        # Conflicting headers retained after divergence detection, for
        # operator inspection / evidence submission (see
        # _compare_with_witnesses).
        self.conflicting_blocks: list = []
        self.store = trusted_store
        self.mode = verification_mode
        self.trust_level = trust_level
        self.max_clock_drift_ns = max_clock_drift_ns
        self.pruning_size = pruning_size
        self._lock = asyncio.Lock()
        self._initialized = False

    # ------------------------------------------------------------- lifecycle

    async def initialize(self, now_ns: Optional[int] = None) -> LightBlock:
        """Fetch + pin the root of trust (reference: light/client.go:292
        initializeWithTrustOptions); checks the stored root against the trust
        options on restart (reference: checkTrustedHeaderUsingOptions :237)."""
        now_ns = now_ns if now_ns is not None else _now_ns()
        async with self._lock:
            with _trace.span("light.load"):
                existing = self.store.light_block(self.trust_options.height)
                trusted = existing is not None and existing.hash() == self.trust_options.hash
            if trusted:
                self._initialized = True
                return existing
            lb = await self.primary.light_block(self.trust_options.height)
            if lb.hash() != self.trust_options.hash:
                raise LightError(
                    f"expected header's hash {self.trust_options.hash.hex()}, "
                    f"but got {lb.hash().hex()}"
                )
            lb.validate_basic(self.chain_id)
            if verifier.header_expired(lb.signed_header, self.trust_options.period_ns, now_ns):
                raise verifier.ErrOldHeaderExpired(
                    lb.time_ns + self.trust_options.period_ns, now_ns
                )
            # The commit must actually be signed by +2/3 of its own valset.
            lb.validator_set.verify_commit_light(
                self.chain_id, lb.signed_header.commit.block_id, lb.height,
                lb.signed_header.commit,
            )
            await self._compare_with_witnesses(lb)
            self.store.save_light_block(lb)
            self._initialized = True
            return lb

    async def _ensure_initialized(self, now_ns: int) -> None:
        if not self._initialized:
            raise LightError("client not initialized — call initialize() first")

    # ------------------------------------------------------------ public API

    async def trusted_light_block(self, height: int) -> Optional[LightBlock]:
        return self.store.light_block(height)

    async def update(self, now_ns: Optional[int] = None) -> Optional[LightBlock]:
        """Verify the latest header from primary
        (reference: light/client.go:465 Update)."""
        now_ns = now_ns if now_ns is not None else _now_ns()
        latest = await self._fetch_from_primary(None)
        last = self.store.latest_light_block()
        if last is not None and latest.height <= last.height:
            return None
        return await self.verify_light_block(latest, now_ns)

    async def verify_light_block_at_height(
        self, height: int, now_ns: Optional[int] = None
    ) -> LightBlock:
        """reference: light/client.go:415 VerifyLightBlockAtHeight."""
        if height <= 0:
            raise ValueError("height must be positive")
        now_ns = now_ns if now_ns is not None else _now_ns()
        await self._ensure_initialized(now_ns)
        with _trace.span("light.load"):
            existing = self.store.light_block(height)
        if existing is not None:
            return existing
        lb = await self._fetch_from_primary(height)
        return await self.verify_light_block(lb, now_ns)

    async def verify_light_block(self, new_lb: LightBlock, now_ns: int) -> LightBlock:
        """Verify a light block obtained elsewhere
        (reference: light/client.go:497 VerifyHeader).

        Around the verification a call spans what it reads of the store
        (`light.load`, the trusted blocks decoded), the target's own checks
        (`light.target_checks`), the witnesses (`light.witness`, across
        awaits: written closed) and the target's save and prune
        (`light.save`); one span each, never one a header."""
        await self._ensure_initialized(now_ns)
        async with self._lock:
            with _trace.span("light.load"):
                existing = self.store.light_block(new_lb.height)
                if existing is None:
                    first = self.store.first_light_block()
                    backwards = first is not None and new_lb.height < first.height
                    closest = None if backwards else \
                        self.store.light_block_before(new_lb.height + 1)
            if existing is not None:
                if existing.hash() != new_lb.hash():
                    raise LightError(
                        f"existing trusted header {existing.hash().hex()} does not "
                        f"match new one {new_lb.hash().hex()} at height {new_lb.height}"
                    )
                return existing
            with _trace.span("light.target_checks"):
                new_lb.validate_basic(self.chain_id)

            if backwards:
                await self._backwards(first, new_lb, now_ns)
            else:
                if closest is None:
                    raise LightError("no trusted state to verify from")
                if self.mode == SEQUENTIAL:
                    await self._verify_sequential(closest, new_lb, now_ns)
                else:
                    await self._verify_skipping(closest, new_lb, now_ns)

            since = time.perf_counter_ns() if _trace.tracer.enabled else 0
            await self._compare_with_witnesses(new_lb)
            if since:
                _trace.interval("light.witness", since, time.perf_counter_ns(),
                                witnesses=len(self.witnesses))
            with _trace.span("light.save"):
                self.store.save_light_block(new_lb)
                self.store.prune(self.pruning_size)
            return new_lb

    # -------------------------------------------------------- verify drivers

    async def _verify_sequential(
        self, trusted: LightBlock, target: LightBlock, now_ns: int
    ) -> None:
        """Verify every height between trusted and target
        (reference: light/client.go:553 verifySequential), in runs: the light
        blocks of a run are fetched, checked and verified together
        (_verify_run), with what one verify_adjacent a height means. A run
        holds as many headers as verify_run_rows() allows, and at least one;
        a run of one header is the one-header call, verify_adjacent, spans and all.

        A run is verified and saved on an executor thread, as the block-sync
        reactor's is: checks, flush and store are some 0.5 s of a 333-header
        run, and the flush may wait on the scheduler's lane, none of which the
        event loop is to be held for. A fetch that fails ends the run before
        it: what was fetched is verified and saved, then the fetch's error is
        raised, as when a header was verified before the next was fetched. The
        first header a new primary serves (_fetch_from_primary promoted a
        witness) is a run of its own: a forged chain from it is refused on one
        header's host verify, not on a run's fetches and the recovery ladder."""
        bound = verify_run_rows()
        loop = asyncio.get_running_loop()
        current, carried = trusted, None
        while current.height < target.height:
            t_fetch = time.perf_counter_ns()
            run, rows, fetch_error = [], 0, None
            while current.height + len(run) < target.height:
                h = current.height + len(run) + 1
                if carried is not None:
                    (lb, alone), carried = carried, None
                elif h == target.height:
                    lb, alone = target, False
                else:
                    primary = self.primary
                    try:
                        lb = await self._fetch_from_primary(h)
                    except Exception as e:
                        fetch_error = e
                        break
                    alone = self.primary is not primary
                n = len(lb.signed_header.commit.signatures)
                if run and (alone or rows + n > bound):
                    carried = (lb, alone)  # the next run's first
                    break
                run.append(lb)
                rows += n
                if alone:
                    break
            if run:
                fetched = (t_fetch, time.perf_counter_ns())
                await loop.run_in_executor(
                    None, self._verify_run, current, run, target, now_ns, fetched
                )
                current = run[-1]
            if fetch_error is not None:
                raise fetch_error

    def _verify_run(
        self, trusted: LightBlock, run: List[LightBlock], target: LightBlock,
        now_ns: int, fetched: tuple,
    ) -> None:
        """One run of sequential verification: verifier.verify_adjacent_run
        over the fetched light blocks, then the store is given the headers
        that were verified, in order (the target is verify_light_block's to
        save, after the witnesses), and the failure of the first height that
        was not, if any, is raised: nothing at or past it is ever saved.

        Called on an executor thread (_verify_sequential). The run's rows
        ride the scheduler's light lane where a default scheduler stands (a
        node: state sync), a plain accumulator where none does (the standalone
        client), as light/service.py's windows do. One span tree a run: root
        `light.verify_run`; `light.fetch` ran across awaits, so it is written
        from its two clock readings."""
        if len(run) == 1:
            lb = run[0]
            verifier.verify_adjacent(
                self.chain_id, trusted.signed_header, lb.signed_header, lb.validator_set,
                self.trust_options.period_ns, now_ns, self.max_clock_drift_ns,
            )
            if lb is not target:
                self.store.save_light_block(lb)
            return
        with _trace.span("light.verify_run", headers=len(run)) as root:
            root.began_at(fetched[0])
            _trace.interval("light.fetch", *fetched, parent=root, headers=len(run))
            sched = _scheduler.default_scheduler()
            acc = sched.accumulate("light") if sched is not None else _batch.FlushAccumulator()
            verified, failure = verifier.verify_adjacent_run(
                self.chain_id, trusted.signed_header, run,
                self.trust_options.period_ns, now_ns, self.max_clock_drift_ns, acc, root,
            )
            with _trace.span("light.store") as sp:
                saved = [lb for lb in run[:verified] if lb is not target]
                sp.set(headers=len(saved),
                       bytes=sum(self.store.save_light_block(lb) for lb in saved))
            if failure is None:
                root.set(verdict="accepted")
            else:
                root.set(verdict=f"refused at height {run[verified].height}: "
                                 f"{type(failure).__name__}")
                raise failure

    async def _verify_skipping(
        self, trusted: LightBlock, target: LightBlock, now_ns: int
    ) -> None:
        """Bisection (reference: light/client.go:643 verifySkipping): try a
        non-adjacent jump; when the trusted valset can't vouch (+1/3 overlap
        missing), bisect to the midpoint and retry."""
        current = trusted
        to_verify = [target]
        while to_verify:
            candidate = to_verify[-1]
            try:
                if candidate.height == current.height + 1:
                    verifier.verify_adjacent(
                        self.chain_id,
                        current.signed_header,
                        candidate.signed_header,
                        candidate.validator_set,
                        self.trust_options.period_ns,
                        now_ns,
                        self.max_clock_drift_ns,
                    )
                else:
                    verifier.verify_non_adjacent(
                        self.chain_id,
                        current.signed_header,
                        current.validator_set,
                        candidate.signed_header,
                        candidate.validator_set,
                        self.trust_options.period_ns,
                        now_ns,
                        self.max_clock_drift_ns,
                        self.trust_level,
                    )
            except ErrNewValSetCantBeTrusted:
                pivot = (current.height + candidate.height) // 2
                if pivot in (current.height, candidate.height):
                    raise LightError(
                        f"bisection stuck between heights {current.height} and "
                        f"{candidate.height}"
                    )
                mid = await self._fetch_from_primary(pivot)
                if mid.height != pivot:
                    raise LightError(
                        f"primary returned height {mid.height} for requested "
                        f"pivot {pivot}"
                    )
                to_verify.append(mid)
                continue
            # verified
            to_verify.pop()
            if candidate.height != target.height:
                self.store.save_light_block(candidate)
            current = candidate

    async def _backwards(
        self, first_trusted: LightBlock, target: LightBlock, now_ns: int
    ) -> None:
        """Walk the hash chain down from the first trusted header
        (reference: light/client.go:860 backwards)."""
        trusted = first_trusted
        for h in range(first_trusted.height - 1, target.height - 1, -1):
            inter = target if h == target.height else await self._fetch_from_primary(h)
            # validate_basic pins the block's valset to header.ValidatorsHash and
            # the commit to the header hash — without it a primary could attach
            # an attacker valset to a genuine header and poison the store.
            inter.validate_basic(self.chain_id)
            verifier.verify_backwards(
                self.chain_id, inter.signed_header, trusted.signed_header
            )
            if h != target.height:
                self.store.save_light_block(inter)
            trusted = inter

    # ------------------------------------------------------------- witnesses

    async def _compare_with_witnesses(self, lb: LightBlock) -> None:
        """Cross-check a verified header against all witnesses; a conflicting
        witness means a possible attack (reference: light/detector.go:33
        detectDivergence). Witnesses that don't respond are skipped; witnesses
        that conflict are removed and the error surfaced."""
        if not self.witnesses:
            return
        conflicts = []
        for i, w in enumerate(list(self.witnesses)):
            try:
                other = await w.light_block(lb.height)
            except ProviderError:
                continue
            if other.hash() != lb.hash():
                conflicts.append((i, w, other))
        if conflicts:
            # Keep the conflicting evidence available for operator
            # inspection (the reference builds LightClientAttackEvidence and
            # reports it to the honest providers, light/detector.go:116; we
            # record the diverging headers and surface them on the error).
            for i, w, other in conflicts:
                logger.error(
                    "witness %s reports conflicting header at height %d: "
                    "primary hash %s vs witness hash %s — possible light-client attack",
                    w,
                    lb.height,
                    lb.hash().hex(),
                    other.hash().hex(),
                )
                self.conflicting_blocks.append(other)
            for _, w, _other in conflicts:
                self.witnesses.remove(w)
            err = ErrConflictingHeaders(conflicts[0][0], lb.height)
            err.conflicting_blocks = [c[2] for c in conflicts]
            raise err

    async def _fetch_from_primary(self, height: Optional[int]) -> LightBlock:
        """Fetch from primary, replacing it with a witness on failure
        (reference: light/client.go:1004 lightBlockFromPrimary +
        :1018 replacePrimaryWithWitness)."""
        try:
            return await self.primary.light_block(height)
        except ProviderError as e:
            logger.warning("primary %s failed (%s); trying witnesses", self.primary, e)
            while self.witnesses:
                w = self.witnesses[0]
                try:
                    lb = await w.light_block(height)
                except ProviderError:
                    self.witnesses.pop(0)
                    continue
                # promote witness to primary; demote old primary to witness
                self.witnesses.pop(0)
                self.witnesses.append(self.primary)
                self.primary = w
                return lb
            raise ErrNoWitnesses(f"primary failed and no witness responded: {e}") from e

    # -------------------------------------------------------------- cleanup

    def first_trusted_height(self) -> Optional[int]:
        lb = self.store.first_light_block()
        return lb.height if lb else None

    def last_trusted_height(self) -> Optional[int]:
        lb = self.store.latest_light_block()
        return lb.height if lb else None
