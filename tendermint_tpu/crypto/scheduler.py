"""Global verification scheduler: one device, every consumer, QoS lanes.

The repo grew five ad-hoc batch-verification entry points — live votes
(types/vote_set.py), the light service (light/service.py), commit
verification (types/validator_set.py), blocksync catch-up
(blocksync/reactor.py), and evidence (evidence/pool.py) — all competing
for the one device uncoordinated, plus the single biggest serial loop
left: per-tx CheckTx signature verification on the admission path
(mempool/mempool.py). This module is the coordinator ROADMAP item 2 calls
for: every consumer submits its (pubkey, msg, sig) rows to a node-wide
`VerifyScheduler`, which owns the device and drains priority lanes into
combined flushes ("Efficient FPGA-based ECDSA Verification Engine for
Permissioned Blockchains", PAPERS.md, is exactly this shape: admission-path
batch verification as the throughput lever for permissioned chains).

Lanes, in priority order:

    votes      the live consensus path. PREEMPTS: queued vote rows flush
               immediately and ALONE — they never wait behind, or share a
               flush with, bulk work (a vote flush's wall must not inflate
               because 10k CheckTx rows were queued).
    light      light-client serving (light/service.py). Rows wait at most
               the PR 9 coalescing-window SLO (`light_max_wait`), so many
               clients x many heights still share one cross-height flush.
    admission  CheckTx signature prechecks (mempool/mempool.py). Bounded
               latency (`admission_max_wait`), bounded rows per flush.
    catchup    blocksync replay + evidence re-verification. Soaks IDLE
               device capacity only: scheduled when no higher lane has
               rows, with a starvation floor so a busy node still syncs.
    quarantine rows from sources the suspicion scorer has quarantined
               (crypto/provenance.py: peers/senders whose rows recently
               failed). Flushes ALONE, only when every other lane is
               empty (plus a starvation floor), so a poisoning flood can
               force recovery bisections only on its own flushes — never
               on a vote/light/admission flush again.

Budgets respond to the PR 5 overload controller (node/overload.py calls
`set_pressure`): level 1 shrinks the admission/catch-up budgets (fewer rows
per flush, longer waits); level 2 pauses catch-up entirely. Per-lane queue
waits feed the PR 8 SLO burn-rate engine (`verify_lane_wait_*` budgets) and
the `tendermint_verify_lane_*` metric series; `stats()` is served as the
`scheduler` block of GET /debug/verify_stats.

Under the hood one dispatch thread drains the lanes into combined
`crypto/batch.verify_batch` flushes. Verdict recovery is the
FlushAccumulator contract (PR 9): the combined RLC check only
short-circuits when EVERY row passes, and any failure recovers the exact
per-row mask, so each consumer's verdict slice is byte-identical to a
standalone verify_batch of its own rows. The flush itself rides the full
PR 4 ladder — circuit breaker, CPU degrade — so a breaker-OPEN routes
every lane to the host loop with zero device work.

Consumers integrate three ways:

    mask = sched.verify_rows("admission", pubkeys, msgs, sigs)   # blocking
    with sched.lane_scope("catchup"):                            # transparent
        ...        # any verify_batch / verify_commit* inside routes via the lane
    with crypto.batch.accumulate_flushes(sched.accumulate("light")) as acc:
        ...        # PR 9 submit/finish phases, flush() rides the lane

All three block the calling thread until the lane's flush lands (the same
contract as calling verify_batch directly — only the WHO-flushes moved).
A consumer is never wedged where an inline verify could free it: a closed
scheduler, or a verdict that misses `wait_timeout`, falls back to an inline
verify_batch on the caller's thread. The one case that waits on is a ticket
whose flush is already running AND whose inline copy would ride the device
as well (batch._flush_route's answer for its row count and backend):
verifying those rows a second time would only queue behind the same compile
or the same hung device, so the consumer waits as a direct caller of
verify_batch would, and looks again every `wait_timeout`.

No reference counterpart: the reference verifies every signature serially
at each call site; a device worth sharing is what makes scheduling it a
subsystem.
"""

from __future__ import annotations

import contextlib
import logging
import threading
import time
from collections import deque
from typing import Dict, List, Optional, Sequence

import numpy as np

from tendermint_tpu.libs import trace as _trace
from tendermint_tpu.libs.txtrace import StageStats

logger = logging.getLogger("tendermint_tpu.crypto.scheduler")

__all__ = [
    "LANES",
    "VerifyScheduler",
    "LaneAccumulator",
    "Ticket",
    "set_default",
    "default_scheduler",
]

# priority order: index 0 preempts everything below it
LANES = ("votes", "light", "admission", "catchup", "quarantine")

# a starving catch-up lane flushes anyway after this many times its
# configured idle wait (unless pressure level 2 pauses it): "soaks idle
# capacity" must not become "a syncing node wedges whenever the chain is
# busy" — the floor trades a little bulk interference for liveness
CATCHUP_STARVATION_FACTOR = 10.0


class Ticket:
    """One submit's claim on a future combined flush: `wait()` blocks until
    the dispatch thread lands the flush and returns this submit's verdict
    slice (or re-raises the flush's error)."""

    __slots__ = ("lane", "rows", "enqueued_t", "flush_seq", "wait_s",
                 "parent", "t0_ns", "_event", "_mask", "_error")

    def __init__(self, lane: str, rows: int):
        self.lane = lane
        self.rows = rows
        self.enqueued_t = time.monotonic()
        # flight recorder (libs/trace.py): the submitting thread's open span,
        # so that `lane.wait`, `lane.flush` and the verify_batch under it
        # hang in the submitting call's tree although they end, or run, on
        # the dispatch thread. None with the recorder off.
        self.parent = _trace.current()
        self.t0_ns = time.perf_counter_ns()
        self.flush_seq: Optional[int] = None  # device flush this rode
        self.wait_s: Optional[float] = None   # queue wait (enqueue -> flush)
        self._event = threading.Event()
        self._mask: Optional[np.ndarray] = None
        self._error: Optional[BaseException] = None

    def done(self) -> bool:
        return self._event.is_set()

    def wait(self, timeout: Optional[float] = None) -> np.ndarray:
        if not self._event.wait(timeout):
            raise TimeoutError(
                f"verify ticket ({self.lane}, {self.rows} rows) not flushed "
                f"within {timeout}s"
            )
        if self._error is not None:
            raise self._error
        return self._mask

    # dispatcher side
    def _resolve(self, mask: Optional[np.ndarray],
                 error: Optional[BaseException]) -> None:
        self._mask = mask
        self._error = error
        self._event.set()


class _LaneState:
    __slots__ = ("name", "queue", "rows", "flushes", "rows_total", "paused")

    def __init__(self, name: str):
        self.name = name
        self.queue: deque = deque()  # of (Ticket, pubkeys, msgs, sigs, key_types)
        self.rows = 0                # queued rows (depth)
        self.flushes = 0             # flushes that carried this lane's rows
        self.rows_total = 0          # rows flushed lifetime
        self.paused = False          # pressure level 2 (catch-up only)


class _Budgets:
    """Effective per-lane budgets under the current pressure level."""

    __slots__ = ("max_rows", "max_wait")

    def __init__(self, max_rows: int, max_wait: float):
        self.max_rows = max_rows
        self.max_wait = max_wait


class LaneAccumulator:
    """FlushAccumulator-compatible adapter (crypto/batch.accumulate_flushes
    installs it unchanged): rows accumulate locally during the submit
    phases, and `flush()` rides the scheduler lane instead of dispatching
    its own device call — so e.g. a whole light coalescing window joins the
    node-wide combined flush. Mirrors FlushAccumulator's latch semantics: a
    failed flush re-raises for every later finish."""

    __slots__ = ("scheduler", "lane", "pubkeys", "msgs", "sigs", "key_types",
                 "_mask", "_flushed", "_error", "flush_count", "flush_seq")

    def __init__(self, scheduler: "VerifyScheduler", lane: str):
        self.scheduler = scheduler
        self.lane = lane
        self.pubkeys: list = []
        self.msgs: list = []
        self.sigs: list = []
        self.key_types: list = []
        self._mask: Optional[np.ndarray] = None
        self._flushed = False
        self._error: Optional[BaseException] = None
        self.flush_count = 0
        self.flush_seq: Optional[int] = None  # the shared device flush id

    @property
    def lanes(self) -> int:
        return len(self.pubkeys)

    def add(self, pubkeys, msgs, sigs, key_types) -> tuple:
        if self._flushed:
            raise RuntimeError("LaneAccumulator already flushed")
        start = len(self.pubkeys)
        self.pubkeys.extend(pubkeys)
        self.msgs.extend(msgs)
        self.sigs.extend(sigs)
        self.key_types.extend(
            key_types if key_types is not None else ["ed25519"] * len(pubkeys)
        )
        return start, len(self.pubkeys)

    def flush(self) -> np.ndarray:
        if self._flushed:
            if self._error is not None:
                raise self._error
            return self._mask
        self._flushed = True
        if not self.pubkeys:
            self._mask = np.zeros(0, dtype=bool)
            return self._mask
        self.flush_count += 1
        try:
            kt = (
                self.key_types
                if any(t != "ed25519" for t in self.key_types)
                else None
            )
            ticket = self.scheduler.submit(
                self.lane, self.pubkeys, self.msgs, self.sigs, self.key_types
            )
            if ticket is None:  # closed/disabled: inline on this thread
                self._mask = self.scheduler._inline(
                    self.pubkeys, self.msgs, self.sigs, kt
                )
                return self._mask
            # rows passed through so a wait_timeout miss verifies inline
            # (the never-wedge contract) instead of failing every rider
            self._mask = self.scheduler._wait_or_fallback(
                ticket, (self.pubkeys, self.msgs, self.sigs, kt)
            )
            self.flush_seq = ticket.flush_seq
        except BaseException as e:
            self._error = e
            raise
        return self._mask


class VerifyScheduler:
    """The node-wide device coordinator (see module docstring)."""

    def __init__(self, config=None, backend: Optional[str] = None,
                 metrics=None, slo=None):
        """config: config.SchedulerConfig (None = defaults); backend: crypto
        backend for the combined flushes (None/"" = crypto default);
        metrics: libs/metrics.SchedulerMetrics or None; slo:
        libs/slo.SLOEngine or None (fed verify_lane_wait_* per flush)."""
        if config is None:
            from tendermint_tpu.config.config import SchedulerConfig

            config = SchedulerConfig()
        self.config = config
        self.backend = backend or (getattr(config, "backend", "") or None)
        self.metrics = metrics
        self.slo = slo
        self._lanes: Dict[str, _LaneState] = {n: _LaneState(n) for n in LANES}
        self._base: Dict[str, _Budgets] = {
            "votes": _Budgets(int(config.votes_max_rows),
                              float(config.votes_max_wait)),
            "light": _Budgets(int(config.light_max_rows),
                              float(config.light_max_wait)),
            "admission": _Budgets(int(config.admission_max_rows),
                                  float(config.admission_max_wait)),
            "catchup": _Budgets(int(config.catchup_max_rows),
                                float(config.catchup_max_wait)),
            "quarantine": _Budgets(
                int(getattr(config, "quarantine_max_rows", 4096)),
                float(getattr(config, "quarantine_max_wait", 0.05)),
            ),
        }
        self.pressure_level = 0
        self.wait_timeout = float(getattr(config, "wait_timeout", 30.0))
        self._cv = threading.Condition()
        self._closed = False
        self.flush_seq = 0          # device flushes issued
        self.preemptions = 0        # vote flushes that jumped queued bulk work
        self.fallbacks = 0          # consumer-side inline fallbacks
        self.wait_stats = StageStats()  # per-lane queue-wait percentiles
        self.flush_rows_last: Dict[str, int] = {}
        # bounded per-flush journal: {"seq", "t" (monotonic, flush start),
        # "wall_s", "rows": {lane: n}, "wait_s": {lane: oldest wait}} —
        # windowed analysis for the tx_admission bench (vote-path p99
        # before/during a flood) and the preemption tests
        self.flush_log: deque = deque(maxlen=4096)
        self._thread = threading.Thread(
            target=self._run, name="verify-scheduler", daemon=True
        )
        self._thread.start()
        # install the lane_scope router so verify_batch/verify_commit* calls
        # inside `with sched.lane_scope(...)` route here transparently
        _install_router()

    # -- budgets / pressure ---------------------------------------------------

    def effective_budget(self, lane: str) -> _Budgets:
        """The lane's budget under the current pressure level: level >= 1
        shrinks admission/catch-up rows by pressure_rows_factor and
        stretches their waits by pressure_wait_factor (votes and light are
        never squeezed); level 2 pauses catch-up (see _plan_locked)."""
        base = self._base[lane]
        if self.pressure_level < 1 or lane in ("votes", "light"):
            return base
        rf = float(getattr(self.config, "pressure_rows_factor", 0.5))
        wf = float(getattr(self.config, "pressure_wait_factor", 2.0))
        return _Budgets(
            max(1, int(base.max_rows * rf)) if base.max_rows > 0 else 0,
            base.max_wait * wf,
        )

    def set_pressure(self, level: int) -> None:
        """Overload-controller hook (node/overload.py): 0 normal, 1 shrink
        admission/catch-up budgets, 2 additionally pause catch-up."""
        with self._cv:
            if level == self.pressure_level:
                return
            self.pressure_level = int(level)
            self._lanes["catchup"].paused = level >= 2
            self._cv.notify_all()

    def set_lane_wait(self, lane: str, max_wait: float) -> None:
        """Re-pin one lane's coalescing window (light/service.py wires its
        [light_service] coalesce_window here so the PR 9 SLO survives the
        migration)."""
        with self._cv:
            self._base[lane].max_wait = max(0.0, float(max_wait))
            self._cv.notify_all()

    # -- submit side ----------------------------------------------------------

    def submit(self, lane: str, pubkeys: Sequence[bytes],
               msgs: Sequence[bytes], sigs: Sequence[bytes],
               key_types: Optional[Sequence[str]] = None,
               sources: Optional[Sequence[str]] = None) -> Optional[Ticket]:
        """Queue one consumer's rows on `lane`; returns a Ticket (None when
        the scheduler is closed — callers verify inline then). Thread-safe;
        never blocks beyond the lane mutex. `sources` is the rows' optional
        provenance (crypto/provenance.py tags); None tags them with the
        consumer lane at flush time."""
        if lane not in self._lanes:
            raise ValueError(f"unknown verify lane {lane!r}")
        n = len(pubkeys)
        if not (n == len(msgs) == len(sigs)):
            raise ValueError("pubkeys/msgs/sigs length mismatch")
        ticket = Ticket(lane, n)
        if n == 0:
            ticket._resolve(np.zeros(0, dtype=bool), None)
            return ticket
        kt = list(key_types) if key_types is not None else None
        src = list(sources) if sources is not None else None
        with self._cv:
            if self._closed:
                return None
            st = self._lanes[lane]
            st.queue.append(
                (ticket, list(pubkeys), list(msgs), list(sigs), kt, src)
            )
            st.rows += n
            if self.metrics is not None:
                self.metrics.lane_depth.labels(lane).set(st.rows)
            self._cv.notify_all()
        return ticket

    def verify_rows(self, lane: str, pubkeys, msgs, sigs,
                    key_types=None, sources=None) -> np.ndarray:
        """Submit + block for the verdict slice — the drop-in replacement
        for a consumer's own `verify_batch(...)` call. Falls back to an
        inline verify_batch when the scheduler is closed or the ticket
        misses wait_timeout (a consumer is never wedged on the lane).

        Rows whose source is QUARANTINED (crypto/provenance.py) split off
        first and ride the quarantine lane instead, so a poisoning flood
        can never drag a vote/light/admission flush into bisection
        recovery (_verify_rows_partitioned merges the verdicts back in
        row order — the caller sees one mask either way).

        The VOTES lane never queues here: vote rows would flush alone
        anyway (bulk rows never ride a vote flush), so queuing them behind
        the dispatch thread only ADDS a handoff — and, worse, parks them
        behind whatever bulk flush is already in flight. True preemption is
        not queuing at all: the vote flush runs immediately on the caller's
        thread, with full lane accounting (depth-0 wait, flush journal,
        preemption count when bulk work sat queued)."""
        if lane != "quarantine" and sources is not None:
            from tendermint_tpu.crypto import provenance as _prov

            q = _prov.default_scorer().quarantined_sources()
            if q and any(s in q for s in sources):
                return self._verify_rows_partitioned(
                    lane, pubkeys, msgs, sigs, key_types, sources, q
                )
        if lane == "votes":
            return self._verify_votes_inline(pubkeys, msgs, sigs, key_types,
                                             sources)
        ticket = self.submit(lane, pubkeys, msgs, sigs, key_types, sources)
        if ticket is None:
            return self._inline(pubkeys, msgs, sigs, key_types, sources)
        return self._wait_or_fallback(
            ticket, (pubkeys, msgs, sigs, key_types, sources)
        )

    def _verify_rows_partitioned(self, lane, pubkeys, msgs, sigs, key_types,
                                 sources, quarantined) -> np.ndarray:
        """Split a submit whose sources are partly quarantined: suspect rows
        queue on the quarantine lane FIRST (non-blocking), the clean rows
        flush through their own lane as usual, then this thread blocks for
        the quarantine verdict and merges the masks in row order."""
        idx_q = [i for i, s in enumerate(sources) if s in quarantined]
        idx_c = [i for i, s in enumerate(sources) if s not in quarantined]

        def _take(seq, idx):
            return [seq[i] for i in idx]

        out = np.zeros(len(pubkeys), dtype=bool)
        q_rows = (
            _take(pubkeys, idx_q), _take(msgs, idx_q), _take(sigs, idx_q),
            _take(key_types, idx_q) if key_types is not None else None,
            _take(sources, idx_q),
        )
        q_ticket = self.submit("quarantine", *q_rows)
        if idx_c:
            out[idx_c] = self.verify_rows(
                lane,
                _take(pubkeys, idx_c), _take(msgs, idx_c), _take(sigs, idx_c),
                _take(key_types, idx_c) if key_types is not None else None,
                _take(sources, idx_c),
            )
        if q_ticket is None:
            out[idx_q] = self._inline(*q_rows)
        else:
            out[idx_q] = self._wait_or_fallback(q_ticket, q_rows)
        return out

    def _verify_votes_inline(self, pubkeys, msgs, sigs, key_types,
                             sources=None) -> np.ndarray:
        n = len(pubkeys)
        if n == 0:
            return np.zeros(0, dtype=bool)
        t0 = time.monotonic()
        with self._cv:
            preempted = any(
                self._lanes[name].queue for name in LANES if name != "votes"
            )
            if preempted:
                self.preemptions += 1
                if self.metrics is not None:
                    self.metrics.preemptions.inc()
        # the span the other lanes' flushes have (_flush), under whatever the
        # caller has open (a VoteSet.flush: `votes.flush`): no ticket, no wait
        with _trace.span("lane.flush", lanes="votes", rows=n, tickets=1) as sp:
            mask = self._inline(pubkeys, msgs, sigs, key_types, sources)
            sp.set(flushes=1)
        wall = time.monotonic() - t0
        with self._cv:
            self.flush_seq += 1
            st = self._lanes["votes"]
            st.flushes += 1
            st.rows_total += n
            self.flush_rows_last = {"votes": n}
            self.flush_log.append({
                "seq": self.flush_seq, "t": t0, "wall_s": wall,
                "rows": {"votes": n}, "wait_s": {"votes": 0.0},
                "error": None,
            })
        self.wait_stats.observe("votes", 0.0)
        if self.metrics is not None:
            self.metrics.lane_wait.labels("votes").observe(0.0)
            self.metrics.lane_flush_rows.labels("votes").observe(n)
        if self.slo is not None:
            self.slo.observe("verify_lane_wait_votes", 0.0)
        return mask

    def _wait_or_fallback(self, ticket: Ticket, rows) -> np.ndarray:
        """Block for the ticket's verdict, at most `wait_timeout` wherever an
        inline verify on the caller's thread could do better: a ticket still
        QUEUED leaves the lane, and so does one riding a long flush (a cold
        compile, the recovery ladder of somebody else's rows) if its inline
        copy runs on the host (`_inline_on_host`: a light-client or CheckTx
        ticket of a few rows). A ticket in flight whose inline copy would
        take the device path is waited for: the second verify of its rows
        would only queue behind the same compile and the same device."""
        while True:
            try:
                return ticket.wait(self.wait_timeout)
            except TimeoutError:
                with self._cv:
                    # dequeue the abandoned ticket: its consumer is about to
                    # verify inline, so flushing these rows later would be
                    # pure duplicate work nobody reads
                    st = self._lanes[ticket.lane]
                    queued = next((e for e in st.queue if e[0] is ticket), None)
                    if queued is not None:
                        st.queue.remove(queued)
                        st.rows -= ticket.rows
                    in_flight = queued is None
                    if not in_flight or self._inline_on_host(ticket.rows):
                        self.fallbacks += 1
                        break
                logger.info(
                    "verify lane %s ticket (%d rows) is in a flush that has run "
                    "past the %.0fs wait timeout; its inline copy would ride "
                    "the same device: waiting for it",
                    ticket.lane, ticket.rows, self.wait_timeout,
                )
        logger.warning(
            "verify lane %s ticket (%d rows) missed the %.0fs wait timeout "
            "(%s); verifying inline on the caller's thread",
            ticket.lane, ticket.rows, self.wait_timeout,
            "in a flush still running" if in_flight else "still queued",
        )
        return self._inline(*rows)

    def _inline_on_host(self, n: int) -> bool:
        """Would an inline verify_batch of `n` rows run on the host, free of
        the device and of whatever holds it? The routing rule's answer
        (batch._flush_route)."""
        from tendermint_tpu.crypto import batch as _batch

        return _batch._flush_route(n, self.backend).backend != "jax"

    def _inline(self, pubkeys, msgs, sigs, key_types,
                sources=None) -> np.ndarray:
        from tendermint_tpu.crypto import batch as _batch

        if sources is None:
            # keep the untagged call shape: tests stub verify_batch with
            # 5-arg fakes, and an untagged flush has nothing to score
            return _batch.verify_batch(
                pubkeys, msgs, sigs, self.backend, key_types
            )
        return _batch.verify_batch(pubkeys, msgs, sigs, self.backend, key_types,
                                   sources=sources)

    def accumulate(self, lane: str) -> LaneAccumulator:
        """A FlushAccumulator-compatible adapter whose flush() rides `lane`
        (install via crypto/batch.accumulate_flushes(acc=...))."""
        return LaneAccumulator(self, lane)

    @contextlib.contextmanager
    def lane_scope(self, lane: str):
        """While active on this thread, verify_batch / verify_batch_submit
        calls (and everything built on them: verify_commit,
        begin_verify_commit_light*, blocksync runs) route their rows
        through `lane` instead of dispatching their own flush."""
        if lane not in self._lanes:
            raise ValueError(f"unknown verify lane {lane!r}")
        prev = getattr(_TLS, "scope", None)
        _TLS.scope = (self, lane)
        try:
            yield self
        finally:
            _TLS.scope = prev

    # -- dispatch thread ------------------------------------------------------

    def _plan_locked(self):
        """Decide the next combined flush under the lock. Returns
        (entries, lanes, preempted, timeout_s): `entries` is the popped
        work (empty = nothing ready; sleep `timeout_s`)."""
        now = time.monotonic()
        votes = self._lanes["votes"]
        if votes.queue:
            # PREEMPT: the whole votes backlog flushes now, alone — bulk
            # rows never ride a vote flush (its wall is the vote path's)
            preempted = any(
                self._lanes[n].queue for n in LANES if n != "votes"
            )
            entries = list(votes.queue)
            votes.queue.clear()
            votes.rows = 0
            return entries, {"votes"}, preempted, None

        ready: List[str] = []
        next_deadline: Optional[float] = None
        bulk_pending = any(
            self._lanes[n].queue for n in ("votes", "light", "admission")
        )
        for lane in ("light", "admission", "catchup"):
            st = self._lanes[lane]
            if not st.queue:
                continue
            eff = self.effective_budget(lane)
            oldest = st.queue[0][0].enqueued_t
            wait = now - oldest
            if lane == "catchup":
                # idle-soak: ready when nothing hotter is queued; the
                # starvation floor keeps a busy node syncing regardless —
                # and bounds the pressure-level-2 pause too (a parked
                # consumer must flush before its wait_timeout inline
                # fallback, or the pause converts into duplicate inline
                # work on a starved executor thread)
                floor = eff.max_wait * CATCHUP_STARVATION_FACTOR
                if st.paused:
                    if wait >= floor:
                        ready.append(lane)
                    else:
                        dl = oldest + floor
                        next_deadline = dl if next_deadline is None else min(next_deadline, dl)
                    continue
                if not bulk_pending and (
                    wait >= eff.max_wait
                    or (eff.max_rows > 0 and st.rows >= eff.max_rows)
                ):
                    ready.append(lane)
                elif wait >= floor:
                    ready.append(lane)
                else:
                    dl = oldest + (floor if bulk_pending else eff.max_wait)
                    next_deadline = dl if next_deadline is None else min(next_deadline, dl)
                continue
            if (eff.max_rows > 0 and st.rows >= eff.max_rows) or wait >= eff.max_wait:
                ready.append(lane)
            else:
                dl = oldest + eff.max_wait
                next_deadline = dl if next_deadline is None else min(next_deadline, dl)
        # Quarantine: suspect rows flush ALONE, and only when every other
        # lane is drained — a poisoned flood's bisection recoveries can
        # never ride, or be ridden by, clean work. The starvation floor
        # (same factor as catch-up) bounds how long a suspect consumer
        # blocks, so parole stays reachable and the wait_timeout inline
        # fallback stays the backstop, not the norm.
        qst = self._lanes["quarantine"]
        if qst.queue:
            eff = self.effective_budget("quarantine")
            oldest = qst.queue[0][0].enqueued_t
            wait = now - oldest
            floor = eff.max_wait * CATCHUP_STARVATION_FACTOR
            others = bulk_pending or bool(self._lanes["catchup"].queue)
            triggered = (not others and not ready) and (
                wait >= eff.max_wait
                or (eff.max_rows > 0 and qst.rows >= eff.max_rows)
            )
            if triggered or wait >= floor:
                entries = []
                taken_rows = 0
                while qst.queue:
                    if eff.max_rows > 0 and taken_rows >= eff.max_rows:
                        break
                    entry = qst.queue.popleft()
                    qst.rows -= entry[0].rows
                    taken_rows += entry[0].rows
                    entries.append(entry)
                return entries, {"quarantine"}, False, None
            dl = oldest + (floor if (others or ready) else eff.max_wait)
            next_deadline = dl if next_deadline is None else min(next_deadline, dl)
        if not ready:
            timeout = None if next_deadline is None else max(0.0, next_deadline - now)
            return [], set(), False, timeout

        # Combined flush: the trigger lane(s) plus a ride-along drain of the
        # other bulk lanes up to their row budgets — rows that would flush
        # within one window anyway share this one. Catch-up never rides a
        # busy flush (idle-soak only); it IS the flush only when it triggered.
        take = set(ready)
        for lane in ("light", "admission"):
            if self._lanes[lane].queue:
                take.add(lane)
        entries = []
        lanes_taken = set()
        for lane in ("light", "admission", "catchup"):
            if lane not in take:
                continue
            st = self._lanes[lane]
            eff = self.effective_budget(lane)
            taken_rows = 0
            while st.queue:
                if eff.max_rows > 0 and taken_rows >= eff.max_rows:
                    break
                entry = st.queue.popleft()
                st.rows -= entry[0].rows
                taken_rows += entry[0].rows
                entries.append(entry)
                lanes_taken.add(lane)
        return entries, lanes_taken, False, None

    def _run(self) -> None:
        while True:
            q_entries: list = []
            with self._cv:
                entries: list = []
                while not self._closed:
                    entries, lanes, preempted, timeout = self._plan_locked()
                    if entries:
                        break
                    self._cv.wait(timeout=timeout)
                if self._closed:
                    # drain everything still queued in one final pass so no
                    # consumer blocks into its fallback timeout on teardown
                    # (quarantined rows still flush separately: the
                    # isolation invariant holds through teardown too)
                    entries = []
                    lanes, preempted = set(), False
                    for lane in LANES:
                        st = self._lanes[lane]
                        if st.queue:
                            if lane == "quarantine":
                                q_entries = list(st.queue)
                            else:
                                lanes.add(lane)
                                entries.extend(st.queue)
                        st.queue.clear()
                        st.rows = 0
                if preempted:
                    self.preemptions += 1
                    if self.metrics is not None:
                        self.metrics.preemptions.inc()
                closed = self._closed
            if entries:
                self._flush(entries, lanes)
            if q_entries:
                self._flush(q_entries, {"quarantine"})
            if closed:
                return

    def _flush(self, entries: list, lanes: set) -> None:
        """One combined device flush for `entries` (dispatch-thread only).
        Slices the combined mask back per ticket — the FlushAccumulator
        recovery contract keeps each slice byte-identical to a standalone
        verify_batch of that submit's rows."""
        from tendermint_tpu.crypto import batch as _batch

        t_flush = time.monotonic()
        t_flush_ns = time.perf_counter_ns()
        pubkeys: list = []
        msgs: list = []
        sigs: list = []
        key_types: list = []
        sources: list = []
        slices = []
        lane_rows: Dict[str, int] = {}
        lane_oldest: Dict[str, float] = {}
        for ticket, pk, ms, sg, kt, src in entries:
            start = len(pubkeys)
            pubkeys.extend(pk)
            msgs.extend(ms)
            sigs.extend(sg)
            key_types.extend(kt if kt is not None else ["ed25519"] * len(pk))
            # provenance: untagged rows sharing a flush with tagged ones
            # carry their consumer lane, so the suspicion scorer can always
            # attribute a failing row (crypto/provenance.py tag conventions)
            sources.extend(
                src if src is not None else [f"lane:{ticket.lane}"] * len(pk)
            )
            slices.append((ticket, start, len(pubkeys)))
            lane_rows[ticket.lane] = lane_rows.get(ticket.lane, 0) + ticket.rows
            prev = lane_oldest.get(ticket.lane)
            if prev is None or ticket.enqueued_t < prev:
                lane_oldest[ticket.lane] = ticket.enqueued_t
        kt_arg = key_types if any(t != "ed25519" for t in key_types) else None
        # an all-untagged flush passes sources=None: nothing to score, and
        # the untagged verify_batch call shape stays byte-for-byte the same
        src_arg = (
            sources if any(e[5] is not None for e in entries) else None
        )
        mask: Optional[np.ndarray] = None
        error: Optional[BaseException] = None
        # one tree per submitting call: the wait of a ticket that was
        # submitted under a span ends here, in that span's tree (a ticket
        # submitted under none, e.g. one a CheckTx, leaves no event: spans are
        # per call, never per row), and the flush, with every verify_batch
        # under it, hangs under the oldest ticket's span
        for ticket, _start, _end in slices:
            if ticket.parent is not None:
                _trace.interval("lane.wait", ticket.t0_ns, t_flush_ns,
                                parent=ticket.parent, lane=ticket.lane, rows=ticket.rows)
        try:
            with _trace.span(
                "lane.flush", parent=slices[0][0].parent,
                lanes=",".join(sorted(lanes)), rows=len(pubkeys), tickets=len(slices),
            ) as sp:
                mask, flushes = self._verify_chunked(pubkeys, msgs, sigs, kt_arg, src_arg)
                sp.set(flushes=flushes)
        except BaseException as e:  # tickets re-raise; the thread survives
            error = e
            logger.exception(
                "scheduler flush failed (%d rows, lanes %s)",
                len(pubkeys), sorted(lanes),
            )
        wall_s = time.monotonic() - t_flush
        with self._cv:
            self.flush_seq += 1
            seq = self.flush_seq
            self.flush_rows_last = dict(lane_rows)
            self.flush_log.append({
                "seq": seq,
                "t": t_flush,
                "wall_s": wall_s,
                "rows": dict(lane_rows),
                "wait_s": {
                    lane: t_flush - t0 for lane, t0 in lane_oldest.items()
                },
                "error": repr(error) if error is not None else None,
            })
            for lane in lane_rows:
                st = self._lanes[lane]
                st.flushes += 1
                st.rows_total += lane_rows[lane]
                if self.metrics is not None:
                    self.metrics.lane_depth.labels(lane).set(st.rows)
        for lane, rows in lane_rows.items():
            wait = t_flush - lane_oldest[lane]
            self.wait_stats.observe(lane, wait)
            if self.metrics is not None:
                self.metrics.lane_wait.labels(lane).observe(wait)
                self.metrics.lane_flush_rows.labels(lane).observe(rows)
            if self.slo is not None:
                self.slo.observe(f"verify_lane_wait_{lane}", wait)
        for ticket, start, end in slices:
            ticket.flush_seq = seq
            ticket.wait_s = t_flush - ticket.enqueued_t
            ticket._resolve(mask[start:end] if mask is not None else None, error)

    def _verify_chunked(self, pubkeys, msgs, sigs, kt_arg,
                        sources=None) -> tuple:
        """The dispatch thread's verify body: an oversized combined flush
        (catch-up super-batches, admission floods) splits into flush-planner
        chunks (crypto/batch._planner_chunks: row spans of at most
        planner_chunk_rows) with a PREEMPTION POINT between chunks — vote
        rows that queued while a chunk ran flush next, alone, before the
        following chunk. A vote flush therefore waits at most ONE chunk,
        never a 200k-lane monolith; verdict slices stay byte-identical
        (chunk masks concatenate in row order, and each chunk rides the
        normal verify_batch ladder). Returns (mask, verify_batch calls made:
        one a chunk, `flushes` on the `lane.flush` span)."""
        from tendermint_tpu.crypto import batch as _batch

        chunks = _batch._planner_chunks(len(pubkeys)) or [(0, 0)]
        parts = []
        for lo, hi in chunks:
            if lo:
                self._preempt_votes_between_chunks()
            # an untagged flush keeps the untagged call shape: tests stub
            # verify_batch with 5-arg fakes, and it has nothing to score
            kw = {} if sources is None else {"sources": sources[lo:hi]}
            parts.append(
                _batch.verify_batch(
                    pubkeys[lo:hi], msgs[lo:hi], sigs[lo:hi], self.backend,
                    kt_arg[lo:hi] if kt_arg is not None else None, **kw,
                )
            )
        return (parts[0] if len(parts) == 1 else np.concatenate(parts)), len(parts)

    def _preempt_votes_between_chunks(self) -> None:
        """Between-chunk preemption point (dispatch thread only): drain any
        queued vote rows into their own flush before the next bulk chunk."""
        with self._cv:
            st = self._lanes["votes"]
            if not st.queue:
                return
            entries = list(st.queue)
            st.queue.clear()
            st.rows = 0
            self.preemptions += 1
            if self.metrics is not None:
                self.metrics.preemptions.inc()
        self._flush(entries, {"votes"})

    # -- introspection / lifecycle --------------------------------------------

    def stats(self) -> dict:
        """The `scheduler` block of GET /debug/verify_stats (see
        docs/SCHEDULER.md for the field list)."""
        with self._cv:
            lanes = {}
            for name in LANES:
                st = self._lanes[name]
                eff = self.effective_budget(name)
                base = self._base[name]
                lanes[name] = {
                    "depth_rows": st.rows,
                    "queued_submits": len(st.queue),
                    "flushes": st.flushes,
                    "rows_total": st.rows_total,
                    "paused": st.paused,
                    "budget": {
                        "max_rows": base.max_rows,
                        "max_wait_s": base.max_wait,
                        "effective_max_rows": eff.max_rows,
                        "effective_max_wait_s": eff.max_wait,
                    },
                }
            out = {
                "enabled": True,
                "closed": self._closed,
                "backend": self.backend or "auto",
                "pressure_level": self.pressure_level,
                "flushes": self.flush_seq,
                "preemptions": self.preemptions,
                "inline_fallbacks": self.fallbacks,
                "last_flush_rows": dict(self.flush_rows_last),
                "lanes": lanes,
            }
        out["lane_wait_percentiles"] = self.wait_stats.percentiles()
        # cross-flush verified-row memo (crypto/batch.py ISSUE 18): every
        # lane consults it before joining the combined flush, so light
        # serving and blocksync catch-up reuse each other's verdicts — the
        # hit/eviction counters belong on the same debug surface
        from tendermint_tpu.crypto import batch as _batch

        out["verified_memo"] = _batch.verified_memo_stats()
        # Elastic mesh (ISSUE 19): the ladder rung every queued flush will
        # route through — a scheduler serving from a survivor mesh (or
        # single-chip after a mesh trip) should say so on the same surface
        # its lane waits are judged on.
        try:
            out["mesh_ladder"] = _batch.mesh_ladder_state()
        except Exception:
            out["mesh_ladder"] = None
        # Adversarial flush defense (crypto/provenance.py): which sources
        # are quarantined / closest to it, on the same surface operators
        # already read lane health from.
        try:
            from tendermint_tpu.crypto import provenance as _prov

            out["suspicion"] = _prov.default_scorer().stats()
        except Exception:
            out["suspicion"] = None
        return out

    def close(self) -> None:
        """Stop the dispatch thread after one final drain; later submits
        return None and consumers verify inline."""
        with self._cv:
            if self._closed:
                return
            self._closed = True
            self._cv.notify_all()
        self._thread.join(timeout=10.0)

    @property
    def closed(self) -> bool:
        return self._closed


# -- lane-scope routing (crypto/batch hook) ------------------------------------

_TLS = threading.local()


def _route_rows(pubkeys, msgs, sigs, backend, key_types, sources=None):
    """crypto/batch's lane router: verify_batch consults this at entry and,
    when the calling thread sits inside a lane_scope, routes the rows
    through that scheduler lane. Returns None (= route normally) outside a
    scope, for a closed scheduler, and for the scheduler's own dispatch
    flush (the scope is cleared around verify_rows)."""
    scope = getattr(_TLS, "scope", None)
    if scope is None:
        return None
    sched, lane = scope
    if sched.closed:
        return None
    _TLS.scope = None  # the inline fallback must not re-enter the router
    try:
        return sched.verify_rows(lane, pubkeys, msgs, sigs, key_types, sources)
    finally:
        _TLS.scope = scope


_ROUTER_INSTALLED = False


def _install_router() -> None:
    global _ROUTER_INSTALLED
    if _ROUTER_INSTALLED:
        return
    from tendermint_tpu.crypto import batch as _batch

    _batch.set_lane_router(_route_rows)
    _ROUTER_INSTALLED = True


# -- process-global default ----------------------------------------------------
#
# Deep consumers (types/vote_set.py, evidence/pool.py) have no wiring path
# from the Node; they read the process-global default — last node wins, the
# same model as the tracer, the SLO flush feed, and the breaker config.

_DEFAULT: Optional[VerifyScheduler] = None


def set_default(sched: Optional[VerifyScheduler]) -> None:
    global _DEFAULT
    _DEFAULT = sched


def default_scheduler() -> Optional[VerifyScheduler]:
    """The live process-global scheduler, or None (closed schedulers read
    as None so a stopped node never wedges a survivor's consumers)."""
    s = _DEFAULT
    if s is None or s.closed:
        return None
    return s
