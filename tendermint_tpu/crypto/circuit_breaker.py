"""Verify-path circuit breaker: persistent device failure trips TPU -> CPU.

The batch pipeline already has a *per-flush* degradation ladder (RLC -> per-sig
-> CPU inside crypto/batch.py), but before this module a persistently sick
device was re-tried on EVERY flush: each one paid the device submit, the
exception, and the CPU fallback — a retry storm that added the device timeout
to every consensus round. The breaker makes the degradation *sticky*:

    CLOSED ──(threshold consecutive device failures
              or flush-deadline overruns)──> OPEN
    OPEN ──(probe scheduled after exponential backoff)──> HALF_OPEN
    HALF_OPEN ──probe passes──> CLOSED   /  probe fails──> OPEN (backoff *= 2)

While OPEN (or HALF_OPEN), `allow_device()` is False and crypto/batch routes
default-"jax" verification straight to the host loop — no device work at all,
so a dead device costs exactly one failed flush. A background daemon thread
probes the device with exponential backoff (base..max, configured via
`[crypto] breaker_probe_base/max`) and re-arms the TPU path when a probe
passes. State + trip counters ride /metrics (tendermint_batch_verify_breaker_*)
and /debug/verify_stats (the `breaker` block).

No reference counterpart — the reference's serial host loop
(types/validator_set.go:680) has no device to break away from. The pattern is
the standard Nygard circuit breaker, applied to an accelerator dependency.
"""

from __future__ import annotations

import logging
import threading
import time
from typing import Callable, Optional

logger = logging.getLogger("tendermint_tpu.crypto.breaker")

CLOSED = "closed"
OPEN = "open"
HALF_OPEN = "half_open"

_STATE_GAUGE = {CLOSED: 0, OPEN: 1, HALF_OPEN: 2}


class VerifyCircuitBreaker:
    """Thread-safe; shared by the consensus event loop, the prewarm thread,
    and its own probe thread."""

    def __init__(
        self,
        *,
        enabled: bool = True,
        failure_threshold: int = 3,
        flush_deadline_s: float = 0.0,
        probe_interval_base: float = 1.0,
        probe_interval_max: float = 60.0,
        probe: Optional[Callable[[], None]] = None,
        clock: Callable[[], float] = time.monotonic,
        spawn_probe_thread: bool = True,
    ):
        self._lock = threading.RLock()
        self.enabled = enabled
        self.failure_threshold = max(1, int(failure_threshold))
        self.flush_deadline_s = float(flush_deadline_s)
        self.probe_interval_base = float(probe_interval_base)
        self.probe_interval_max = float(probe_interval_max)
        self._probe = probe  # raises on an unhealthy device
        self._clock = clock
        self._spawn_probe_thread = spawn_probe_thread
        self.state = CLOSED
        self._consec_failures = 0
        self._consec_overruns = 0
        self._trips = {}  # reason -> count
        self._last_error: Optional[str] = None
        self._opened_at: Optional[float] = None
        self._probe_backoff = self.probe_interval_base
        self._probe_thread: Optional[threading.Thread] = None
        self._probe_wakeup = threading.Event()
        # Per-backend rungs (ISSUE 19): named sub-breakers below the global
        # device gate — e.g. "mesh" covers the sharded multi-chip path, so a
        # sick MESH degrades to single-chip while allow_device() stays True.
        self._backends: dict = {}  # name -> state dict

    # -- config / lifecycle -------------------------------------------------

    def configure(
        self,
        *,
        enabled: Optional[bool] = None,
        failure_threshold: Optional[int] = None,
        flush_deadline_s: Optional[float] = None,
        probe_interval_base: Optional[float] = None,
        probe_interval_max: Optional[float] = None,
    ) -> None:
        """Apply `[crypto]` config (node/node.py). Keeps current state except
        that disabling re-closes an open breaker."""
        with self._lock:
            if failure_threshold is not None:
                self.failure_threshold = max(1, int(failure_threshold))
            if flush_deadline_s is not None:
                self.flush_deadline_s = float(flush_deadline_s)
            if probe_interval_base is not None:
                self.probe_interval_base = float(probe_interval_base)
            if probe_interval_max is not None:
                self.probe_interval_max = float(probe_interval_max)
            if enabled is not None:
                self.enabled = bool(enabled)
                if not self.enabled and self.state != CLOSED:
                    self._close_locked()
        self._probe_wakeup.set()  # wake a sleeping probe loop to re-check

    def set_probe(self, probe: Optional[Callable[[], None]]) -> None:
        self._probe = probe

    def reset(self) -> None:
        """Force-close and zero counters (tests, operator reset)."""
        with self._lock:
            self._close_locked()
            self._trips.clear()
            self._last_error = None
            self._backends.clear()
        self._probe_wakeup.set()  # let the probe loop notice and exit now

    # -- the hot-path gate --------------------------------------------------

    def allow_device(self) -> bool:
        """One cheap read on every flush: True routes to the device, False
        means the caller must use the CPU path without touching the device."""
        return (not self.enabled) or self.state == CLOSED

    # -- outcome recording --------------------------------------------------

    def record_success(self, duration_s: Optional[float] = None) -> None:
        if not self.enabled:
            return
        with self._lock:
            if (
                duration_s is not None
                and self.flush_deadline_s > 0
                and duration_s > self.flush_deadline_s
            ):
                self._consec_overruns += 1
                self._consec_failures = 0
                if (
                    self.state == CLOSED
                    and self._consec_overruns >= self.failure_threshold
                ):
                    # CLOSED guard: a straggler flush submitted before a trip
                    # must not re-trip an already-open breaker (double-counted
                    # trips + a probe-backoff reset mid-escalation)
                    self._trip_locked("flush_deadline", f"flush took {duration_s:.3f}s")
                return
            self._consec_failures = 0
            self._consec_overruns = 0

    def record_failure(self, error: str = "") -> None:
        if not self.enabled:
            return
        with self._lock:
            self._last_error = error or "device call failed"
            self._consec_failures += 1
            if self.state == CLOSED and self._consec_failures >= self.failure_threshold:
                self._trip_locked("device_error", error)

    # -- per-backend states (ISSUE 19 elastic mesh) -------------------------
    #
    # The global CLOSED/OPEN pair above answers "may we touch the device AT
    # ALL"; these named rungs answer "may we use THIS path on the device".
    # Opening a backend never opens the global breaker: tripping "mesh"
    # routes sharded flushes to the single-chip fused path while
    # allow_device() stays True — the all-or-nothing trip becomes a ladder.

    def _backend_locked(self, name: str) -> dict:
        st = self._backends.get(name)
        if st is None:
            st = self._backends[name] = {
                "state": CLOSED,
                "consec_failures": 0,
                "trips": 0,
                "last_error": None,
                "opened_at": None,
                "backoff": self.probe_interval_base,
            }
        return st

    def allow_backend(self, name: str) -> bool:
        """Cheap per-flush gate for a named backend rung. False while that
        rung is open; after the rung's backoff elapses it half-opens, so
        exactly one trial flush re-tests the path (no dedicated prober:
        the trial IS the probe — its success/failure records below)."""
        if not self.enabled:
            return True
        with self._lock:
            st = self._backends.get(name)
            if st is None or st["state"] == CLOSED:
                return True
            if st["state"] == HALF_OPEN:
                return True
            if (
                st["opened_at"] is not None
                and self._clock() - st["opened_at"] >= st["backoff"]
            ):
                st["state"] = HALF_OPEN
                return True
            return False

    def record_backend_failure(self, name: str, error: str = "") -> bool:
        """One failure that is attributable to the BACKEND, not to a single
        device (e.g. an un-attributed mesh flush failure: every per-device
        probe passed, yet the collective call died). Trips the rung open at
        the same consecutive-failure threshold as the global breaker; a
        half-open trial failure re-opens immediately with doubled backoff.
        Returns True when this call tripped the rung."""
        if not self.enabled:
            return False
        with self._lock:
            st = self._backend_locked(name)
            st["last_error"] = error or "backend call failed"
            st["consec_failures"] += 1
            tripped = False
            if st["state"] == HALF_OPEN:
                st["state"] = OPEN
                st["opened_at"] = self._clock()
                st["backoff"] = min(st["backoff"] * 2, self.probe_interval_max)
                st["consec_failures"] = 0
            elif (
                st["state"] == CLOSED
                and st["consec_failures"] >= self.failure_threshold
            ):
                st["state"] = OPEN
                st["trips"] += 1
                st["consec_failures"] = 0
                st["opened_at"] = self._clock()
                st["backoff"] = self.probe_interval_base
                tripped = True
        if tripped:
            try:
                self._metrics().breaker_trips.labels(f"backend:{name}").inc()
            except Exception:
                pass
            logger.error(
                "verify backend %r tripped open: %s — degrading one rung "
                "(device path itself stays armed)", name, error or "n/a",
            )
        return tripped

    def record_backend_success(self, name: str) -> None:
        if not self.enabled:
            return
        with self._lock:
            st = self._backends.get(name)
            if st is None:
                return
            st["consec_failures"] = 0
            if st["state"] == HALF_OPEN:
                st["state"] = CLOSED
                st["opened_at"] = None
                st["backoff"] = self.probe_interval_base
                logger.warning("verify backend %r trial passed — re-armed", name)

    def open_backend(self, name: str, error: str = "") -> None:
        """Force a rung open (the mesh health model uses this when the
        healthy device count can no longer form a >= 2-chip mesh)."""
        with self._lock:
            st = self._backend_locked(name)
            if st["state"] != OPEN:
                st["state"] = OPEN
                st["trips"] += 1
                st["opened_at"] = self._clock()
            st["last_error"] = error or st["last_error"]

    def close_backend(self, name: str) -> None:
        """Re-arm a rung (health prober, after clean probes)."""
        with self._lock:
            st = self._backends.get(name)
            if st is None:
                return
            if st["state"] != CLOSED:
                logger.warning("verify backend %r re-armed", name)
            st["state"] = CLOSED
            st["consec_failures"] = 0
            st["opened_at"] = None
            st["backoff"] = self.probe_interval_base

    # -- state transitions --------------------------------------------------

    def _metrics(self):
        from tendermint_tpu.libs import metrics as _metrics

        return _metrics.batch_metrics()

    def _set_state_locked(self, state: str) -> None:
        self.state = state
        try:
            self._metrics().breaker_state.set(_STATE_GAUGE[state])
        except Exception:  # metrics must never break the verify path
            pass

    def _trip_locked(self, reason: str, error: str) -> None:
        self._trips[reason] = self._trips.get(reason, 0) + 1
        self._opened_at = self._clock()
        self._probe_backoff = self.probe_interval_base
        self._consec_failures = 0
        self._consec_overruns = 0
        self._set_state_locked(OPEN)
        try:
            self._metrics().breaker_trips.labels(reason).inc()
        except Exception:
            pass
        logger.error(
            "verify-path circuit breaker TRIPPED (%s): %s — routing "
            "verification to the CPU host loop until a health probe passes",
            reason, error or "n/a",
        )
        try:
            from tendermint_tpu.libs.trace import tracer

            if tracer.enabled:
                tracer.event("breaker.trip", reason=reason, error=error or None)
        except Exception:
            pass
        if self._spawn_probe_thread:
            self._start_probe_thread_locked()

    def _close_locked(self) -> None:
        self._consec_failures = 0
        self._consec_overruns = 0
        self._opened_at = None
        self._probe_backoff = self.probe_interval_base
        self._set_state_locked(CLOSED)

    # -- probing ------------------------------------------------------------

    def probe_now(self) -> bool:
        """One synchronous probe attempt; True iff it passed (breaker closes).
        Used by tests/bench and the probe thread."""
        probe = self._probe
        with self._lock:
            if self.state == CLOSED:
                return True
            self._set_state_locked(HALF_OPEN)
        ok, err = True, ""
        if probe is not None:
            try:
                probe()
            except Exception as e:
                ok, err = False, repr(e)
        with self._lock:
            try:
                self._metrics().breaker_probes.labels("pass" if ok else "fail").inc()
            except Exception:
                pass
            open_for = (
                round(self._clock() - self._opened_at, 3)
                if self._opened_at is not None
                else None
            )
            if ok:
                logger.warning(
                    "verify-path circuit breaker: health probe passed — "
                    "re-arming the device path"
                )
                self._close_locked()
            else:
                self._last_error = err
                self._probe_backoff = min(
                    self._probe_backoff * 2, self.probe_interval_max
                )
                self._set_state_locked(OPEN)
            next_backoff = self._probe_backoff
        # Flight-recorder events (same ring as the flush spans they explain:
        # /debug/trace interleaves breaker history with the degraded flushes)
        try:
            from tendermint_tpu.libs.trace import tracer

            if tracer.enabled:
                if ok:
                    tracer.event("breaker.rearm", open_for_s=open_for)
                else:
                    tracer.event(
                        "breaker.probe_fail",
                        reason=err or None,
                        next_backoff_s=next_backoff,
                        open_for_s=open_for,
                    )
        except Exception:
            pass
        return ok

    def _start_probe_thread_locked(self) -> None:
        if self._probe_thread is not None and self._probe_thread.is_alive():
            # A live loop serves the new trip too; the nudge covers the
            # window where it is mid-backoff (it re-checks state on wake).
            # The loop can only decide to EXIT (and clear _probe_thread)
            # under this same lock, so a thread seen alive here either
            # already cleared the slot (and we spawn below) or will observe
            # the new OPEN state at its next top-of-loop check — no
            # open-forever-with-no-prober window.
            self._probe_wakeup.set()
            return
        self._probe_wakeup.clear()
        self._probe_thread = threading.Thread(
            target=self._probe_loop, name="verify-breaker-probe", daemon=True
        )
        self._probe_thread.start()

    def _probe_loop(self) -> None:
        while True:
            with self._lock:
                if self.state == CLOSED or not self.enabled:
                    # exit decision and slot clear are atomic with the
                    # trip path's is_alive check (same lock)
                    self._probe_thread = None
                    return
                wait = self._probe_backoff
            # Event.wait instead of sleep: reset()/configure()/a re-trip
            # wake the loop early to re-check state
            if self._probe_wakeup.wait(wait):
                self._probe_wakeup.clear()
            with self._lock:
                if self.state == CLOSED or not self.enabled:
                    self._probe_thread = None
                    return
            try:
                self.probe_now()
            except Exception:  # a broken probe fn must not kill the loop
                logger.exception("breaker probe raised unexpectedly")

    # -- introspection ------------------------------------------------------

    def snapshot(self) -> dict:
        """The /debug/verify_stats `breaker` block."""
        with self._lock:
            return {
                "enabled": self.enabled,
                "state": self.state,
                "consecutive_failures": self._consec_failures,
                "consecutive_overruns": self._consec_overruns,
                "failure_threshold": self.failure_threshold,
                "flush_deadline_s": self.flush_deadline_s or None,
                "trips": dict(self._trips),
                "open_for_s": (
                    round(self._clock() - self._opened_at, 3)
                    if self._opened_at is not None and self.state != CLOSED
                    else None
                ),
                "probe_backoff_s": (
                    self._probe_backoff if self.state != CLOSED else None
                ),
                "last_error": self._last_error,
                "backends": {
                    name: {
                        "state": st["state"],
                        "consecutive_failures": st["consec_failures"],
                        "trips": st["trips"],
                        "open_for_s": (
                            round(self._clock() - st["opened_at"], 3)
                            if st["opened_at"] is not None and st["state"] != CLOSED
                            else None
                        ),
                        "last_error": st["last_error"],
                    }
                    for name, st in sorted(self._backends.items())
                },
            }
