"""Flight recorder for the batch-verify pipeline.

Lightweight nested spans and point events into a bounded, thread-safe ring
buffer with JSONL export — the tracing half of the observability story whose
metrics half lives in libs/metrics.py (BatchVerifyMetrics). The reference
wires per-service Prometheus metrics through every subsystem
(consensus/metrics.go, node/node.go:106-121) but has no in-process tracer;
this module exists because the single most important path here —
crypto/batch.py's device pipeline — fails in ways a counter can't localise
(BENCH_r05: `verify_commit_latency = -1`, "device initialization stalled",
with zero insight into WHICH stage stalled).

Three consumers:

- the `/debug/trace` RPC route (rpc/server.py) dumps the ring as JSON;
- `/debug/verify_stats` + bench.py's JSON `extra` read `verify_stats()`,
  the aggregated per-flush breakdown (prep / compile / transfer / total
  per path), so a regression names its stage instead of one opaque number;
- node liveness and the bench's stall detector read `device_health()`
  (device init duration, last-successful-device-call age, `device_up`).

Overhead contract: the instrumented hot paths open spans through the
module-level `span()` / `timed()` below. With `tracer.enabled` False `span()`
is one flag read and hands back ONE shared no-op context manager (no `Span`
is constructed, the ring is untouched); `timed()` hands back a bare
`perf_counter_ns` pair where the caller feeds the duration into the flush
record either way, so a stage is timed once whether the recorder is on or
off. The ring buffer never exceeds its configured size (deque maxlen).
Configure via `[instrumentation] trace_enabled / trace_ring_size`
(node/node.py) or the TMTPU_TRACE env default.

Every ring event carries `t0_ns` (`time.perf_counter_ns()` at entry; for a
point event, at the event) and `root`, the id of the outermost span open
when it began, so the spans of one request share an identifier. Work handed
to another thread nests under the submitting span with `span(name,
parent=<that span>)`; an interval that starts on one thread and ends on
another is written closed, with `interval(name, t0_ns, t1_ns, parent=...)`.
While a JAX profiler session is live each span is mirrored as
`jax.profiler.TraceAnnotation("tm:" + name)`, which puts the program's spans
on the host plane of the same `.xplane.pb`, on the profiler's clock, beside
the device plane.

While the recorder is on, one `gc.callbacks` hook times every collection of
the garbage collector into process totals by generation (`gc_stats()`,
`tendermint_process_gc_pause_seconds{generation}`), stamps the totals so far
(`gc_ms`, `gc_n`) on every root span as it closes, and leaves one
`gc.collect` span a FULL collection, under the span it interrupted. The hook
takes no lock and touches no ring: a collection can start on a thread that
holds the ring's lock (an allocation in `dump()`), so the span is handed over
and written by the next ordinary event. With the recorder off (`configure(
enabled=False)`) the hook is not registered at all.
"""

from __future__ import annotations

import gc
import itertools
import json
import os
import sys
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional

DEFAULT_RING_SIZE = 4096
ANNOTATION_PREFIX = "tm:"

_perf_ns = time.perf_counter_ns

# jax.profiler.TraceAnnotation once JAX is loaded (False: looked for and not
# usable). A host-only process never imports JAX to trace.
_ANNOTATION: Any = None


def _live_annotation():
    """TraceAnnotation while a profiler session is live, else None. The
    check is TraceMe's own (it asks the profiler, whoever started it), 0.05
    us a span against 0.3 us for an annotation nobody records."""
    global _ANNOTATION
    ann = _ANNOTATION
    if ann is None:
        if "jax" not in sys.modules:
            return None
        try:
            from jax.profiler import TraceAnnotation as ann
        except Exception:  # JAX still loading: ask again at the next span
            return None
        # an older JAX without is_enabled: no mirror, the ring is unaffected
        _ANNOTATION = ann = ann if hasattr(ann, "is_enabled") else False
    return ann if ann and ann.is_enabled() else None


class _Interval:
    """What Span and Stopwatch share: a start and an end on
    `perf_counter_ns`, handed back in `perf_counter`'s seconds so they can
    stand beside bare `time.perf_counter()` readings."""

    __slots__ = ()

    @property
    def seconds(self) -> float:
        return (self.t1_ns - self.t0_ns) / 1e9

    def interval(self) -> tuple:
        return (self.t0_ns / 1e9, self.t1_ns / 1e9)

    def elapsed(self) -> float:
        """Seconds since entry, for a span that is still open."""
        return (_perf_ns() - self.t0_ns) / 1e9


class Span(_Interval):
    """An in-flight span; records one event into the tracer's ring on exit.

    `set(**attrs)` attaches attributes mid-flight (e.g. the chosen path,
    known only at the end of a flush). `parent` is a Span of another thread
    (or one already closed): this span takes its id as parent and its root."""

    __slots__ = ("_tracer", "name", "attrs", "span_id", "parent_id", "root",
                 "t0_ns", "t1_ns", "_parent", "_ann")
    recording = True

    def __init__(self, tracer: "Tracer", name: str, attrs: Dict[str, Any],
                 parent: "Optional[Span]" = None):
        self._tracer = tracer
        self.name = name
        self.attrs = attrs
        self.span_id = tracer._next_id()
        self._parent = parent if parent is not None and parent.recording else None
        self.parent_id: Optional[int] = None
        self.root = self.span_id
        self.t0_ns = self.t1_ns = 0
        self._ann = None

    def set(self, **attrs) -> "Span":
        self.attrs.update(attrs)
        return self

    def began_at(self, t0_ns: int) -> "Span":
        """Moves an open span's start back to an earlier reading of
        `perf_counter_ns`: for a root whose first stage ran across awaits,
        where no thread's stack may hold a span open (another task's spans
        would nest under it, and close out of order). The stage itself is
        written with `interval(..., parent=root)`."""
        self.t0_ns = t0_ns
        return self

    def __enter__(self) -> "Span":
        stack = self._tracer._stack()
        parent = self._parent or (stack[-1] if stack else None)
        if parent is not None:
            self.parent_id, self.root = parent.span_id, parent.root
        stack.append(self)
        ann = _live_annotation()
        if ann is not None:
            self._ann = ann(ANNOTATION_PREFIX + self.name)
            self._ann.__enter__()
        self.t0_ns = _perf_ns()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.t1_ns = _perf_ns()
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
        stack = self._tracer._stack()
        if stack and stack[-1] is self:
            stack.pop()
        if exc_type is not None:
            self.attrs.setdefault("error", exc_type.__name__)
        self._tracer._record(
            self.name, self.span_id, self.parent_id, self.root, self.t0_ns,
            (self.t1_ns - self.t0_ns) / 1e9, self.attrs,
            # a root carries the collector's totals so far, monotone: what
            # the calls between two roots paid is the difference
            (sum(_GC_NS), sum(_GC_N)) if self.parent_id is None and _GC_HOOKED else None,
        )


class Stopwatch(_Interval):
    """`timed()` with the recorder off: the bare clock pair, nothing else."""

    __slots__ = ("t0_ns", "t1_ns")
    recording = False

    def __init__(self):
        self.t0_ns = self.t1_ns = 0

    def set(self, **attrs) -> "Stopwatch":
        return self

    def __enter__(self) -> "Stopwatch":
        self.t0_ns = _perf_ns()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.t1_ns = _perf_ns()


class _NoopSpan:
    """`span()` with the recorder off: one shared object, no state."""

    __slots__ = ()
    recording = False

    def set(self, **attrs) -> "_NoopSpan":
        return self

    def began_at(self, t0_ns: int) -> "_NoopSpan":
        return self

    def __enter__(self) -> "_NoopSpan":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        return None


NOOP = _NoopSpan()


class Tracer:
    """Thread-safe bounded flight recorder: nested spans + point events."""

    def __init__(self, ring_size: int = DEFAULT_RING_SIZE, enabled: bool = True):
        self.enabled = enabled
        self._lock = threading.Lock()
        self._ring: deque = deque(maxlen=max(1, int(ring_size)))
        self._local = threading.local()
        self._ids = itertools.count(1)  # next() is atomic: no lock a span
        # full collections the GC hook handed over, not yet in the ring
        self._gc_pending: list = []

    # -- recording ----------------------------------------------------------

    def span(self, name: str, parent: Optional[Span] = None, **attrs) -> Span:
        return Span(self, name, attrs, parent)

    def event(self, name: str, **attrs) -> None:
        """A zero-duration point event, parented to the current span."""
        stack = self._stack()
        span_id = self._next_id()
        top = stack[-1] if stack else None
        self._record(
            name, span_id, top.span_id if top else None,
            top.root if top else span_id, _perf_ns(), None, attrs,
        )

    def current(self) -> Optional[Span]:
        """This thread's innermost open span: what a task handed to another
        thread names as its `parent`."""
        stack = self._stack()
        return stack[-1] if stack else None

    # -- introspection ------------------------------------------------------

    def dump(self, limit: Optional[int] = None) -> List[dict]:
        """Ring contents, oldest first (most recent `limit` if given)."""
        self._take_gc()
        with self._lock:
            events = list(self._ring)
        if limit is not None and limit >= 0:
            events = events[-limit:] if limit else []
        return events

    def to_jsonl(self) -> str:
        return "\n".join(json.dumps(e, sort_keys=True) for e in self.dump())

    @staticmethod
    def from_jsonl(text: str) -> List[dict]:
        return [json.loads(line) for line in text.splitlines() if line.strip()]

    @property
    def ring_size(self) -> int:
        return self._ring.maxlen or 0

    def clear(self) -> None:
        self._gc_pending.clear()
        with self._lock:
            self._ring.clear()

    def configure(
        self, enabled: Optional[bool] = None, ring_size: Optional[int] = None
    ) -> None:
        """Apply [instrumentation] config; shrinking keeps the newest events."""
        with self._lock:
            if ring_size is not None and ring_size != self._ring.maxlen:
                self._ring = deque(self._ring, maxlen=max(1, int(ring_size)))
        if enabled is not None:
            self.enabled = bool(enabled)
            if self is tracer:
                _hook_gc(self.enabled)

    # -- internals ----------------------------------------------------------

    def _next_id(self) -> int:
        return next(self._ids)

    def _stack(self) -> list:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def _record(self, name, span_id, parent_id, root, t0_ns, dur_s, attrs,
                gc_total=None) -> None:
        event = _event(name, span_id, parent_id, root, t0_ns, dur_s, attrs)
        if gc_total is not None:
            event["gc_ms"] = round(gc_total[0] / 1e6, 4)
            event["gc_n"] = gc_total[1]
        if self._gc_pending:
            self._take_gc()
        with self._lock:
            self._ring.append(event)

    def _take_gc(self) -> None:
        """Writes the full collections the GC hook handed over, each as the
        `gc.collect` span it was: outside the hook, where a lock may be taken."""
        events = []
        pending = self._gc_pending
        while pending:
            try:
                t0_ns, dur_ns, top, gen, collected, uncollectable = pending.pop(0)
            except IndexError:  # another thread took the last one
                break
            span_id = self._next_id()
            events.append(_event(
                "gc.collect", span_id, top.span_id if top else None,
                top.root if top else span_id, t0_ns, dur_ns / 1e9,
                {"generation": gen, "collected": collected,
                 "uncollectable": uncollectable},
            ))
        if events:
            with self._lock:
                self._ring.extend(events)


def _event(name, span_id, parent_id, root, t0_ns, dur_s, attrs) -> dict:
    event = {
        "name": name,
        "span": span_id,
        "parent": parent_id,
        "root": root,
        "t0_ns": t0_ns,
        "ts": time.time(),
    }
    if dur_s is not None:
        event["dur_ms"] = round(dur_s * 1e3, 4)
    if attrs:
        event["attrs"] = dict(attrs)
    return event


# -- garbage-collector pauses --------------------------------------------------
# Process totals by generation, written by the hook alone: nanoseconds and
# collections.
_GC_NS = [0, 0, 0]
_GC_N = [0, 0, 0]
_GC_OPEN: list = [0, None]  # the running collection: its start, its tm: mirror
_GC_HOOKED = False


def _on_gc(phase: str, info: dict) -> None:
    """gc.callbacks hook. Runs on whatever thread allocated, maybe one that
    holds the ring's lock: it takes no lock, imports nothing and writes plain
    numbers; a full collection is handed to the recorder as a tuple."""
    if phase == "start":
        _GC_OPEN[0] = _perf_ns()
        ann = _ANNOTATION
        if info["generation"] == 2 and ann and ann.is_enabled():
            _GC_OPEN[1] = ann(ANNOTATION_PREFIX + "gc.collect")
            _GC_OPEN[1].__enter__()
        return
    t1 = _perf_ns()
    t0 = _GC_OPEN[0]
    gen = info["generation"]
    _GC_NS[gen] += t1 - t0
    _GC_N[gen] += 1
    ann, _GC_OPEN[1] = _GC_OPEN[1], None
    if ann is not None:
        ann.__exit__(None, None, None)
    t = tracer
    if gen == 2 and t.enabled:
        stack = getattr(t._local, "stack", None)
        t._gc_pending.append((t0, t1 - t0, stack[-1] if stack else None, gen,
                              info["collected"], info["uncollectable"]))


def _hook_gc(on: bool) -> None:
    """The hook in `gc.callbacks` while the recorder is on, and not at all
    while it is off."""
    global _GC_HOOKED
    if on and _on_gc not in gc.callbacks:
        gc.callbacks.append(_on_gc)
    elif not on:
        while _on_gc in gc.callbacks:
            gc.callbacks.remove(_on_gc)
    _GC_HOOKED = _on_gc in gc.callbacks


def gc_stats() -> dict:
    """Collections and their seconds by generation since the process started,
    counted while the recorder was on."""
    return {
        "hooked": _GC_HOOKED,
        "generations": {
            str(g): {"collections": _GC_N[g], "seconds": _GC_NS[g] / 1e9}
            for g in range(3)
        },
    }


def fill_gc_series(process_metrics) -> None:
    """`tendermint_process_gc_pause_seconds{generation}`, read at scrape time
    (libs/metrics.py ProcessMetrics)."""
    process_metrics.gc_pause_seconds.replace_series(
        {(str(g),): _GC_NS[g] / 1e9 for g in range(3)}
    )


tracer = Tracer(enabled=os.environ.get("TMTPU_TRACE", "1") != "0")
_hook_gc(tracer.enabled)


def span(name: str, parent=None, **attrs):
    """A span of the process's recorder, or the shared no-op when it is off:
    `with trace.span("flush.sync", chunk=k): ...` is the whole call site."""
    t = tracer
    if not t.enabled:
        return NOOP
    return Span(t, name, attrs, parent)


def timed(name: str, parent=None, **attrs):
    """`span()` for a stage whose duration also feeds the flush record: a
    Span when the recorder is on, a bare Stopwatch when it is off. Either
    way `.seconds` / `.interval()` after the block are the one timing."""
    t = tracer
    if not t.enabled:
        return Stopwatch()
    return Span(t, name, attrs, parent)


def interval(name: str, t0_ns: int, t1_ns: int, parent=None, **attrs) -> None:
    """A closed span from two readings of `perf_counter_ns`, for an interval
    that begins on one thread and ends on another (a ticket's wait in a
    scheduler lane: submit on the caller's thread, flush start on the
    dispatch thread), so no thread's stack can hold it open. It nests under
    `parent` as `span(name, parent=...)` does; with the recorder off it is
    one flag read. It has no `tm:` mirror: an annotation cannot be opened in
    the past."""
    t = tracer
    if not t.enabled:
        return
    span_id = t._next_id()
    if parent is not None and parent.recording:
        parent_id, root = parent.span_id, parent.root
    else:
        parent_id, root = None, span_id
    t._record(name, span_id, parent_id, root, t0_ns, (t1_ns - t0_ns) / 1e9, attrs)


class Since:
    """The open start of an interval that ends in another call, maybe of
    another thread (`since()`): its reading of `perf_counter_ns` and, while a
    profiler session is live, its `tm:` mirror, opened now. `end()` closes
    the mirror and returns the end's reading; the interval is then written
    closed with `interval()`. A TraceMe is written by the thread that closes
    it, so a mirror closed on another thread lies on that thread's line."""

    __slots__ = ("t0_ns", "_ann")

    def __init__(self, name: str):
        ann = _live_annotation()
        self._ann = None
        if ann is not None:
            self._ann = ann(ANNOTATION_PREFIX + name)
            self._ann.__enter__()
        self.t0_ns = _perf_ns()

    def end(self) -> int:
        t1_ns = _perf_ns()
        ann, self._ann = self._ann, None
        if ann is not None:
            ann.__exit__(None, None, None)
        return t1_ns


def since(name: str) -> Optional[Since]:
    """The start of an interval whose end a later call reads (the first vote
    queued to the flush that takes it); None when the recorder is off: one
    flag read."""
    return Since(name) if tracer.enabled else None


def current():
    """The calling thread's innermost open span (None when the recorder is
    off or nothing is open): pass it as `parent=` to a task of another
    thread."""
    t = tracer
    return t.current() if t.enabled else None


# ---------------------------------------------------------------------------
# Aggregated per-flush telemetry (the /debug/verify_stats + bench `extra`
# surface) and device-health state (the `device_up` surface). Both also feed
# the process-global Prometheus series (libs.metrics.batch_metrics) so the
# node's /metrics exposition carries them without any node->crypto plumbing.

_STATS_LOCK = threading.Lock()
_TOTALS: Dict[tuple, Dict[str, float]] = {}  # (backend, path) -> counters
_LAST_FLUSH: Dict[str, Any] = {}
_COUNTS = {
    "rlc_fallbacks": 0,
    "cache_hits": 0,
    "cache_misses": 0,
    "recovery_flushes": 0,
    "quarantined_rows": 0,
}
_STAGE_SECONDS = {"prep": 0.0, "compile": 0.0, "transfer": 0.0, "total": 0.0}
# Slope-methodology raw data (PERF.md: single-sync timings lie on this
# runtime, so per-batch cost is fit from (k, seconds) over k chained
# submits). Two sources, both served by /debug/verify_stats so a live
# node's suspicious slope can be re-fit WITHOUT a bench rerun:
# - the last recorded fit (bench.py rlc_slope_samples calls
#   record_slope_samples with its raw pairs), and
# - a bounded ring of live per-flush (n, seconds) samples for rlc* paths.
_SLOPE_FIT: Dict[str, Any] = {}
_FLUSH_SAMPLES: deque = deque(maxlen=128)  # (n, total_s, path)

_DEVICE_LOCK = threading.Lock()
_DEVICE: Dict[str, Any] = {
    "up": None,  # None = no device call attempted yet
    "init_seconds": None,
    "last_call_monotonic": None,
    "last_error": None,
}


def record_flush(
    *,
    backend: str,
    path: str,
    n: int,
    total_s: float,
    n_valid: Optional[int] = None,
    prep_s: Optional[float] = None,
    compile_s: Optional[float] = None,
    transfer_s: Optional[float] = None,
    jit_bucket: Optional[int] = None,
    padding_lanes: Optional[int] = None,
    cache_hits: Optional[int] = None,
    cache_misses: Optional[int] = None,
    rlc_fallback: bool = False,
    fused: Optional[bool] = None,
    h2d_bytes: Optional[int] = None,
    device_dispatches: Optional[int] = None,
    chunks: Optional[int] = None,
    chunk_lanes: Optional[int] = None,
    prep_overlap_s: Optional[float] = None,
    prep_stages: Optional[dict] = None,
    memo_hits: Optional[int] = None,
    memo_rows: Optional[int] = None,
    memo_inserted: Optional[int] = None,
    memo_s: Optional[float] = None,
    recovery_flushes: Optional[int] = None,
    quarantined: Optional[int] = None,
    tracer_: Optional[Tracer] = None,
) -> None:
    """One batch-verify flush completed. Called by crypto/batch.verify_batch
    for EVERY flush on EVERY backend; `tracer_` is the caller's already-
    resolved tracer (or None when tracing is disabled) so this function adds
    no tracer-flag reads of its own."""
    from tendermint_tpu.libs import metrics as _metrics
    from tendermint_tpu.libs import slo as _slo

    # SLO feed (verify_flush_wall): one None check when no engine registered
    _slo.feed_flush(total_s)

    m = _metrics.batch_metrics()
    m.flushes.labels(backend, path).inc()
    m.sigs.labels(backend, path).inc(n)
    m.batch_size.observe(n)
    m.flush_seconds.labels(path).observe(total_s)
    if prep_s is not None:
        m.prep_seconds.observe(prep_s)
    # compile_s is NOT re-counted into m.compile_seconds here: record_compile
    # already did, at the aot_cache call site; it rides only the breakdown.
    if transfer_s is not None:
        m.transfer_seconds.inc(transfer_s)
    if jit_bucket is not None:
        m.jit_bucket.set(jit_bucket)
    if padding_lanes is not None:
        m.padding_lanes.set(padding_lanes)
    if cache_hits:
        m.pubkey_cache_hits.inc(cache_hits)
    if cache_misses:
        m.pubkey_cache_misses.inc(cache_misses)
    if rlc_fallback:
        m.rlc_fallbacks.inc()
    # adversarial flush defense (crypto/batch.py _bisect_recover +
    # crypto/provenance.py): recovery cost + quarantined-row attribution
    if recovery_flushes:
        m.recovery_flushes.inc(recovery_flushes)
    if quarantined:
        m.quarantined_rows.inc(quarantined)
    # streamed flush planner (crypto/batch.py ISSUE 13): chunk count per
    # flush + the host-prep wall the double buffer hid behind device work
    if chunks is not None:
        m.chunks_per_flush.observe(chunks)
    if prep_overlap_s:
        m.prep_overlap_seconds.inc(prep_overlap_s)
    # ISSUE 18: hidden-prep fraction of THIS flush (streamed, pipelined and
    # striped host-RLC paths all report prep_overlap_s now). memo_hits rides
    # only the last-flush dict — VerifiedRowMemo.lookup owns the counter.
    if prep_s and prep_overlap_s is not None:
        m.prep_hidden_ratio.set(min(1.0, prep_overlap_s / prep_s))

    last = {
        "backend": backend,
        "path": path,
        "n": n,
        "total_ms": round(total_s * 1e3, 4),
    }
    if n_valid is not None:
        last["n_valid"] = n_valid
    if prep_s is not None:
        last["prep_ms"] = round(prep_s * 1e3, 4)
    if compile_s is not None:
        last["compile_ms"] = round(compile_s * 1e3, 4)
    if transfer_s is not None:
        last["transfer_ms"] = round(transfer_s * 1e3, 4)
    if jit_bucket is not None:
        last["jit_bucket"] = jit_bucket
        last["padding_lanes"] = padding_lanes
    if cache_hits is not None or cache_misses is not None:
        hits, misses = cache_hits or 0, cache_misses or 0
        last["pubkey_cache_hits"] = hits
        last["pubkey_cache_misses"] = misses
        if hits + misses:
            last["pubkey_cache_hit_rate"] = round(hits / (hits + misses), 4)
    if rlc_fallback:
        last["rlc_fallback"] = True
    if fused is not None:
        last["fused"] = bool(fused)
    if h2d_bytes is not None:
        last["h2d_bytes"] = h2d_bytes
    if device_dispatches is not None:
        last["device_dispatches"] = device_dispatches
    if chunks is not None:
        last["chunks"] = chunks
    if chunk_lanes is not None:
        last["chunk_lanes"] = chunk_lanes
    if prep_overlap_s is not None:
        last["prep_overlap_ms"] = round(prep_overlap_s * 1e3, 4)
    if prep_stages:
        last["prep_stages_ms"] = {
            k[:-2] if k.endswith("_s") else k: round(v * 1e3, 4)
            for k, v in prep_stages.items()
        }
    if memo_hits is not None:
        last["memo_hits"] = memo_hits
    if memo_rows is not None:
        # the verified-row memo's pass over this verify: rows asked, rows
        # newly inserted, and its digest + look-up + insert time
        last["memo_rows"] = memo_rows
        last["memo_inserted"] = memo_inserted or 0
        last["memo_ms"] = round((memo_s or 0.0) * 1e3, 4)
    if recovery_flushes is not None:
        last["recovery_flushes"] = recovery_flushes
    if quarantined is not None:
        last["quarantined"] = quarantined
    with _STATS_LOCK:
        t = _TOTALS.setdefault(
            (backend, path), {"flushes": 0, "sigs": 0, "seconds": 0.0}
        )
        t["flushes"] += 1
        t["sigs"] += n
        t["seconds"] += total_s
        _COUNTS["cache_hits"] += cache_hits or 0
        _COUNTS["cache_misses"] += cache_misses or 0
        if rlc_fallback:
            _COUNTS["rlc_fallbacks"] += 1
        _COUNTS["recovery_flushes"] += recovery_flushes or 0
        _COUNTS["quarantined_rows"] += quarantined or 0
        _STAGE_SECONDS["prep"] += prep_s or 0.0
        _STAGE_SECONDS["compile"] += compile_s or 0.0
        _STAGE_SECONDS["transfer"] += transfer_s or 0.0
        _STAGE_SECONDS["total"] += total_s
        _LAST_FLUSH.clear()
        _LAST_FLUSH.update(last)
        if path.startswith("rlc"):
            _FLUSH_SAMPLES.append((n, round(total_s, 6), path))
    if tracer_ is not None:
        tracer_.event("batch_verify.flush", **last)


def record_slope_samples(
    samples,
    slope_ms: Optional[float] = None,
    fused: Optional[bool] = None,
    source: str = "bench",
) -> None:
    """Record a slope fit's RAW (k, seconds) pairs (bench.py
    rlc_slope_samples) so /debug/verify_stats serves them for post-hoc
    re-fitting — previously bench-JSON-only."""
    with _STATS_LOCK:
        _SLOPE_FIT.clear()
        _SLOPE_FIT.update(
            samples=[list(s) for s in samples],
            slope_ms=slope_ms,
            fused=fused,
            source=source,
            recorded_at=time.time(),
        )


def verify_stats() -> dict:
    """Aggregated flush telemetry: per-(backend, path) totals, the per-stage
    time split, and the last flush's breakdown. Shape documented in
    docs/OBSERVABILITY.md; served by /debug/verify_stats and attached to
    bench.py's JSON `extra`."""
    with _STATS_LOCK:
        totals = {
            f"{backend}/{path}": dict(t) for (backend, path), t in _TOTALS.items()
        }
        out = {
            "totals": totals,
            "stage_seconds": dict(_STAGE_SECONDS),
            "counters": dict(_COUNTS),
            "last_flush": dict(_LAST_FLUSH),
            "slope_samples": {
                "fit": dict(_SLOPE_FIT) or None,
                "flush_samples": [list(s) for s in _FLUSH_SAMPLES],
            },
        }
    out["device"] = device_health()
    out["gc"] = gc_stats()
    try:
        # lazy: batch imports this module at load time; the reverse edge
        # only exists at call time
        from tendermint_tpu.crypto.batch import BREAKER

        out["breaker"] = BREAKER.snapshot()
    except Exception:  # telemetry must never fail the stats read
        pass
    try:
        # mesh telemetry rides along so ONE stats read covers single-chip
        # and sharded pipelines (full snapshot: GET /debug/mesh)
        from tendermint_tpu.parallel import telemetry as _mesh_tm

        out["mesh"] = _mesh_tm.mesh_stats()
    except Exception:
        pass
    try:
        # the global verification scheduler's lane state (process-global
        # default, last node wins): who is queued for the device and under
        # what budgets — the QoS half of the flush totals above
        from tendermint_tpu.crypto import scheduler as _scheduler

        sched = _scheduler.default_scheduler()
        if sched is not None:
            out["scheduler"] = sched.stats()
    except Exception:
        pass
    return out


def reset_stats() -> None:
    """Test hook: zero the aggregated flush telemetry (not the metrics)."""
    with _STATS_LOCK:
        _TOTALS.clear()
        _LAST_FLUSH.clear()
        _SLOPE_FIT.clear()
        _FLUSH_SAMPLES.clear()
        for k in _COUNTS:
            _COUNTS[k] = 0
        for k in _STAGE_SECONDS:
            _STAGE_SECONDS[k] = 0.0


# -- device health -----------------------------------------------------------


def record_device_init(seconds: float, ok: bool = True, error: str = "") -> None:
    """Device/backend initialization finished (or stalled: ok=False)."""
    from tendermint_tpu.libs import metrics as _metrics

    m = _metrics.batch_metrics()
    with _DEVICE_LOCK:
        _DEVICE["init_seconds"] = seconds
        _DEVICE["up"] = bool(ok)
        _DEVICE["last_error"] = error or None
        if ok:
            _DEVICE["last_call_monotonic"] = time.monotonic()
    m.device_init_seconds.set(seconds)
    m.device_up.set(1.0 if ok else 0.0)
    if ok:
        m.device_last_call_timestamp.set(time.time())
    if tracer.enabled:
        tracer.event("device.init", seconds=round(seconds, 4), ok=bool(ok))


def mark_device_call(ok: bool = True, error: str = "") -> None:
    """A device round trip completed (ok) or failed/stalled (not ok) — the
    signal the bench's stall detector and node liveness read as `device_up`."""
    from tendermint_tpu.libs import metrics as _metrics

    m = _metrics.batch_metrics()
    with _DEVICE_LOCK:
        _DEVICE["up"] = bool(ok)
        if ok:
            _DEVICE["last_call_monotonic"] = time.monotonic()
            _DEVICE["last_error"] = None
        else:
            _DEVICE["last_error"] = error or "device call failed"
    m.device_up.set(1.0 if ok else 0.0)
    if ok:
        m.device_last_call_timestamp.set(time.time())


def device_health() -> dict:
    """{"device_up": 0/1/None, "init_seconds", "last_call_age_s", "last_error"}.
    device_up None means no device call has been attempted this process."""
    with _DEVICE_LOCK:
        up = _DEVICE["up"]
        last = _DEVICE["last_call_monotonic"]
        return {
            "device_up": None if up is None else int(up),
            "init_seconds": _DEVICE["init_seconds"],
            "last_call_age_s": (
                round(time.monotonic() - last, 3) if last is not None else None
            ),
            "last_error": _DEVICE["last_error"],
        }


# -- compile accounting ------------------------------------------------------

_COMPILE_LOCK = threading.Lock()
_COMPILE_TOTAL = 0.0  # seconds spent tracing/exporting/deserializing kernels


def record_compile(name: str, seconds: float, kind: str) -> None:
    """ops/aot_cache.py: a kernel trace+export ("export") or artifact load
    ("deserialize") took `seconds`. Feeds the compile-vs-execute split."""
    global _COMPILE_TOTAL
    from tendermint_tpu.libs import metrics as _metrics

    with _COMPILE_LOCK:
        _COMPILE_TOTAL += seconds
    _metrics.batch_metrics().compile_seconds.labels(kind).inc(seconds)
    if tracer.enabled:
        tracer.event(f"aot.{kind}", kernel=name, seconds=round(seconds, 4))


def compile_seconds_total() -> float:
    """Monotonic compile-time counter; diff around a flush to attribute
    compile seconds to it (crypto/batch.verify_batch)."""
    with _COMPILE_LOCK:
        return _COMPILE_TOTAL
