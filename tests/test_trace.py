"""libs/trace.py — the batch-verify flight recorder: span nesting, ring
bounds, JSONL round-trip, thread safety, and the disabled-mode overhead
contract (crypto/batch.py makes ZERO tracer calls beyond one flag read)."""

import threading

import numpy as np
import pytest

from tendermint_tpu.libs import trace
from tendermint_tpu.libs.trace import Tracer


def test_span_nesting_parent_ids():
    t = Tracer(ring_size=16)
    with t.span("outer", a=1):
        with t.span("inner"):
            t.event("leaf", x="y")
    events = t.dump()
    # exit order: leaf (event), inner, outer
    assert [e["name"] for e in events] == ["leaf", "inner", "outer"]
    leaf, inner, outer = events
    assert outer["parent"] is None
    assert inner["parent"] == outer["span"]
    assert leaf["parent"] == inner["span"]
    assert outer["attrs"] == {"a": 1}
    assert "dur_ms" in outer and "dur_ms" not in leaf


def test_span_set_attrs_mid_flight():
    t = Tracer(ring_size=4)
    with t.span("flush", n=3) as s:
        s.set(path="cpu")
    (e,) = t.dump()
    assert e["attrs"] == {"n": 3, "path": "cpu"}


def test_span_records_error_name():
    t = Tracer(ring_size=4)
    with pytest.raises(ValueError):
        with t.span("boom"):
            raise ValueError("x")
    (e,) = t.dump()
    assert e["attrs"]["error"] == "ValueError"


def test_ring_buffer_bounded_keeps_newest():
    t = Tracer(ring_size=8)
    for i in range(50):
        t.event("e", i=i)
    events = t.dump()
    assert len(events) == 8  # never exceeds the configured size
    assert [e["attrs"]["i"] for e in events] == list(range(42, 50))
    assert t.dump(limit=3) == events[-3:]
    assert t.dump(limit=0) == []


def test_configure_resize_and_enable():
    t = Tracer(ring_size=8)
    for i in range(8):
        t.event("e", i=i)
    t.configure(ring_size=4)
    assert t.ring_size == 4
    assert [e["attrs"]["i"] for e in t.dump()] == [4, 5, 6, 7]
    t.configure(enabled=False)
    assert t.enabled is False
    t.configure(enabled=True, ring_size=2)
    assert t.enabled is True and len(t.dump()) == 2


def test_jsonl_round_trip():
    t = Tracer(ring_size=16)
    with t.span("flush", n=4, path="cpu"):
        t.event("mark", detail="unicode-ok: ✓")
    text = t.to_jsonl()
    assert len(text.splitlines()) == 2
    back = Tracer.from_jsonl(text)
    assert back == t.dump()


def test_thread_safety_and_per_thread_nesting():
    t = Tracer(ring_size=10_000)
    errors = []

    def work(tid):
        try:
            for i in range(100):
                with t.span("outer", tid=tid):
                    with t.span("inner", tid=tid, i=i):
                        pass
        except Exception as e:  # pragma: no cover - failure reporting
            errors.append(e)

    threads = [threading.Thread(target=work, args=(k,)) for k in range(8)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    assert not errors
    events = t.dump()
    assert len(events) == 8 * 100 * 2
    # nesting is tracked per thread: every inner's parent is an outer span
    # from the SAME thread's stack
    by_id = {e["span"]: e for e in events}
    for e in events:
        if e["name"] == "inner":
            parent = by_id[e["parent"]]
            assert parent["name"] == "outer"
            assert parent["attrs"]["tid"] == e["attrs"]["tid"]


def _make_cpu_batch(n=4):
    keys = pytest.importorskip(
        "tendermint_tpu.crypto.keys", reason="host crypto unavailable"
    )
    priv = keys.gen_ed25519(b"\x42" * 32)
    pk = priv.pub_key().bytes()
    msgs = [b"trace-%d" % i for i in range(n)]
    return [pk] * n, msgs, [priv.sign(m) for m in msgs]


class _DisabledSentinel:
    """tracer stand-in: counts flag reads, explodes on any recording call."""

    def __init__(self):
        self.flag_reads = 0

    @property
    def enabled(self):
        self.flag_reads += 1
        return False

    def __getattr__(self, name):
        raise AssertionError(f"tracer.{name} called while tracing disabled")


def test_batch_path_zero_tracer_calls_when_disabled(monkeypatch):
    """The overhead contract: with tracing off, a verify_batch flush touches
    the tracer exactly once (the hoisted flag read) and never calls it."""
    from tendermint_tpu.crypto import batch as B

    pubkeys, msgs, sigs = _make_cpu_batch(4)
    sentinel = _DisabledSentinel()
    monkeypatch.setattr(trace, "tracer", sentinel)
    mask = B.verify_batch(pubkeys, msgs, sigs, backend="cpu")
    assert mask.all()
    assert sentinel.flag_reads == 1


def test_batch_path_emits_span_and_flush_event_when_enabled(monkeypatch):
    from tendermint_tpu.crypto import batch as B

    pubkeys, msgs, sigs = _make_cpu_batch(5)
    t = Tracer(ring_size=64)
    monkeypatch.setattr(trace, "tracer", t)
    mask = B.verify_batch(pubkeys, msgs, sigs, backend="cpu")
    assert mask.all()
    names = [e["name"] for e in t.dump()]
    assert "verify_batch" in names and "batch_verify.flush" in names
    span = next(e for e in t.dump() if e["name"] == "verify_batch")
    assert span["attrs"]["n"] == 5
    assert span["attrs"]["path"] == "cpu"
    flush = next(e for e in t.dump() if e["name"] == "batch_verify.flush")
    # the flush event is parented INSIDE the verify_batch span (span tree)
    assert flush["parent"] == span["span"] or flush["parent"] is None


def test_record_flush_aggregates_stats():
    trace.reset_stats()
    trace.record_flush(
        backend="cpu", path="cpu", n=7, total_s=0.01, n_valid=7,
        jit_bucket=8, padding_lanes=1, cache_hits=3, cache_misses=4,
    )
    trace.record_flush(
        backend="jax", path="rlc", n=1024, total_s=0.2, n_valid=1024,
        prep_s=0.05, transfer_s=0.1, rlc_fallback=True,
    )
    stats = trace.verify_stats()
    assert stats["totals"]["cpu/cpu"]["flushes"] == 1
    assert stats["totals"]["jax/rlc"]["sigs"] == 1024
    assert stats["counters"]["rlc_fallbacks"] == 1
    assert stats["counters"]["cache_hits"] == 3
    assert stats["stage_seconds"]["prep"] == pytest.approx(0.05)
    assert stats["stage_seconds"]["transfer"] == pytest.approx(0.1)
    assert stats["last_flush"]["path"] == "rlc"
    assert stats["last_flush"]["rlc_fallback"] is True
    assert "device" in stats


def test_device_health_gauges():
    trace.record_device_init(1.5, ok=True)
    h = trace.device_health()
    assert h["device_up"] == 1
    assert h["init_seconds"] == 1.5
    assert h["last_call_age_s"] is not None and h["last_call_age_s"] >= 0
    trace.mark_device_call(ok=False, error="device down")
    h = trace.device_health()
    assert h["device_up"] == 0
    assert h["last_error"] == "device down"
    trace.mark_device_call(ok=True)
    assert trace.device_health()["device_up"] == 1
    # the Prometheus exposition carries the same gauges
    from tendermint_tpu.libs import metrics

    text = metrics.global_registry().expose()
    assert "tendermint_device_up 1" in text
    assert "tendermint_device_init_seconds 1.5" in text


def test_flush_detail_reports_bucket_and_padding():
    """prepare_batch stamps the jit bucket + padding waste the flush
    record picks up (no device needed: host prep only)."""
    from tendermint_tpu.crypto import batch as B

    B.LAST_FLUSH_DETAIL.clear()
    rng = np.random.default_rng(3)
    pks = [bytes(rng.integers(0, 256, 32, dtype=np.uint8)) for _ in range(5)]
    sigs = [bytes(64) for _ in range(5)]
    B.prepare_batch(pks, [b"m"] * 5, sigs)
    assert B.LAST_FLUSH_DETAIL["jit_bucket"] == 8
    assert B.LAST_FLUSH_DETAIL["padding_lanes"] == 3
