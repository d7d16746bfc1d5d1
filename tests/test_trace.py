"""libs/trace.py — the batch-verify flight recorder: span nesting, ring
bounds, JSONL round-trip, thread safety, and the disabled-mode overhead
contract (crypto/batch.py makes ZERO tracer calls beyond one flag read)."""

import gc
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
    """The overhead contract: with tracing off, a verify_batch flush only
    ever READS the tracer's flag (once per span site on its path: the
    verify_batch span, flush.record, and the flush event's resolution) and
    never calls it: no span is constructed, nothing is recorded."""
    from tendermint_tpu.crypto import batch as B

    pubkeys, msgs, sigs = _make_cpu_batch(4)
    sentinel = _DisabledSentinel()
    monkeypatch.setattr(trace, "tracer", sentinel)
    built = []
    monkeypatch.setattr(
        trace.Span, "__init__", lambda self, *a, **kw: built.append(a)
    )
    mask = B.verify_batch(pubkeys, msgs, sigs, backend="cpu")
    assert mask.all()
    assert 1 <= sentinel.flag_reads <= 3
    assert built == []


def test_span_off_is_the_shared_noop(monkeypatch):
    """trace.span with the recorder off: the ONE shared no-op, no Span
    constructed, ring untouched; trace.timed hands back a bare clock pair
    that still times the block."""
    t = Tracer(ring_size=8, enabled=False)
    monkeypatch.setattr(trace, "tracer", t)
    built = []
    monkeypatch.setattr(
        trace.Span, "__init__", lambda self, *a, **kw: built.append(a)
    )
    a = trace.span("x", k=1)
    b = trace.span("y")
    assert a is b is trace.NOOP and a.recording is False
    with a as got:
        assert got.set(z=2) is got
    assert trace.current() is None
    with trace.timed("stage", chunk=0) as st:
        pass
    assert isinstance(st, trace.Stopwatch) and st.seconds >= 0.0
    assert st.interval()[1] >= st.interval()[0] > 0.0
    assert built == [] and t.dump() == []


def test_event_has_t0_root_and_starts_before_children():
    t = Tracer(ring_size=16)
    with t.span("outer"):
        with t.span("inner"):
            t.event("leaf")
    t.event("lonely")
    leaf, inner, outer, lonely = t.dump()
    assert outer["root"] == inner["root"] == leaf["root"] == outer["span"]
    assert lonely["root"] == lonely["span"] and lonely["parent"] is None
    assert outer["t0_ns"] <= inner["t0_ns"] <= leaf["t0_ns"] <= lonely["t0_ns"]
    # dur_ms and t0_ns are one clock: the child ends inside the parent
    assert inner["t0_ns"] + inner["dur_ms"] * 1e6 <= (
        outer["t0_ns"] + outer["dur_ms"] * 1e6 + 1e3
    )
    assert all(isinstance(e["ts"], float) for e in t.dump())


def test_explicit_parent_nests_pool_thread_under_submitter():
    from concurrent.futures import ThreadPoolExecutor

    t = Tracer(ring_size=16)

    def work(parent):
        with t.span("prep.chunk", parent=parent, chunk=0) as sp:
            with t.span("prep.hash"):
                pass
        return sp.interval()

    with ThreadPoolExecutor(max_workers=1) as pool:
        with t.span("commit.verify"):
            with t.span("rlc.pipelined") as flush:
                lo, hi = pool.submit(work, t.current()).result()
    by = {e["name"]: e for e in t.dump()}
    root = by["commit.verify"]["span"]
    assert by["prep.chunk"]["parent"] == by["rlc.pipelined"]["span"] == flush.span_id
    assert by["prep.hash"]["parent"] == by["prep.chunk"]["span"]
    assert {e["root"] for e in t.dump()} == {root}
    assert hi >= lo and (hi - lo) * 1e3 == pytest.approx(
        by["prep.chunk"]["dur_ms"], abs=1e-3
    )
    # a span handed a closed parent (finish() of a light entry) still shares its root
    with t.span("commit.finish", parent=flush):
        pass
    assert t.dump()[-1]["root"] == root


def test_batch_path_emits_span_and_flush_event_when_enabled(monkeypatch):
    from tendermint_tpu.crypto import batch as B

    pubkeys, msgs, sigs = _make_cpu_batch(5)
    t = Tracer(ring_size=64)
    monkeypatch.setattr(trace, "tracer", t)
    mask = B.verify_batch(pubkeys, msgs, sigs, backend="cpu")
    assert mask.all()
    by = {e["name"]: e for e in t.dump()}
    assert {"verify_batch", "flush.record", "batch_verify.flush"} <= set(by)
    span = by["verify_batch"]
    assert span["attrs"]["n"] == 5
    assert span["attrs"]["path"] == "cpu"
    # the flush event sits INSIDE the verify_batch span (span tree), under
    # the record's own span, which starts after the flush's total closed
    assert by["batch_verify.flush"]["parent"] == by["flush.record"]["span"]
    assert by["flush.record"]["parent"] == span["span"]
    assert by["batch_verify.flush"]["root"] == span["span"]
    total_ms = by["batch_verify.flush"]["attrs"]["total_ms"]
    assert span["t0_ns"] + total_ms * 1e6 <= by["flush.record"]["t0_ns"] + 1e3


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


# ---------------------------------------------------------------------------
# One span tree per verify_commit (ISSUE 26): the entry's spans, the prep
# worker's under an explicit parent, the flush record from the spans' own
# intervals, the tm: mirror on the profiler's clock.

from test_flush_planner import _install_host_twins  # noqa: E402
from test_prep_pipeline import needs_native, prep_cfg, small_rlc  # noqa: E402,F401

CHAIN = "trace-chain"


def _signed_commit(n, height=9, absent=()):
    from tendermint_tpu.crypto import gen_ed25519
    from tendermint_tpu.types.basic import BlockID, BlockIDFlag, PartSetHeader
    from tendermint_tpu.types.block import Commit, CommitSig
    from tendermint_tpu.types.validator_set import Validator, ValidatorSet

    rng = np.random.default_rng(26)
    privs = [gen_ed25519(rng.integers(0, 256, 32, dtype=np.uint8).tobytes())
             for _ in range(n)]
    vals = ValidatorSet([Validator(p.pub_key(), 10) for p in privs])
    by_addr = {p.pub_key().address(): p for p in privs}
    bid = BlockID(b"\x07" * 32, PartSetHeader(1, b"\x08" * 32))
    blank = [CommitSig(BlockIDFlag.COMMIT, v.address, 1_000 + i, b"\x00" * 64)
             for i, v in enumerate(vals.validators)]
    msgs = Commit(height, 0, bid, blank).vote_sign_bytes_many(CHAIN, range(n))
    sigs = [
        CommitSig.absent_sig() if i in absent
        else CommitSig(BlockIDFlag.COMMIT, v.address, 1_000 + i,
                       by_addr[v.address].sign(msgs[i]))
        for i, v in enumerate(vals.validators)
    ]
    return vals, bid, Commit(height, 0, bid, sigs)


def _device_route(monkeypatch):
    """verify_commit's verify_batch takes the device route (host twins)."""
    from tendermint_tpu.crypto import batch as B

    _install_host_twins(monkeypatch)
    monkeypatch.delenv("TMTPU_CRYPTO_BACKEND", raising=False)
    monkeypatch.setattr(B, "_JAX_MIN_BATCH", 8)


def _tree(events, root_name="commit.verify"):
    root = next(e for e in events if e["name"] == root_name)
    mine = [e for e in events if e["root"] == root["span"]]
    children = sorted(e["name"] for e in mine if e["parent"] == root["span"])
    return root, mine, children


# events a call may leave in the ring on any path (ISSUE 26 gave the
# pipelined path 40 for its two chunks; its one chunk since ISSUE 30 leaves
# 18 on the chip, the staged single flush at 1,024 the same). Rows do not
# enter: a span per row would pass it at once.
BUDGET = 30


@needs_native
@pytest.mark.parametrize("path,n", [("rlc-pipelined", 24), ("rlc", 12), ("cpu", 6)])
def test_verify_commit_span_tree_and_budget(small_rlc, prep_cfg, monkeypatch, path, n):
    """One verify_commit = one tree: commit.verify with exactly gather,
    sign_bytes, verify_batch, tally under it, every event of the call under
    its root (the prep worker's too), and no more events than the budget."""
    if path != "cpu":
        _device_route(monkeypatch)
    prep_cfg["stream_floor"] = 16  # 24 rows ride the chunk bucket, 12 do not
    vals, bid, commit = _signed_commit(n)
    t = Tracer(ring_size=256)
    monkeypatch.setattr(trace, "tracer", t)
    vals.verify_commit(CHAIN, bid, commit.height, commit)
    events = t.dump()
    root, mine, children = _tree(events)
    assert children == ["commit.gather", "commit.sign_bytes", "commit.tally", "verify_batch"]
    assert len(mine) == len(events) <= BUDGET
    assert root["attrs"] == {"entry": "verify_commit", "rows": n, "height": 9,
                             "verdict": "accepted"}
    assert events[-1] is root or events[-1]["span"] == root["span"]  # written last
    by = {}
    for e in mine:
        by.setdefault(e["name"], []).append(e)
    assert by["commit.sign_bytes"][0]["attrs"]["bytes"] > 64 * n
    assert by["verify_batch"][0]["attrs"]["path"] == path
    assert by["flush.record"][0]["parent"] == by["verify_batch"][0]["span"]
    for e in mine:  # a child starts inside its root
        assert e["t0_ns"] >= root["t0_ns"]
    if path == "rlc-pipelined":
        flush = by["rlc.pipelined"][0]["span"]
        assert [e["attrs"]["chunk"] for e in by["prep.chunk"]] == [0]  # one chunk
        assert by["prep.chunk"][0]["parent"] == flush
        chunk_id = by["prep.chunk"][0]["span"]
        for name in ("prep.hash", "prep.scalars", "prep.sort"):
            assert [e["parent"] for e in by[name]] == [chunk_id], name
        assert len(by["flush.prep_wait"]) == 1
        assert [e["attrs"] for e in by["flush.sync"]] == [
            {"chunk": 0}, {"what": "identity"}]
    elif path == "rlc":
        sub = by["rlc.submit"][0]["span"]
        for name in ("prep.precheck", "prep.hash", "flush.prep_wait", "prep.scalars",
                     "prep.sort"):
            assert [e["parent"] for e in by[name]] == [sub], name
        assert by["flush.sync"][0]["parent"] == by["rlc.finish"][0]["span"]


def test_verify_commit_refusal_names_its_verdict(monkeypatch):
    vals, bid, commit = _signed_commit(6, absent=(0, 1, 2))
    t = Tracer(ring_size=64)
    monkeypatch.setattr(trace, "tracer", t)
    from tendermint_tpu.types.validator_set import NotEnoughVotingPowerError

    with pytest.raises(NotEnoughVotingPowerError):
        vals.verify_commit(CHAIN, bid, commit.height, commit)
    root, mine, children = _tree(t.dump())
    assert root["attrs"]["verdict"] == "NotEnoughVotingPowerError"
    assert root["attrs"]["rows"] == 3 and "commit.tally" in children


def test_light_entries_carry_the_commit_spans(monkeypatch):
    """begin/finish: the submit phase is the root (`submitted`), finish()
    nests under it by explicit parent although the root has closed."""
    from fractions import Fraction

    vals, bid, commit = _signed_commit(6)
    t = Tracer(ring_size=64)
    monkeypatch.setattr(trace, "tracer", t)
    f1 = vals.begin_verify_commit_light(CHAIN, bid, commit.height, commit)
    f2 = vals.begin_verify_commit_light_trusting(CHAIN, commit, Fraction(1, 3))
    f1()
    f2()
    roots = [e for e in t.dump() if e["name"] == "commit.verify"]
    assert [r["attrs"]["entry"] for r in roots] == [
        "verify_commit_light", "verify_commit_light_trusting"]
    for r in roots:
        assert r["attrs"]["verdict"] == "submitted"
        mine = [e for e in t.dump() if e["root"] == r["span"]]
        names = {e["name"] for e in mine}
        assert {"commit.gather", "commit.sign_bytes", "commit.finish", "commit.tally"} <= names
        fin = next(e for e in mine if e["name"] == "commit.finish")
        assert fin["parent"] == r["span"] and fin["attrs"]["verdict"] == "accepted"
        assert fin["attrs"]["entry"] == r["attrs"]["entry"]


@needs_native
@pytest.mark.parametrize("path,n", [("rlc-pipelined", 24), ("rlc", 12)])
def test_flush_record_equals_the_spans(small_rlc, prep_cfg, monkeypatch, path, n):
    """One timing, two consumers: the flush record's prep_ms, prep_stages_ms
    and transfer_ms ARE the spans' durations."""
    from tendermint_tpu.crypto import batch as B

    _device_route(monkeypatch)
    prep_cfg["stream_floor"] = 16  # 24 rows ride the chunk bucket, 12 do not
    vals, bid, commit = _signed_commit(n)
    t = Tracer(ring_size=256)
    monkeypatch.setattr(trace, "tracer", t)
    vals.verify_commit(CHAIN, bid, commit.height, commit)
    by = {}
    for e in t.dump():
        by.setdefault(e["name"], []).append(e)
    rec = by["batch_verify.flush"][0]["attrs"]
    assert rec["path"] == path

    def total(name):
        return sum(e["dur_ms"] for e in by[name])

    tol = dict(abs=2e-3)  # both are rounded to 0.1 us, stage by stage
    whole = "prep.chunk" if path == "rlc-pipelined" else "rlc.submit"
    assert rec["prep_ms"] == pytest.approx(total(whole), **tol)
    for stage in ("hash", "scalars", "sort"):
        assert rec["prep_stages_ms"][stage] == pytest.approx(total("prep." + stage), **tol)
    if path == "rlc":
        assert rec["prep_stages_ms"]["precheck"] == pytest.approx(
            total("prep.precheck"), **tol)
    assert rec["transfer_ms"] == pytest.approx(by["flush.sync"][-1]["dur_ms"], **tol)
    # the record is not inside its own total
    vb = by["verify_batch"][0]
    assert vb["t0_ns"] + rec["total_ms"] * 1e6 <= by["flush.record"][0]["t0_ns"] + 1e3


def test_flush_record_unchanged_with_recorder_off(small_rlc, prep_cfg, monkeypatch):
    """Recorder off: the bare clock pairs still fill the flush record, and
    no Span is constructed anywhere on the device route."""
    _device_route(monkeypatch)
    prep_cfg["stream_floor"] = 16
    vals, bid, commit = _signed_commit(24)
    t = Tracer(ring_size=64, enabled=False)
    monkeypatch.setattr(trace, "tracer", t)
    built = []
    monkeypatch.setattr(trace.Span, "__init__", lambda self, *a, **kw: built.append(a))
    trace.reset_stats()
    vals.verify_commit(CHAIN, bid, commit.height, commit)
    last = trace.verify_stats()["last_flush"]
    assert last["path"] == "rlc-pipelined" and last["chunks"] == 1
    assert last["prep_ms"] > 0 and last["transfer_ms"] >= 0 and last["total_ms"] > 0
    assert set(last["prep_stages_ms"]) >= {"hash", "scalars", "sort"}
    assert built == [] and t.dump() == []


def test_tm_mirror_on_the_profilers_host_plane(tmp_path, monkeypatch):
    """Under a jax.profiler session a verify_commit leaves tm:commit.verify
    and its children on a host plane of the .xplane.pb, on the profiler's
    clock; outside a session nothing is mirrored."""
    import glob

    import jax
    from jax.profiler import ProfileData

    vals, bid, commit = _signed_commit(6)
    t = Tracer(ring_size=64)
    monkeypatch.setattr(trace, "tracer", t)
    assert trace._live_annotation() is None
    jax.profiler.start_trace(str(tmp_path))
    try:
        assert trace._live_annotation() is not None
        vals.verify_commit(CHAIN, bid, commit.height, commit)
    finally:
        jax.profiler.stop_trace()
    assert trace._live_annotation() is None
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    seen = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("tm:"):
                    seen[e.name] = (e.start_ns, e.start_ns + e.duration_ns)
    assert {"tm:commit.verify", "tm:commit.gather", "tm:commit.sign_bytes",
            "tm:verify_batch", "tm:flush.record", "tm:commit.tally"} <= set(seen)
    lo, hi = seen["tm:commit.verify"]
    for name, (s, e) in seen.items():
        assert lo <= s and e <= hi, name
    assert not any(n.startswith("bench:") for n in seen)


# -- the garbage collector's pauses (ISSUE 38)


@pytest.fixture
def gc_hooked(monkeypatch):
    """A fresh recorder with the GC hook on (tests/conftest.py turns the hook
    off for every test)."""
    t = Tracer(ring_size=256)
    monkeypatch.setattr(trace, "tracer", t)
    trace._hook_gc(True)
    yield t
    trace._hook_gc(False)


def test_a_full_collection_in_an_open_span_leaves_one_gc_collect_child(gc_hooked):
    t = gc_hooked
    with t.span("outer") as outer:
        gc.collect()
    events = t.dump()
    (col,) = [e for e in events if e["name"] == "gc.collect"]
    assert col["parent"] == outer.span_id and col["root"] == outer.span_id
    assert col["attrs"]["generation"] == 2
    assert set(col["attrs"]) == {"generation", "collected", "uncollectable"}
    assert outer.t0_ns <= col["t0_ns"] and col["t0_ns"] + col["dur_ms"] * 1e6 <= outer.t1_ns + 1e3
    assert events[-1]["name"] == "outer" and "gc_ms" not in col  # written before its root
    # a young generation's collection is counted and writes no event
    n = trace.gc_stats()["generations"]["0"]["collections"]
    with t.span("young"):
        gc.collect(0)
        gc.collect(1)
    assert [e["name"] for e in t.dump()[len(events):]] == ["young"]
    assert trace.gc_stats()["generations"]["0"]["collections"] == n + 1


def test_root_stamps_are_monotone_totals_of_every_generation(gc_hooked):
    t = gc_hooked
    for i in range(6):
        with t.span("root"):
            with t.span("child"):
                gc.collect(i % 3)
    events = t.dump()
    roots = [e for e in events if e["name"] == "root"]
    assert all("gc_ms" not in e for e in events if e["name"] != "root")
    ms, n = [e["gc_ms"] for e in roots], [e["gc_n"] for e in roots]
    assert ms == sorted(ms) and all(b > a for a, b in zip(n, n[1:]))
    stats = trace.gc_stats()
    assert stats["hooked"] is True
    assert sum(g["collections"] for g in stats["generations"].values()) >= n[-1]
    assert trace.verify_stats()["gc"]["generations"]["2"]["collections"] >= 2


def test_the_hook_is_in_gc_callbacks_only_while_the_recorder_is_on(monkeypatch):
    t = Tracer(ring_size=16)
    monkeypatch.setattr(trace, "tracer", t)
    try:
        t.configure(enabled=True)
        t.configure(enabled=True)
        assert gc.callbacks.count(trace._on_gc) == 1
        Tracer(ring_size=4).configure(enabled=False)  # not the process's: leaves the hook be
        assert trace._on_gc in gc.callbacks
        t.configure(enabled=False)
        assert trace._on_gc not in gc.callbacks and trace.gc_stats()["hooked"] is False
        # off, each new site is one flag read: no Span, no Since, no event
        assert trace.since("votes.pending") is None and trace.span("memo.digest") is trace.NOOP
        trace.interval("light.witness", 1, 2)
        assert t.dump() == []
    finally:
        t.configure(enabled=True)
        trace._hook_gc(False)
    # on, with the hook off (as in tests): roots carry no stamp
    with t.span("root"):
        pass
    assert "gc_ms" not in t.dump()[-1]


def test_a_collection_inside_dumps_critical_section_completes(gc_hooked):
    """The hook runs on the thread that allocated, here one that holds the
    ring's lock (dump() copies the ring under it): it takes no lock, so the
    call returns, and the collection is written by the next event."""
    import collections

    t = gc_hooked

    class Collecting(collections.deque):
        def __iter__(self):
            gc.collect()  # a full collection, with the ring's lock held
            return super().__iter__()

    t._ring = Collecting(t._ring, maxlen=t._ring.maxlen)
    done = []

    def go():
        for _ in range(20):
            with t.span("s"):
                pass
            done.append(len(t.dump()))

    old = gc.get_threshold()
    gc.set_threshold(1)
    try:
        worker = threading.Thread(target=go, daemon=True)
        worker.start()
        worker.join(timeout=60)
    finally:
        gc.set_threshold(*old)
    assert not worker.is_alive() and len(done) == 20
    with t.span("last"):
        pass
    names = [e["name"] for e in t.dump()]
    assert names.count("gc.collect") >= 20 and names[-1] == "last"


def test_tm_mirror_of_the_queue_the_digests_the_scorer_and_the_collector(tmp_path, monkeypatch):
    """Under a profiler session the host plane carries tm:votes.pending
    (ending where tm:votes.flush begins), tm:memo.digest,
    tm:provenance.score and tm:gc.collect (inside the span it interrupted)."""
    import glob

    import jax
    from jax.profiler import ProfileData
    from test_vote_commit_path import CHAIN as VOTE_CHAIN
    from test_vote_commit_path import scenario

    from tendermint_tpu.consensus.round_state import HeightVoteSet
    from tendermint_tpu.crypto import batch

    t = Tracer(ring_size=256)
    monkeypatch.setattr(trace, "tracer", t)
    batch.configure_verified_memo(4096)
    step, arrivals = scenario("shuffled", 8, seed=47)
    trace._hook_gc(True)
    jax.profiler.start_trace(str(tmp_path))
    try:
        votes = HeightVoteSet(VOTE_CHAIN, step.height, step.vals, defer_verification=True)
        for vote, peer in arrivals:
            votes.add_vote(vote, peer)
        votes.flush_all()
        with trace.span("outer"):
            gc.collect()
    finally:
        jax.profiler.stop_trace()
        trace._hook_gc(False)
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    seen = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("tm:"):
                    seen.setdefault(e.name, []).append((e.start_ns, e.start_ns + e.duration_ns))
    assert {"tm:votes.pending", "tm:votes.flush", "tm:memo.digest", "tm:provenance.score",
            "tm:gc.collect", "tm:outer"} <= set(seen)
    (pending,), (flush,) = seen["tm:votes.pending"], seen["tm:votes.flush"]
    assert pending[0] < pending[1] <= flush[0]
    (outer,) = seen["tm:outer"]
    assert any(outer[0] <= s and e <= outer[1] for s, e in seen["tm:gc.collect"])


def test_the_collectors_pauses_are_a_series_filled_at_scrape_time(gc_hooked):
    from tendermint_tpu.libs import metrics

    gc.collect()
    fams = metrics.parse_exposition(metrics.global_registry().expose())
    fam = fams["tendermint_process_gc_pause_seconds"]
    assert fam["type"] == "counter"
    got = {labels["generation"]: v for _, labels, v in fam["samples"]}
    want = trace.gc_stats()["generations"]
    assert set(got) == {"0", "1", "2"} and 0 < got["2"] <= want["2"]["seconds"]
    snap = metrics.global_registry().snapshot()["tendermint_process_gc_pause_seconds"]
    assert snap["series"]['generation="2"'] == got["2"]
