"""benchmark/program_spans.py and the eight per-layer readers that divide a
verify_commit call from the inside (ISSUE 26), on a synthetic ring: whole
calls, a call the ring has rolled over, a refused call, a call of another
size and a probe's bare verify_batch mixed in. And benchmark/spec.py's lint
on the BENCHMARK.json that lists them."""

import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import program_spans  # noqa: E402
import spec  # noqa: E402

NEW = ["entry.gather_ms", "entry.sign_bytes_ms", "flush.record_ms",
       "prep.first_dispatch_ms", "prep.hash_ms", "prep.scalars_ms", "prep.sort_ms",
       "prep.wait_ms"]
ROWS = 100


class Ring:
    """Writes events as the recorder does: children before their root."""

    def __init__(self):
        self.events, self.next_id, self.now = [], 0, 1_000_000

    def call(self, k, rows=ROWS, verdict="accepted", whole=True, chunks=2):
        """One verify_commit whose flush has `chunks` chunks (two, so that a
        reader's sum over chunks can be told from one chunk's; the timed
        cells' flushes are one chunk since PR 30); call k's spans last
        k-dependent times so that medians can be told from means."""
        self.next_id += 1
        root = self.next_id
        t0 = self.now

        def ev(name, start_us, dur_ms, **attrs):
            self.next_id += 1
            e = {"name": name, "span": self.next_id, "parent": root, "root": root,
                 "t0_ns": t0 + int(start_us * 1e3), "ts": 0.0, "dur_ms": dur_ms}
            if attrs:
                e["attrs"] = attrs
            self.events.append(e)

        if whole:
            ev("commit.gather", 0, 1.0 + k)
        ev("commit.sign_bytes", 2_000, 2.0 + k, rows=rows)
        for c in range(chunks):
            ev("prep.hash", 5_000 + c, 0.5 + k, chunk=c)
            ev("prep.scalars", 5_600 + c, 0.25, chunk=c)
            ev("prep.sort", 5_900 + c, 1.5, chunk=c)
            ev("flush.prep_wait", 5_000 + c, 0.125 * (k + 1), chunk=c)
            ev("dispatch", 9_000 + 3_000 * c, 0.75, program="rlc_partial_f")
        ev("flush.record", 20_000, 0.0625 * (k + 1))
        ev("verify_batch", 4_000, 17.0)
        ev("commit.tally", 21_500, 0.5)
        self.events.append({"name": "commit.verify", "span": root, "parent": None,
                            "root": root, "t0_ns": t0, "ts": 0.0, "dur_ms": 22.0,
                            "attrs": {"entry": "verify_commit", "rows": rows,
                                      "height": 5, "verdict": verdict}})
        self.now += 30_000_000

    def probe(self):
        """A combined check asked directly: a bare verify_batch, no root
        named commit.verify; and a point event of the old shape, rootless."""
        self.next_id += 1
        root = self.next_id
        self.events.append({"name": "prep.hash", "span": root + 1000, "parent": root,
                            "root": root, "t0_ns": self.now, "ts": 0.0, "dur_ms": 99.0})
        self.events.append({"name": "verify_batch", "span": root, "parent": None,
                            "root": root, "t0_ns": self.now, "ts": 0.0, "dur_ms": 99.0})
        self.events.append({"name": "aot.deserialize", "span": 0, "parent": None,
                            "ts": 0.0, "attrs": {"kernel": "x", "seconds": 1.0}})


def _ctx(events, monkeypatch, rows=ROWS):
    monkeypatch.setattr(program_spans, "ring", lambda: events)
    return types.SimpleNamespace(rows=rows)


def _mixed_ring(n_whole=31):
    r = Ring()
    r.call(0, whole=False)  # its first child has rolled over
    for k in range(n_whole):
        r.call(k)
        if k % 7 == 0:
            r.probe()
    r.call(500, verdict="NotEnoughVotingPowerError")
    r.call(600, rows=ROWS - 40)  # the short-power entry probe's size
    return r.events


def test_whole_calls_keeps_only_the_windows_whole_accepted_calls():
    calls = program_spans.whole_calls(_mixed_ring(), ROWS)
    assert len(calls) == 31
    assert all(len(c["prep.hash"]) == 2 and "commit.gather" in c for c in calls)
    assert sorted(c["commit.gather"][0][1] for c in calls) == [1.0 + k for k in range(31)]
    (other,) = program_spans.whole_calls(_mixed_ring(), ROWS - 40)
    assert other["commit.gather"][0][1] == 601.0


@pytest.mark.parametrize("name,want", [
    ("entry.gather_ms", 1.0 + 15),
    ("entry.sign_bytes_ms", 2.0 + 15),
    ("flush.record_ms", 0.0625 * 16),
    ("prep.first_dispatch_ms", 5.0 + 0.75),  # verify_batch starts at 4 ms, dispatch 9..9.75
    ("prep.hash_ms", 2 * (0.5 + 15)),
    ("prep.scalars_ms", 0.5),
    ("prep.sort_ms", 3.0),
    ("prep.wait_ms", 2 * 0.125 * 16),
])
def test_reader_is_the_median_over_whole_calls(monkeypatch, name, want):
    ctx = _ctx(_mixed_ring(), monkeypatch)
    reader = spec.load_module(spec.module_path(BENCH, "layer_metrics", name))
    assert reader.read(ctx) == pytest.approx(want)


@pytest.mark.parametrize("name", NEW)
def test_reader_returns_none_when_there_is_nothing_to_read(monkeypatch, name):
    reader = spec.load_module(spec.module_path(BENCH, "layer_metrics", name))
    # fewer than 30 whole calls
    assert reader.read(_ctx(_mixed_ring(n_whole=29), monkeypatch)) is None
    # the parent's ring: events without `root`, no commit.verify
    old = [{k: v for k, v in e.items() if k not in ("root", "t0_ns")}
           for e in _mixed_ring() if not e["name"].startswith("commit.")]
    assert reader.read(_ctx(old, monkeypatch)) is None
    assert reader.read(_ctx([], monkeypatch)) is None


def test_a_span_off_the_cells_path_leaves_its_metric_out(monkeypatch):
    events = [e for e in _mixed_ring() if e["name"] != "flush.prep_wait"]
    ctx = _ctx(events, monkeypatch)
    assert program_spans.median_sum_ms(ctx, "flush.prep_wait") is None
    assert program_spans.median_sum_ms(ctx, "prep.hash") == pytest.approx(31.0)


def test_ring_reads_the_programs_recorder(monkeypatch):
    from tendermint_tpu.libs import trace

    t = trace.Tracer(ring_size=8)
    monkeypatch.setattr(trace, "tracer", t)
    with t.span("commit.verify", rows=3, verdict="accepted"):
        with t.span("commit.gather"):
            pass
    (call,) = program_spans.whole_calls(program_spans.ring(), 3)
    assert set(call) == {"commit.verify", "commit.gather"}


def test_benchmark_json_lints_and_lists_the_eight():
    bm = spec.load_benchmark(ROOT)
    assert spec.lint(bm, ROOT, BENCH) == []
    added = [m for m in bm["per_layer"] if m["name"] in NEW]
    assert [m["name"] for m in added] == NEW
    cells = ["commit-10k.verify-commit", "commit-1024.verify-commit"]
    for m in added:
        assert m["source"] == "program_span" and m["moves"] == "verify_ms_p50"
        assert m["unit"] == "ms" and m["better"] == "lower"
        # since PR 29: they pick calls by the `commit.verify` root, so they
        # name the two cells whose entry opens it
        assert m["workloads"] == cells
    for cell in cells:
        mine = {m["name"] for m in spec.metrics_of(bm, "per_layer", cell)}
        assert set(NEW) <= mine
    assert not set(NEW) & {m["name"] for m in spec.metrics_of(bm, "per_layer", "hub-175.catchup")}
