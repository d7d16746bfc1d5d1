"""The readers of PR 38's six per-layer metrics (`call_spans.py`,
`vote_spans.py`): each gives the value a handmade ring and its calls say, and
None with fewer than 30 calls covered or nothing of its own in the ring; and
each reads a number off a ring the program itself recorded on a rehearsal of
the live and the light cell (24 and 4 validators on the host backend).

Second half of the pin of `test_accepted_view.py` (imported first): the
accepted files have loaded, so `spec.load_benchmark` is given back, and their
recorders of a rehearsed ring, which read every metric that names their cell
alone, are held to the metrics those files name (the six are read here).

Run: python -m pytest benchmark/tests -q   (or python benchmark/selftest.py)"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import types

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

import program_spans  # noqa: E402
import spec  # noqa: E402
import test_accepted_view as view  # noqa: E402  (by the name both loaders give it)
import test_light_seq as light_seq  # noqa: E402
import test_live10k as live10k  # noqa: E402

spec.load_benchmark = view.FULL_LOAD  # the pin


def _named_only(record):
    def recorded(calls: int) -> dict:
        out = record(calls)
        for k in ("empty", "recorded", "other_size", "no_root_stated"):
            if k in out:
                out[k] = {n: v for n, v in out[k].items() if n not in view.NEW38}
        return out
    return recorded


live10k.record_live = _named_only(live10k.record_live)  # the pin
light_seq.record_light = _named_only(light_seq.record_light)  # the pin

BM = view.FULL_BM
MS = 10**6  # ns a millisecond


def reader(name: str, cell: str = view.LIVE_CELL):
    return spec.Cell(BM, cell).reader(name)


def read(monkeypatch, name, events, calls, rows=24, cell=view.LIVE_CELL):
    monkeypatch.setattr(program_spans, "ring", lambda: events)
    ctx = types.SimpleNamespace(rows=rows, calls=calls, traffic=spec.Cell(BM, cell).traffic)
    return reader(name, cell).read(ctx)


def live_call(k: int, gc_extra: float = 0.0, stamped: bool = True) -> tuple:
    """One call of the live cell as the program writes it, 100 ms from
    k seconds: the flush's tree (the queue's wait before its root's start),
    the commit made, the commit answered from the memo. The collector's
    stamps rise 2 ms a call (and `gc_extra` in this one)."""
    t0, base = k * 10**9, 100 * (k + 1)
    at = lambda ms: t0 + int(ms * MS)  # noqa: E731
    gc = 2.0 * k + gc_extra

    def ev(name, span, parent, root, start, dur, **attrs):
        return {"name": name, "span": span, "parent": parent, "root": root, "t0_ns": at(start),
                "dur_ms": dur, "attrs": attrs}

    def stamp(e, ms):
        if stamped:
            e.update(gc_ms=ms, gc_n=3 * k)
        return e

    flush = [ev("votes.pending", base + 1, base, base, 1, 8.0, rows=24),
             ev("votes.gather", base + 2, base, base, 10, 5.0),
             ev("memo.digest", base + 4, base + 3, base, 20, 3.0, rows=24),
             ev("verify_batch.memo", base + 3, base, base, 19, 5.0, rows=24, hits=0),
             ev("provenance.score", base + 5, base, base, 40, 2.0, rows=24),
             stamp(ev("votes.flush", base, None, base, 10, 50.0, rows=24, committed=24,
                      failed=0), gc + 0.5),
             stamp(ev("votes.make_commit", base + 6, None, base + 6, 62, 6.0, rows=24), gc + 1.0),
             ev("memo.digest", base + 8, base + 7, base + 7, 72, 4.0, rows=24),
             stamp(ev("commit.verify", base + 7, None, base + 7, 70, 20.0, rows=24,
                      verdict="accepted"), gc + 1.5)]
    return flush, {"start": k * 1.0, "end": k * 1.0 + 0.1}


def live_ring(n: int, **kw):
    events, calls = [], []
    for k in range(n):
        evs, call = live_call(k, gc_extra=30.0 if k >= 5 else 0.0, **kw)
        events += evs
        calls.append(call)
    return events, calls


def test_the_call_readers_on_a_handmade_live_ring(monkeypatch):
    """Each call: wall 100 ms, named 30 (wait 8, gather 5, the memo's pass 5
    around its digests, scorer 2, the commit made 6, the commit's digests 4;
    the two roots with children name nothing of their own)."""
    events, calls = live_ring(40)
    got = {n: read(monkeypatch, n, events, calls) for n in view.NEW38 if n != "light.client_ms"}
    assert got["call.unnamed_ms"] == pytest.approx(70.0)
    assert got["votes.memo_digest_ms"] == pytest.approx(7.0)
    assert got["votes.provenance_ms"] == pytest.approx(2.0)
    assert got["votes.pending_ms"] == pytest.approx(8.0)
    # the ring's oldest 32 events close in call 3: calls 4-39 are covered; the
    # collector paid 2 ms a call and 30 more in call 5: a mean, not a median
    assert got["gc.pause_ms"] == pytest.approx((36 * 2.0 + 30.0) / 36)
    # a call from which a span rolled out is not read: cut the ring's head
    assert read(monkeypatch, "call.unnamed_ms", events[5:], calls) == pytest.approx(70.0)


def test_none_under_thirty_calls_or_with_nothing_of_their_own(monkeypatch):
    few_events, few_calls = live_ring(29)  # 29 flushes, 25 calls covered
    for name in view.NEW38:
        cell = view.LIGHT_CELL if name == "light.client_ms" else view.LIVE_CELL
        assert read(monkeypatch, name, few_events, few_calls, cell=cell) is None, name
        assert read(monkeypatch, name, [], live_ring(40)[1], cell=cell) is None, name
    events, calls = live_ring(40, stamped=False)  # the parent: no root carries a stamp
    assert read(monkeypatch, "gc.pause_ms", events, calls) is None
    assert read(monkeypatch, "call.unnamed_ms", events, calls) == pytest.approx(70.0)
    assert read(monkeypatch, "light.client_ms", events, calls, cell=view.LIGHT_CELL) is None
    # the parent's flush: no queue's wait, no digests, no scorer spans
    bare = [e for e in events if e["name"] not in ("votes.pending", "memo.digest",
                                                   "provenance.score")]
    for name in ("votes.pending_ms", "votes.memo_digest_ms", "votes.provenance_ms"):
        assert read(monkeypatch, name, bare, calls) is None, name
    # calls without their clock readings (a recorder of the accepted files): nothing
    assert read(monkeypatch, "call.unnamed_ms", events, [{"flush": {}}] * 40) is None


def test_the_light_client_reader_sums_the_four_spans_around_the_run(monkeypatch):
    events, calls = [], []
    for k in range(40):
        t0, base = k * 10**9, 100 * (k + 1)
        spans = [("light.load", 1, 1.0), ("light.load", 3, 1.0), ("light.load", 5, 1.0),
                 ("light.target_checks", 7, 2.0), ("light.fetch", 10, 0.5),
                 ("light.header_checks", 11, 30.0), ("light.witness", 80, 0.5),
                 ("light.save", 81, 1.5)]
        for j, (name, start, dur) in enumerate(spans):
            inside = name in ("light.fetch", "light.header_checks")
            events.append({"name": name, "span": base + j, "parent": base + 9 if inside else None,
                           "root": base + 9 if inside else base + j,
                           "t0_ns": t0 + start * MS, "dur_ms": dur})
        events.append({"name": "light.verify_run", "span": base + 9, "parent": None,
                       "root": base + 9, "t0_ns": t0 + 10 * MS, "dur_ms": 60.0})
        calls.append({"start": k * 1.0, "end": k * 1.0 + 0.09})
    assert read(monkeypatch, "light.client_ms", events, calls,
                cell=view.LIGHT_CELL) == pytest.approx(7.0)
    # named: loads 3, checks 2, fetch 0.5 and header checks 30 (the run's
    # children), witness 0.5, save 1.5 of 90 ms
    assert read(monkeypatch, "call.unnamed_ms", events, calls,
                cell=view.LIGHT_CELL) == pytest.approx(52.5)


RECORD = r'''
import gc, json, os, sys, time, types
sys.path[:0] = [os.getcwd(), os.path.join(os.getcwd(), "benchmark")]
os.environ["TMTPU_CRYPTO_BACKEND"] = "cpu"
import data, spec
from tendermint_tpu.libs import trace
cell_name, n, calls_n = sys.argv[1], int(sys.argv[2]), int(sys.argv[3])
bm = spec.load_benchmark()
cell = spec.Cell(bm, cell_name)
traffic = dict(cell.traffic, commits_per_call=6) if "light" in cell_name else cell.traffic
vals = data.make_validators(37, cell.config, n)
ring = data.make_ring(37, cell.config, traffic, vals)
entry = cell.entry()
entry.configure(traffic)
state = entry.build(cell.config, vals, ring)
for i in range(3):
    entry.call(state, i % len(ring))
trace.tracer.clear()
calls = []
for k in range(calls_n):
    start = time.perf_counter()
    assert entry.call(state, k % len(ring)) == "accepted"
    end = time.perf_counter()
    calls.append({"start": start, "end": end, "flush": entry.flush_reading()})
    if k % 4 == 3:
        gc.collect()  # a full collection between calls: the next call pays it
names = [m["name"] for m in cell.per_layer if m["name"] in sys.argv[4:]]
ctx = types.SimpleNamespace(rows=data.n_rows(ring[0]), traffic=traffic, calls=calls)
out = {k: cell.reader(k).read(ctx) for k in names}
events = trace.tracer.dump()
out["gc_spans"] = sum(e["name"] == "gc.collect" for e in events)
out["wall_ms"] = sorted((c["end"] - c["start"]) * 1e3 for c in calls)[calls_n // 2]
print(json.dumps(out))
'''


def record(cell: str, n: int, names: list) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "-c", RECORD, cell, str(n), "36", *names], cwd=ROOT,
                       env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-2000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_the_readers_on_a_ring_the_live_cells_rehearsal_recorded():
    out = record(view.LIVE_CELL, 24, list(view.NEW38))
    assert set(out) - {"gc_spans", "wall_ms"} == set(view.NEW38) - {"light.client_ms"}
    for name in ("votes.pending_ms", "votes.memo_digest_ms", "votes.provenance_ms"):
        assert isinstance(out[name], float) and 0 < out[name] < out["wall_ms"], (name, out)
    assert 0 <= out["call.unnamed_ms"] < out["wall_ms"]
    # a full collection every fourth call, each one a `gc.collect` span, paid in the next call
    assert out["gc_spans"] >= 9 and out["gc.pause_ms"] > 0


def test_the_readers_on_a_ring_the_light_cells_rehearsal_recorded():
    out = record(view.LIGHT_CELL, 4, list(view.NEW38))
    assert set(out) - {"gc_spans", "wall_ms"} == {"gc.pause_ms", "call.unnamed_ms",
                                                 "light.client_ms"}
    assert isinstance(out["light.client_ms"], float) and 0 < out["light.client_ms"] < out["wall_ms"]
    assert 0 <= out["call.unnamed_ms"] < out["wall_ms"] and out["gc.pause_ms"] > 0
