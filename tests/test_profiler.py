"""On-demand profiler capture (libs/profiler.py) and the offline analyzer
(tools/profile_report.py): the start→stop round-trip on the CPU backend and
the per-stage attribution table — acceptance for the observatory's layer 1.
The CPU caveat (docs/OBSERVABILITY.md): the capture carries host/XLA:CPU
spans but no device plane; the PIPELINE is identical on real accelerators,
which is exactly what these tests pin."""

import gzip
import json
import os

import pytest

from tendermint_tpu.libs import profiler
from tendermint_tpu.tools import profile_report


def _flush_once():
    import jax
    import jax.numpy as jnp

    x = jnp.arange(1024.0)
    return jax.block_until_ready(jnp.dot(x, x))


def test_start_stop_roundtrip_on_cpu_backend(tmp_path):
    info = profiler.start(str(tmp_path))
    assert info["active"] and info["dir"].startswith(str(tmp_path))
    st = profiler.status()
    assert st["active"] and st["running_s"] >= 0
    with pytest.raises(profiler.ProfilerError):
        profiler.start(str(tmp_path))  # one session per process
    _flush_once()
    out = profiler.stop()
    assert out["active"] is False and out["duration_s"] >= 0
    assert out["artifacts"], "CPU-backend capture must still produce artifacts"
    st = profiler.status()
    assert not st["active"] and st["last_capture"]["dir"] == out["dir"]
    with pytest.raises(profiler.ProfilerError):
        profiler.stop()  # stop when idle is an error, not a no-op

    # the captured trace renders a per-stage table in one command
    rep = profile_report.report(out["dir"])
    assert rep["events"] > 0 and rep["stages"]
    md = profile_report.render_markdown(rep)
    assert "| stage |" in md and "## Top ops" in md


def test_trace_function_one_flush_capture(tmp_path):
    result, run_dir = profiler.trace_function(
        _flush_once, base_dir=str(tmp_path)
    )
    assert float(result) > 0  # the traced fn's result comes back
    assert profile_report.find_capture_files(run_dir)
    rep = profile_report.report(run_dir, top=5)
    assert len(rep["ops"]) <= 5


def _write_chrome_trace(path, events):
    with gzip.open(path, "wt") as f:
        json.dump({"traceEvents": events}, f)


def test_profile_report_stage_classification_and_self_times(tmp_path):
    """Parser unit test on a synthetic perfetto trace: fused-stage names
    classify into the PERF.md stages, and `self` excludes nested children."""
    _write_chrome_trace(tmp_path / "x.trace.json.gz", [
        {"ph": "M", "name": "process_name", "pid": 1,
         "args": {"name": "/device:TPU:0"}},
        {"ph": "M", "name": "thread_name", "pid": 1, "tid": 2,
         "args": {"name": "XLA Ops"}},
        {"ph": "X", "name": "fused_uptree_pass2", "pid": 1, "tid": 2,
         "ts": 0, "dur": 500},
        {"ph": "X", "name": "fenwick_reduce.3", "pid": 1, "tid": 2,
         "ts": 500, "dur": 300},
        {"ph": "X", "name": "bucket_fold_kernel", "pid": 1, "tid": 2,
         "ts": 800, "dur": 100},
        {"ph": "X", "name": "persig_ladder", "pid": 1, "tid": 2,
         "ts": 900, "dur": 50},
        # nesting on another thread: outer 1000us contains inner 400us
        {"ph": "X", "name": "outer_op", "pid": 1, "tid": 3, "ts": 0,
         "dur": 1000},
        {"ph": "X", "name": "inner_op", "pid": 1, "tid": 3, "ts": 100,
         "dur": 400},
    ])
    rep = profile_report.report(str(tmp_path))
    stages = {s["name"]: s for s in rep["stages"]}
    assert stages["uptree"]["total_us"] == 500
    assert stages["fenwick_reduce"]["total_us"] == 300
    assert stages["bucket_fold"]["total_us"] == 100
    assert stages["persig"]["total_us"] == 50
    ops = {o["name"]: o for o in rep["ops"]}
    assert ops["outer_op"]["total_us"] == 1000
    assert ops["outer_op"]["self_us"] == 600  # minus the nested inner
    assert ops["inner_op"]["self_us"] == 400
    # plane names resolved from the M metadata events
    assert any(p["plane"] == "/device:TPU:0" for p in rep["planes"])


def test_profile_report_parses_xplane_artifacts(tmp_path):
    """The xplane.pb protobuf walker parses a REAL capture's artifact (no
    tensorflow/tensorboard in this container — the walker is our only
    reader) and agrees with the capture's own artifact list."""
    _, run_dir = profiler.trace_function(_flush_once, base_dir=str(tmp_path))
    xplanes = [
        os.path.join(dp, fn)
        for dp, _, fns in os.walk(run_dir)
        for fn in fns if fn.endswith(".xplane.pb")
    ]
    if not xplanes:
        pytest.skip("jax build wrote no xplane artifact")
    events = profile_report.load_events(xplanes[0])
    assert events, "xplane walker must decode events from a real capture"
    assert all(e["dur_us"] >= 0 for e in events)


def test_report_errors_on_empty_dir(tmp_path):
    with pytest.raises(FileNotFoundError):
        profile_report.report(str(tmp_path))
    assert profile_report.main([str(tmp_path)]) == 2


def test_classify_first_match_wins():
    assert profile_report.classify("fused_uptree_x") == "uptree"
    assert profile_report.classify("jit_rlc_msm") == "msm_other"
    assert profile_report.classify("TransferToDevice") == "transfer"
    assert profile_report.classify("$SomePythonFrame") == "host_python"
    assert profile_report.classify("mystery") == "other"


def test_debug_device_profile_route(tmp_path):
    """GET /debug/device_profile?action=start|stop|status against a live
    RPCServer handler: the operator surface for profiling a running node."""
    import asyncio
    from types import SimpleNamespace

    from tendermint_tpu.config.config import test_config
    from tendermint_tpu.rpc.server import RPCServer

    cfg = test_config()
    cfg.instrumentation.profile_dir = str(tmp_path)
    rpc = RPCServer(SimpleNamespace(config=cfg, metrics=None))

    async def run():
        st = await rpc._debug_device_profile({})
        assert st["active"] is False
        # start/stop are unsafe-gated (they mutate process-global profiler
        # state); status above served fine without it
        cfg.rpc.unsafe = False
        with pytest.raises(ValueError, match="unsafe"):
            await rpc._debug_device_profile({"action": "start"})
        cfg.rpc.unsafe = True
        out = await rpc._debug_device_profile({"action": "start"})
        assert out["active"] and out["dir"].startswith(str(tmp_path))
        _flush_once()
        out = await rpc._debug_device_profile({"action": "stop"})
        assert not out["active"] and out["artifacts"]
        with pytest.raises(ValueError):
            await rpc._debug_device_profile({"action": "bogus"})

    asyncio.run(run())


def test_profiler_actions_counted():
    from tendermint_tpu.libs import metrics as M

    text = M.global_registry().expose()
    # the round-trips above incremented start/stop at least once each
    assert "tendermint_profiler_actions_total" in text


def _ev(plane, thread, name, ts, dur, scope=""):
    return {"name": name, "scope": scope, "ts_us": float(ts), "dur_us": float(dur),
            "pid": plane, "tid": thread, "plane": plane, "thread": thread}


def test_idle_gaps_go_to_the_innermost_tm_span():
    """Each idle gap of the device plane is put down to the innermost tm:
    span open on the caller's thread at that time; a gap that outlasts a
    span is split where the span ends; the worker's spans do not enter."""
    host, dev = "/host:CPU", "/device:TPU:0"
    events = [
        _ev(host, "main", "tm:commit.verify", 0, 1000),
        _ev(host, "main", "tm:commit.gather", 0, 300),
        _ev(host, "main", "tm:verify_batch", 400, 500),
        _ev(host, "main", "tm:flush.prep_wait", 400, 100),
        _ev(host, "main", "tm:flush.sync", 600, 250),
        _ev(host, "flush-prep_0", "tm:prep.chunk", 400, 90),
        _ev(host, "main", "bench:call", 0, 1100),
        _ev(dev, "XLA Ops", "%fusion.1 = ...", 550, 100),
        _ev(dev, "XLA Ops", "%msm_uptree.3 = ...", 650, 150),
        _ev(dev, "XLA Modules", "jit_call(1)", 550, 250),
        _ev(dev, "Steps", "0", 0, 2000),
    ]
    got = profile_report.idle_by_span(events)
    assert got["calls"] == 1
    assert got["window_ms"] == 1.0 and got["busy_ms"] == 0.25 and got["idle_ms"] == 0.75
    rows = {r["span"]: r for r in got["rows"]}
    assert rows["commit.gather"]["idle_ms"] == 0.3
    assert rows["commit.verify"]["idle_ms"] == 0.2  # 300-400 and 900-1000
    assert rows["commit.verify"]["gaps"] == 2
    assert rows["flush.prep_wait"]["idle_ms"] == 0.1
    assert rows["verify_batch"]["idle_ms"] == 0.1  # 500-550 and 850-900
    assert rows["flush.sync"]["idle_ms"] == 0.05  # 800-850, after the last op
    assert "prep.chunk" not in rows
    assert sum(r["idle_ms"] for r in got["rows"]) == pytest.approx(got["idle_ms"])
    assert got["rows"][0]["span"] == "commit.gather"
    md = profile_report.render_markdown(
        {**profile_report.analyze(events), "idle_by_span": got, "capture": []})
    assert "| `flush.prep_wait` | 0.100 |" in md
    # a capture without tm: spans (an old program) or without a device: no table
    assert profile_report.idle_by_span([e for e in events if "tm:" not in e["name"]]) == {}
    assert profile_report.idle_by_span([e for e in events if e["plane"] == host]) == {}


def test_classify_reads_the_named_scopes_and_kernel_names():
    c = profile_report.classify
    assert c("%msm_bucket_fold.46 = s32[4,20,8,128] custom-call(...)") == "bucket_fold"
    assert c("%msm_fenwick_reduce.2 = ...") == "fenwick_reduce"
    assert c("%msm_uptree.1 = ...") == "uptree"
    assert c("%fe_padd.7 = ...") == "field_kernels"
    # an XLA operation is named by its scope path, not by its HLO name
    scope = "jit(call)/call_exported/jit(_rlc_partial_core)/%s/gather:"
    assert c("%reshape.38 = ...", scope % "fenwick_gather") == "fenwick_gather"
    assert c("%copy.598 = ...", scope % "row_gather") == "row_gather"
    assert c("%fusion.1 = ...", scope % "top_tree") == "top_tree"
    assert c("%fe_padd.9 = ...", scope % "bucket_fold/fe_padd") == "bucket_fold"
    assert c("%fe_fsq.3 = ...", scope % "decompress/jit(pow_p58)/fe_fsq") == "decompress"
    assert c("%fusion.9 = ...", scope % "window_combine") == "window_combine"
    assert c("%fusion.2 = ...", scope % "identity_check") == "identity_check"
    assert c("%fusion.5 = ...", "") == "other"


DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


@pytest.mark.parametrize("cell", ["commit-10k", "commit-1024"])
def test_recorded_chip_slice_gives_its_tables(cell):
    """A slice of each benchmark cell kept on the chip (run.py --keep-trace,
    PR 26): the program's spans sit on the host plane as tm: events inside
    the benchmark's bench:call spans, the Pallas kernels carry their names,
    and the report gives the stage table and the idle-gap table stored
    beside the slice. benchmark/tracing.py's reduction of the same slice is
    what the run itself printed: the tm: events do not move it."""
    import json
    import sys

    path = os.path.join(DATA, cell + ".slice.xplane.pb.gz")
    with open(os.path.join(DATA, cell + ".slice.expect.json")) as f:
        want = json.load(f)
    events = profile_report.load_events(path)
    tm = [e for e in events if e["name"].startswith("tm:")]
    calls = [e for e in events if e["name"] == "bench:call"]
    roots = [e for e in tm if e["name"] == "tm:commit.verify"]
    assert len(roots) == len(calls) == 8
    for r, c in zip(sorted(roots, key=lambda e: e["ts_us"]),
                    sorted(calls, key=lambda e: e["ts_us"])):
        assert c["ts_us"] <= r["ts_us"]
        assert r["ts_us"] + r["dur_us"] <= c["ts_us"] + c["dur_us"]
    for name in ("commit.gather", "commit.sign_bytes", "verify_batch", "flush.record",
                 "commit.tally", "dispatch", "flush.sync", "flush.prep_wait",
                 "prep.hash", "prep.scalars", "prep.sort"):
        assert any(e["name"] == "tm:" + name for e in tm), name
    assert len({e["tid"] for e in tm}) == 2  # the caller's thread and the prep worker's
    assert len(tm) / 8 == want["tm_spans_per_call"] <= (39 if cell == "commit-10k" else 29)
    names = " ".join(e["name"][:40] for e in events if e["plane"].startswith("/device:"))
    assert "_unknown_" not in names
    for kernel in ("%msm_uptree", "%msm_fenwick_reduce", "%msm_bucket_fold", "%fe_fsq"):
        assert kernel in names, kernel
    rep = profile_report.report(path, top=5)
    assert json.loads(json.dumps(rep["device_stages"])) == want["device_stages"]
    assert json.loads(json.dumps(rep["idle_by_span"])) == want["idle_by_span"]
    assert rep["idle_by_span"]["rows"][0]["span"] == "commit.sign_bytes"
    assert "| `commit.sign_bytes` |" in profile_report.render_markdown(rep)

    bench = os.path.join(os.path.dirname(DATA), "..", "benchmark")
    sys.path.insert(0, os.path.abspath(bench))
    try:
        import tracing
    finally:
        sys.path.pop(0)
    devices, spans = tracing.load_xplane(path)
    assert {n for _, _, n in spans} == {"slice", "call", "flush"}  # no tm: name gets in
    got = tracing.reduce(devices, spans)
    for k, v in want["benchmark_reduction"].items():
        assert json.loads(json.dumps(got[k])) == v, k
