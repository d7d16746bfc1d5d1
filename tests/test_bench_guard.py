"""bench.py's stall guards: the driver's end-of-round bench must emit its
one JSON line even when the device hangs uninterruptibly (observed
r5: jax.devices() blocked in C without servicing SIGALRM, indefinitely)."""

import contextlib
import io
import json
import os
import sys
import time

import pytest


def _bench():
    import bench

    return bench


def test_watchdog_fires_and_resets():
    bench = _bench()
    with pytest.raises(TimeoutError):
        with bench.watchdog(1):
            time.sleep(3)
    # alarm cleared: nothing fires after the context exits
    with bench.watchdog(1):
        pass
    time.sleep(1.2)


def test_guarded_main_passes_child_json_through(tmp_path, monkeypatch):
    bench = _bench()
    stub = tmp_path / "stub_bench.py"
    stub.write_text('print(\'{"metric": "stub", "value": 1, "unit": "ms", "vs_baseline": 2.0}\')\n')
    monkeypatch.setattr(bench, "__file__", str(stub))
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        bench.guarded_main()
    out = buf.getvalue()
    assert json.loads(out)["metric"] == "stub"
    assert out.count("\n") == 1


def test_guarded_main_emits_fallback_on_hung_child(tmp_path, monkeypatch):
    bench = _bench()
    stub = tmp_path / "hang_bench.py"
    stub.write_text("import time\ntime.sleep(600)\n")
    monkeypatch.setattr(bench, "__file__", str(stub))
    monkeypatch.setenv("TMTPU_BENCH_BUDGET_S", "1")
    monkeypatch.setenv("TMTPU_BENCH_HARD_MARGIN_S", "1")
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        bench.guarded_main()
    rep = json.loads(buf.getvalue())
    assert rep["value"] == -1
    assert "deadline" in rep["extra"]["error"]


def test_guarded_main_salvages_json_printed_before_hang(tmp_path, monkeypatch):
    """A child that prints its complete result and THEN hangs in teardown
    (the device client's threads) must have that result forwarded."""
    bench = _bench()
    stub = tmp_path / "hang_after_json.py"
    stub.write_text(
        'import sys, time\n'
        'print(\'{"metric": "late", "value": 7, "unit": "ms", "vs_baseline": 3.0}\', flush=True)\n'
        "time.sleep(600)\n"
    )
    monkeypatch.setattr(bench, "__file__", str(stub))
    # deadline must comfortably cover interpreter startup under load: the
    # stub prints immediately, so 8 s total is plenty and stays flake-free
    monkeypatch.setenv("TMTPU_BENCH_BUDGET_S", "4")
    monkeypatch.setenv("TMTPU_BENCH_HARD_MARGIN_S", "4")
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        bench.guarded_main()
    rep = json.loads(buf.getvalue())
    assert rep["metric"] == "late" and rep["value"] == 7


def test_guarded_main_salvages_json_from_crashing_child(tmp_path, monkeypatch):
    """A child that prints the result then exits NONZERO (teardown crash)
    must still have the result forwarded, not replaced by the fallback."""
    bench = _bench()
    stub = tmp_path / "crash_after_json.py"
    stub.write_text(
        'import sys\n'
        'print(\'{"metric": "crashy", "value": 9, "unit": "ms", "vs_baseline": 1.5}\')\n'
        "sys.exit(134)\n"
    )
    monkeypatch.setattr(bench, "__file__", str(stub))
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        bench.guarded_main()
    rep = json.loads(buf.getvalue())
    assert rep["metric"] == "crashy" and rep["value"] == 9


def test_help_documents_flight_recorder_breakdown():
    """Acceptance: the per-stage breakdown bench attaches to its JSON
    `extra` is documented in `bench.py --help`."""
    import subprocess

    p = subprocess.run(
        [sys.executable, "bench.py", "--help"],
        capture_output=True,
        text=True,
        cwd=os.path.dirname(os.path.abspath(_bench().__file__)),
        timeout=120,
    )
    assert p.returncode == 0
    assert "verify_stats" in p.stdout
    assert "device_health" in p.stdout
    assert "stage_seconds" in p.stdout


def test_flight_recorder_extra_present_in_results():
    """extra.verify_stats carries the per-stage breakdown after a CPU flush,
    and even the stall-fallback JSON includes it (so a -1 result still
    localises the failed stage)."""
    import contextlib
    import io

    bench = _bench()
    from tendermint_tpu.crypto import batch as B
    from tendermint_tpu.crypto.keys import gen_ed25519

    priv = gen_ed25519(b"\x54" * 32)
    msgs = [b"bench-extra-%d" % i for i in range(3)]
    sigs = [priv.sign(m) for m in msgs]
    assert B.verify_batch(
        [priv.pub_key().bytes()] * 3, msgs, sigs, backend="cpu"
    ).all()

    extra = bench._flight_recorder_extra()
    assert extra["verify_stats"]["totals"]["cpu/cpu"]["flushes"] >= 1
    assert "stage_seconds" in extra["verify_stats"]
    assert "last_flush" in extra["verify_stats"]
    assert "device_up" in extra["device_health"]

    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        bench._emit_fallback("device initialization stalled (test)")
    rep = json.loads(buf.getvalue())
    assert rep["value"] == -1
    assert rep["extra"]["error"].startswith("device initialization stalled")
    assert "verify_stats" in rep["extra"]
    assert "device_health" in rep["extra"]


def _last_json(buf: str) -> dict:
    for line in reversed(buf.strip().splitlines()):
        try:
            return json.loads(line)
        except ValueError:
            continue
    raise AssertionError(f"no JSON in output: {buf!r}")


def test_scenario_fault_degrades_one_scenario_not_the_run(monkeypatch, capsys):
    """ISSUE 6 acceptance: a device fault in ONE scenario yields a
    clearly-marked CPU-fallback datapoint for that scenario while every
    other scenario (and the headline) survives — no whole-run -1."""
    bench = _bench()
    monkeypatch.setenv("TMTPU_BENCH_INPROC", "1")
    monkeypatch.setenv("TMTPU_BENCH_SCENARIOS", "cfg_a,dead_b,extra_c")
    monkeypatch.setenv("TMTPU_BENCH_BUDGET_S", "600")
    monkeypatch.setattr(bench, "_CONFIG_SIZES", {"cfg_a": (8, None)})
    fns = {
        "cfg_a": lambda: {"n": 8, "tpu_e2e_ms": 1.25, "speedup_e2e": 2.0},
        "dead_b": lambda: (_ for _ in ()).throw(
            RuntimeError("injected device stall")
        ),
        "extra_c": lambda: {"blocks_per_sec": 42},
    }
    monkeypatch.setattr(bench, "_scenario_fns", lambda: fns)
    monkeypatch.setattr(
        bench,
        "_cpu_fallback_fns",
        lambda: {"dead_b": lambda: {"cpu_blocks_per_sec": 3}},
    )
    bench.main()
    rep = _last_json(capsys.readouterr().out)
    # headline survived the faulted scenario
    assert rep["metric"] == "cfg_a_latency" and rep["value"] == 1.25
    # the faulted scenario still emitted a parseable, clearly-marked datapoint
    dead = rep["extra"]["dead_b"]
    assert dead["degraded"] == "cpu-fallback"
    assert "injected device stall" in dead["degrade_reason"]
    assert dead["cpu_blocks_per_sec"] == 3
    # unaffected scenarios ran normally
    assert rep["extra"]["extra_c"] == {"blocks_per_sec": 42}


def test_degraded_headline_is_marked_at_top_level(monkeypatch, capsys):
    """When the only available headline is a CPU-fallback measurement, the
    top-level JSON says so — a consumer tracking metric/value across rounds
    must never mistake a host-loop number for a device datapoint."""
    bench = _bench()
    monkeypatch.setenv("TMTPU_BENCH_INPROC", "1")
    monkeypatch.setenv("TMTPU_BENCH_SCENARIOS", "cfg_a")
    monkeypatch.setenv("TMTPU_BENCH_BUDGET_S", "600")
    monkeypatch.setattr(bench, "_CONFIG_SIZES", {"cfg_a": (8, None)})

    def boom():
        raise RuntimeError("device gone")

    monkeypatch.setattr(bench, "_scenario_fns", lambda: {"cfg_a": boom})
    monkeypatch.setattr(
        bench,
        "_cpu_fallback_fns",
        lambda: {"cfg_a": lambda: {"n": 8, "tpu_e2e_ms": 9.9, "speedup_e2e": 1.0}},
    )
    bench.main()
    rep = _last_json(capsys.readouterr().out)
    assert rep["value"] == 9.9
    assert rep["degraded"] == "cpu-fallback"
    assert "device gone" in rep["degrade_reason"]
    assert rep["extra"]["cfg_a"]["degraded"] == "cpu-fallback"


def test_all_scenarios_failing_still_emits_every_datapoint(monkeypatch, capsys):
    bench = _bench()
    monkeypatch.setenv("TMTPU_BENCH_INPROC", "1")
    monkeypatch.setenv("TMTPU_BENCH_SCENARIOS", "dead_a,dead_b")
    monkeypatch.setenv("TMTPU_BENCH_BUDGET_S", "600")
    monkeypatch.setattr(bench, "_CONFIG_SIZES", {})

    def boom():
        raise RuntimeError("device down")

    monkeypatch.setattr(
        bench, "_scenario_fns", lambda: {"dead_a": boom, "dead_b": boom}
    )
    monkeypatch.setattr(bench, "_cpu_fallback_fns", lambda: {})
    bench.main()
    rep = _last_json(capsys.readouterr().out)
    assert rep["value"] == -1  # no headline possible...
    for name in ("dead_a", "dead_b"):  # ...but every scenario is accounted for
        assert rep["extra"][name]["degraded"] == "cpu-fallback"
        assert "device down" in rep["extra"][name]["degrade_reason"]


def test_bench_fault_hook_fires_for_named_scenario_only(monkeypatch, capsys):
    bench = _bench()
    monkeypatch.setenv("TMTPU_BENCH_INPROC", "1")
    monkeypatch.setenv("TMTPU_BENCH_SCENARIOS", "selftest_fast")
    monkeypatch.setenv("TMTPU_BENCH_FAULT", "selftest_fast:raise")
    monkeypatch.setenv("TMTPU_BENCH_BUDGET_S", "600")
    monkeypatch.setattr(bench, "_CONFIG_SIZES", {})
    bench.main()
    rep = _last_json(capsys.readouterr().out)
    st = rep["extra"]["selftest_fast"]
    assert st["degraded"] == "cpu-fallback"
    assert "injected bench fault" in st["degrade_reason"]
    # the degraded (CPU) retry must NOT re-fire the fault
    assert "error" not in st


def test_scenario_child_subprocess_protocol():
    """One real scenario child: prints exactly one JSON line with the
    scenario report, isolated in its own process."""
    import subprocess

    env = dict(
        os.environ,
        TMTPU_BENCH_SCENARIO="selftest_fast",
        JAX_PLATFORMS="cpu",
        TMTPU_CRYPTO_BACKEND="cpu",
    )
    p = subprocess.run(
        [sys.executable, "bench.py"],
        capture_output=True,
        text=True,
        cwd=os.path.dirname(os.path.abspath(_bench().__file__)),
        env=env,
        timeout=240,
    )
    assert p.returncode == 0, p.stderr[-2000:]
    rep = json.loads(p.stdout.strip().splitlines()[-1])
    assert rep["scenario"] == "selftest_fast"
    assert rep["ok"] is True
    assert rep["result"]["marker"] == "selftest"
    assert "verify_stats" in rep["flight"]


def test_help_documents_scenario_isolation_and_slope():
    import subprocess

    p = subprocess.run(
        [sys.executable, "bench.py", "--help"],
        capture_output=True,
        text=True,
        cwd=os.path.dirname(os.path.abspath(_bench().__file__)),
        timeout=120,
    )
    assert p.returncode == 0
    assert "slope_samples" in p.stdout
    assert "cpu-fallback" in p.stdout
    assert "TMTPU_BENCH_FAULT" in p.stdout


def test_guarded_main_emits_fallback_on_dead_child(tmp_path, monkeypatch):
    bench = _bench()
    stub = tmp_path / "dead_bench.py"
    stub.write_text("import sys\nsys.exit(3)\n")
    monkeypatch.setattr(bench, "__file__", str(stub))
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        bench.guarded_main()
    rep = json.loads(buf.getvalue())
    assert rep["value"] == -1
    assert "rc=3" in rep["extra"]["error"]


def test_multichip_scenario_shape(monkeypatch):
    """ISSUE 11 satellite: the `multichip` scenario wires the fused
    single-chip AND sharded arms into one report (internals stubbed — the
    real kernels are device-round work; this pins the plumbing: slope
    samples attached, mesh telemetry attached, the ledger's `speedup`
    key present)."""
    import numpy as np

    bench = _bench()
    from tendermint_tpu.crypto import batch as B

    monkeypatch.setattr(bench, "time_rlc", lambda *a, **k: (0.5, 0.2, 0.01))
    monkeypatch.setattr(
        bench, "rlc_slope_samples", lambda *a, **k: ([[1, 0.1], [2, 0.2]], 100.0)
    )
    monkeypatch.setattr(
        bench, "make_batch",
        lambda n, **k: ([b"\x01" * 32] * n, [b"m"] * n, [b"\x02" * 64] * n,
                        ["ed25519"] * n),
    )
    monkeypatch.setattr(
        B, "verify_batch_jax",
        lambda pk, ms, sg: np.ones(len(pk), dtype=bool),
    )
    monkeypatch.setattr(B, "_sharded_env", lambda: (8, None, None))
    B.LAST_JAX_PATH[0] = "rlc-sharded"
    rep = bench.bench_multichip(n=64)
    assert rep["single_chip"]["rlc_e2e_ms"] == 200.0
    assert rep["single_chip"]["slope_samples"] == [[1, 0.1], [2, 0.2]]
    assert rep["sharded"]["n_devices"] == 8
    assert "mesh_telemetry" in rep["sharded"]
    assert rep["speedup"] > 0  # single-vs-sharded ratio (stub arms)
    assert rep["sigs_per_sec_sharded"] > 0
    assert os.environ.get("TMTPU_SHARDED") is None  # env restored
