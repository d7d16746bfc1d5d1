"""chip_smoke.py must FAIL, not fall back: each condition that would let a
run pass without the device is injected once here, on the CPU, without
building a validator (the phases themselves need the chip; their walk at a
tiny size is a rehearsal, not a test)."""

import importlib.util
import logging
import os

import pytest

_PATH = os.path.join(os.path.dirname(__file__), "..", "chip_smoke.py")


@pytest.fixture
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", _PATH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    yield mod
    logging.getLogger("tendermint_tpu").removeHandler(mod.ALARM)


def _device_reading(**over):
    r = {
        "smoke": "flush", "phase": "t", "rows": 10, "backend": "jax",
        "path": "rlc-pipelined", "jax_path": "rlc-pipelined", "mode": "pipelined",
        "lane_bucket": 12288, "chunks": 1, "fused": True, "rlc_fallback": False,
        "recovery_flushes": None, "compile_s_inside": 0.0, "wall_s_single_run": 0.1,
    }
    r.update(over)
    return r


def _no_data(*a, **k):
    raise AssertionError("built validators before the run was allowed to")


def test_exits_nonzero_on_cpu_before_building_anything(smoke, monkeypatch, capsys):
    monkeypatch.setattr(smoke, "make_signed_set", _no_data)
    with pytest.raises(SystemExit) as e:
        smoke.main([])
    assert e.value.code not in (0, None)
    assert "'cpu'" in str(e.value.code)  # says which platform it found
    assert capsys.readouterr().out == ""  # no result line, nothing at all


def test_wrong_chip_count_exits_nonzero(smoke, monkeypatch):
    import jax

    class _Dev:
        platform, device_kind = "tpu", "TPU v5 lite"

    monkeypatch.setattr(jax, "devices", lambda *a: [_Dev()])
    assert smoke.require_tpu(1) == {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    with pytest.raises(SystemExit):
        smoke.require_tpu(4)


def test_healthy_device_flush_passes(smoke, capsys):
    smoke.check_device_flush(_device_reading(), 10, {"rlc", "rlc-pipelined"}, False)
    assert '"path": "rlc-pipelined"' in capsys.readouterr().out


@pytest.mark.parametrize(
    "over",
    [
        {"backend": "cpu", "path": "cpu-degraded"},  # device -> host rung
        {"backend": "cpu", "path": "cpu-breaker"},  # breaker open -> host
        {"path": "persig", "jax_path": "persig"},  # RLC -> per-signature
        {"rlc_fallback": True},  # recovery ladder on a valid batch
        {"fused": False},  # unfused retry
        {"rows": 9},  # a memo/residue flush, not the whole batch
    ],
    ids=["cpu-degraded", "cpu-breaker", "persig", "rlc-fallback", "unfused", "rows"],
)
def test_flush_off_its_claimed_path_fails(smoke, over, capsys):
    with pytest.raises(AssertionError):
        smoke.check_device_flush(
            _device_reading(**over), 10, {"rlc", "rlc-pipelined"}, False
        )
    capsys.readouterr()


def test_disabled_fused_pipeline_fails(smoke, monkeypatch, capsys):
    from tendermint_tpu.ops import msm_jax

    monkeypatch.setattr(msm_jax, "_FUSED_DISABLED", ["Mosaic said no"])
    with pytest.raises(AssertionError, match="fused pipeline disabled"):
        smoke.check_device_flush(_device_reading(), 10, {"rlc-pipelined"}, False)
    capsys.readouterr()


def test_breaker_failure_fails(smoke, capsys):
    from tendermint_tpu.crypto import batch

    batch.BREAKER.record_failure("injected")
    try:
        with pytest.raises(AssertionError, match="breaker"):
            smoke.check_device_flush(_device_reading(), 10, {"rlc-pipelined"}, False)
    finally:
        batch.BREAKER.record_success(0.0)
    capsys.readouterr()


def _past_platform_check(smoke, monkeypatch):
    monkeypatch.setattr(
        smoke, "require_tpu", lambda chips: {"platform": "tpu", "kind": "t", "count": chips}
    )
    # the run must not rewire this process's compile cache
    from tendermint_tpu.ops import aot_cache

    monkeypatch.setattr(aot_cache, "configure_compile_cache", lambda: "unchanged")


def test_warning_during_device_phase_fails(smoke, monkeypatch, capsys):
    _past_platform_check(smoke, monkeypatch)

    def phase(seed, compiles):
        logging.getLogger("tendermint_tpu.crypto.batch").warning(
            "device verification failed; degrading flush to CPU"
        )

    monkeypatch.setattr(smoke, "run_one_chip", phase)
    with pytest.raises(AssertionError, match="degrading flush to CPU"):
        smoke.main([])
    out = capsys.readouterr().out
    assert '"ok"' not in out.strip().splitlines()[-1]  # no result line


def test_missing_native_library_fails(smoke, monkeypatch, capsys):
    from tendermint_tpu import native

    _past_platform_check(smoke, monkeypatch)
    monkeypatch.setattr(native, "available", lambda: False)
    monkeypatch.setattr(smoke, "make_signed_set", _no_data)
    with pytest.raises(AssertionError, match="native prep library"):
        smoke.main([])
    capsys.readouterr()


def test_result_line_is_last_and_exact(smoke, monkeypatch, capsys):
    import json

    _past_platform_check(smoke, monkeypatch)
    monkeypatch.setattr(smoke, "run_four_chips", lambda seed, compiles: None)
    assert smoke.main(["--chips", "4"]) == 0
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(last) == {
        "ok": True, "device": {"platform": "tpu", "kind": "t", "count": 4}
    }


def test_one_process_only():
    src = open(_PATH).read()
    assert "subprocess" not in src and "multiprocessing" not in src
    assert "JAX_PLATFORMS" not in src and "from tests" not in src
