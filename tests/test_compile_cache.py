"""The one compile-cache rule (ops/aot_cache.configure_compile_cache): every
entry point calls it before its first compile, and nothing else sets the
directory."""

import glob
import os
import re

import jax
import pytest

from tendermint_tpu.ops import aot_cache
from tendermint_tpu.ops.cache_hardening import machine_fingerprint

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


@pytest.fixture
def restore_cache_config():
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


def test_env_dir_is_used_as_is(monkeypatch, restore_cache_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    assert aot_cache.configure_compile_cache() == "/some/dir"
    assert jax.config.jax_compilation_cache_dir == "/some/dir"
    assert aot_cache._cache_dir() == "/some/dir/export"


def test_default_is_fixed_path_under_checkout(monkeypatch, restore_cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = aot_cache.configure_compile_cache()
    assert first == os.path.join(ROOT, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == first
    assert aot_cache.configure_compile_cache() == first  # no pid, time, tmp name


@pytest.mark.parametrize("env", ["/some/dir", None], ids=["env", "default"])
def test_cpu_lane_is_a_per_machine_subdirectory(env, monkeypatch, restore_cache_config):
    if env:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env)
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    root = env or os.path.join(ROOT, ".jax_cache")
    got = aot_cache.configure_compile_cache(cpu_lane=True)
    assert got == os.path.join(root, "cpu", f"mach-{machine_fingerprint()}")
    assert jax.config.jax_compilation_cache_dir == got


def _py_files():
    for pat in ("*.py", "tools/*.py", "tests/*.py", "tendermint_tpu/**/*.py"):
        yield from glob.glob(os.path.join(ROOT, pat), recursive=True)


def test_no_other_call_site_sets_the_directory():
    setter = re.compile(
        r"""update\(\s*["']jax_compilation_cache_dir|"""
        r"""environ(\.setdefault\(|\[)\s*["']JAX_COMPILATION_CACHE_DIR["']\s*(,|\]\s*=)"""
    )
    offenders = [
        os.path.relpath(p, ROOT)
        for p in _py_files()
        if setter.search(open(p).read())
        and os.path.relpath(p, ROOT)
        not in ("tendermint_tpu/ops/aot_cache.py", "tests/test_compile_cache.py")
    ]
    assert offenders == []


@pytest.mark.parametrize(
    "entry",
    [
        "tendermint_tpu/cli/main.py", "chip_smoke.py", "bench.py",
        "tools/profile_msm.py", "tools/micro_fe.py", "__graft_entry__.py",
        "tests/conftest.py",
    ],
)
def test_entry_point_applies_the_rule(entry):
    assert "configure_compile_cache(" in open(os.path.join(ROOT, entry)).read()
