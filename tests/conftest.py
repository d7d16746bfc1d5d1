"""Test configuration.

Must run before jax initializes: force the CPU platform with 8 virtual devices
so multi-chip sharding paths (jax.sharding.Mesh over 8 devices) are exercised
without TPU hardware. The chip is reached through chip_smoke.py, which imports
nothing from tests/.
"""

import os

# Force CPU whatever the ambient environment selects; override with
# TMTPU_TEST_PLATFORM to run the tests on hardware.
_platform = os.environ.get("TMTPU_TEST_PLATFORM", "cpu")
os.environ["JAX_PLATFORMS"] = _platform
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

# Persistent compilation cache (the ed25519 scan kernel is expensive to
# compile on CPU): the package's one cache rule (ops/aot_cache.py), on the
# CPU lane's per-machine subdirectory — XLA:CPU executables bake in the
# compile host's CPU features, so hosts never share entries.
from tendermint_tpu.ops.aot_cache import configure_compile_cache  # noqa: E402

configure_compile_cache(cpu_lane=_platform == "cpu")


try:
    import cryptography  # noqa: F401

    HAVE_CRYPTOGRAPHY = True
except ImportError:  # minimal containers: crypto/keys.py falls back to the
    HAVE_CRYPTOGRAPHY = False  # pure-Python ed25519 (see keys._HAVE_OPENSSL)

import pytest  # noqa: E402

# For tests that need the `cryptography` wheel itself (p2p secret
# connection, armor's ChaCha/Scrypt, signer-socket auth) or its OpenSSL
# speed — the pure-Python fallback can't stand in for those.
requires_cryptography = pytest.mark.skipif(
    not HAVE_CRYPTOGRAPHY,
    reason="needs the `cryptography` wheel (OpenSSL)",
)


@pytest.fixture(autouse=True)
def _verified_memo_off():
    """The cross-flush verified-row memo (crypto/batch.py ISSUE 18) is
    process-global state that changes which flushes run device work — a
    repeat verify of the same rows answers from the memo. Tests assert
    path/flush-count behavior on exactly such repeats, so each test runs
    with the memo DISABLED unless it installs one itself
    (configure_verified_memo / node config)."""
    from tendermint_tpu.crypto import batch

    prev = batch._MEMO
    batch._MEMO = batch.VerifiedRowMemo(0)
    yield
    batch._MEMO = prev


def free_compile_memory() -> None:
    """Drop every previously-compiled executable in this process. Used as a
    module fixture by the heavyweight kernel test modules: XLA ABORTED
    (SIGABRT in backend_compile r4, in the persistent-cache read path r5)
    compiling/deserializing their multi-hundred-MB executables in a process
    already holding many earlier tests' executables. Later tests reload
    from the persistent cache."""
    import gc

    import jax as _jax

    _jax.clear_caches()
    gc.collect()


@pytest.fixture(autouse=True)
def _gc_hook_off():
    """The flight recorder's garbage-collector hook (libs/trace.py) is off in
    every test unless it turns it on (`trace._hook_gc(True)`): a full
    collection lands wherever an allocation tips it, and would leave a
    `gc.collect` span in whatever ring a test holds to its exact spans."""
    from tendermint_tpu.libs import trace

    trace._hook_gc(False)
    yield
    trace._hook_gc(trace.tracer.enabled)
