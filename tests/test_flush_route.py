"""ISSUE 31 — one place decides a flush's path (crypto/batch._flush_route).

  (a) the routing table, case by case: the label `_flush_route` returns is
      the `path` on the flush record `verify_batch` then writes;
  (b) the scheduler's inline-fallback question agrees with it;
  (c) `prewarm` writes no module global: a flush that arrives during it is
      routed as at any other time;
  (d) the `TMTPU_*` variables the package reads are a listed set, so the
      next switch is added on purpose.

Device kernels are ed25519_ref host twins (tests/test_flush_planner.py).
"""

import os
import re
import threading

import numpy as np
import pytest

from tendermint_tpu.crypto import batch
from tendermint_tpu.libs import trace
from test_flush_planner import _fake_mesh_env, _install_host_twins, _signed_rows

CHUNK = 31  # rows a chunk at a 64-lane planner budget


@pytest.fixture
def small_table(monkeypatch):
    """Every threshold at a few rows: auto-jax floor 8, RLC floor 12, stream
    floor 16, 31 rows a chunk. Memo off (a hit would answer in a flush's
    place), backend auto = jax, breaker closed, no mesh."""
    _install_host_twins(monkeypatch)
    monkeypatch.delenv("TMTPU_CRYPTO_BACKEND", raising=False)
    monkeypatch.setenv("TMTPU_SHARDED", "0")
    monkeypatch.setattr(batch, "_JAX_MIN_BATCH", 8)
    monkeypatch.setattr(batch, "RLC_MIN", 12)
    prev_cfg, prev_budget = dict(batch._PREP_CFG), batch.planner_budget()
    batch._PREP_CFG["stream_floor"] = 16
    batch.configure_planner(max_flush_lanes=2 * (CHUNK + 1))
    batch.configure_verified_memo(0)
    batch.BREAKER.reset()
    yield
    batch._PREP_CFG.clear()
    batch._PREP_CFG.update(prev_cfg)
    batch.configure_planner(max_flush_lanes=prev_budget)
    batch.configure_verified_memo(batch._memo_env_rows())
    batch.BREAKER.reset()


def _mesh(monkeypatch):
    """A mesh runner stands: the per-signature twin as its sharded kernel,
    test_flush_planner's host-twin stream as its chunk runner."""
    from tendermint_tpu.ops import ed25519_jax

    nd, _, _, stream = _fake_mesh_env(4)
    env = (nd, lambda *a: ed25519_jax.verify_prepared(*a), None, stream)
    monkeypatch.setattr(batch, "_sharded_env", lambda: env)


def _breaker_open(monkeypatch):
    monkeypatch.setattr(batch.BREAKER, "allow_device", lambda: False)


def _combined_check_passes(monkeypatch):
    """The mixed kernel has no host twin: its executor answers for it."""
    monkeypatch.setattr(
        batch, "_verify_batch_rlc",
        lambda pk, m, s, kt=None: np.ones(len(pk), dtype=bool),
    )


MIXED = ["ed25519"] * 13 + ["sr25519"]
UNKNOWN = ["ed25519"] * 13 + ["secp256k1"]

# id: (rows, backend asked for, key types, what stands, label, backend, async)
TABLE = {
    "auto-under-jax-min": (6, None, None, None, "cpu", "cpu", False),
    "explicit-under-jax-min": (6, "jax", None, None, "persig", "jax", False),
    "host-asked-for": (24, "cpu", None, None, "cpu", "cpu", False),
    "under-rlc-min": (10, None, None, None, "persig", "jax", False),
    "under-the-floor": (14, None, None, None, "rlc", "jax", True),
    "over-the-floor": (24, None, None, None, "rlc-pipelined", "jax", True),
    "the-chunk-itself": (CHUNK, None, None, None, "rlc-pipelined", "jax", True),
    "over-the-chunk": (CHUNK + 9, None, None, None, "rlc-streamed", "jax", False),
    "breaker-open": (24, "jax", None, _breaker_open, "cpu-breaker", "cpu", False),
    "mesh-under-rlc-min": (6, "jax", None, _mesh, "sharded", "jax", False),
    "mesh-over-the-chunk": (CHUNK + 9, "jax", None, _mesh, "rlc-sharded-streamed", "jax", False),
    "mixed": (14, "jax", MIXED, _combined_check_passes, "rlc-mixed", "jax", True),
    "mixed-auto-under-jax-min": (14, None, MIXED, _combined_check_passes, "rlc-mixed", "jax", True),
    "mixed-unknown-type": (14, "jax", UNKNOWN, None, "mixed", "jax", False),
    "mixed-breaker-open": (14, "jax", MIXED, _breaker_open, "mixed", "jax", False),
}


@pytest.mark.parametrize("case", sorted(TABLE))
def test_route_label_is_the_flush_records_path(small_table, monkeypatch, case):
    n, backend, key_types, stands, label, be, async_ok = TABLE[case]
    if stands is not None:
        stands(monkeypatch)
    route = batch._flush_route(n, backend, key_types)
    assert route == (label, be, async_ok)
    pks, msgs, sigs = _signed_rows(n, b"\x31")
    mask = batch.verify_batch(pks, msgs, sigs, backend, key_types)
    last = trace.verify_stats()["last_flush"]
    assert (last["path"], last["backend"], last["n"]) == (label, be, n)
    if key_types is None:
        assert mask.all()
        # the device executor, asked alone, agrees wherever the route is its
        assert be != "jax" or batch._flush_route(n, on_device=True).path == label


def test_mixed_auto_under_jax_min_is_not_submitted_async(small_table, monkeypatch):
    """The one place the two copies differed on purpose: an auto-selected
    backend submits nothing asynchronously under _JAX_MIN_BATCH rows."""
    monkeypatch.setattr(batch, "_JAX_MIN_BATCH", 16)
    assert batch._flush_route(14, None, MIXED) == ("rlc-mixed", "jax", False)
    assert batch._flush_route(14, "jax", MIXED) == ("rlc-mixed", "jax", True)


def test_unknown_backend_is_refused_by_the_route(small_table):
    with pytest.raises(ValueError, match="unknown crypto backend"):
        batch._flush_route(24, "gpu")


@pytest.mark.parametrize(
    "case", sorted(c for c in TABLE if TABLE[c][2] is None)
)
def test_inline_on_host_asks_the_route(small_table, monkeypatch, case):
    """crypto/scheduler holds no copy of the rule: an inline verify runs on
    the host exactly where the route's backend is not the device."""
    from tendermint_tpu.crypto.scheduler import VerifyScheduler

    n, backend, _, stands, label, be, _ = TABLE[case]
    if stands is not None:
        stands(monkeypatch)
    s = VerifyScheduler(backend=backend)
    try:
        assert s._inline_on_host(n) is (label in ("cpu", "cpu-breaker"))
        assert s._inline_on_host(n) is (batch._flush_route(n, backend).backend != "jax")
    finally:
        s.close()


def test_prewarm_writes_no_global_a_live_flush_keeps_its_route(small_table, monkeypatch):
    """A flush of 2,048 rows or more that arrives while the node's
    background thread is inside `prewarm` takes the chunk bucket, as at any
    other time, and `_PREP_CFG` reads as prewarm found it at every warm."""
    monkeypatch.setattr(batch, "_JAX_MIN_BATCH", 256)
    monkeypatch.setattr(batch, "RLC_MIN", 512)
    batch._PREP_CFG["stream_floor"] = 2048
    batch.configure_planner(max_flush_lanes=24576)
    before = dict(batch._PREP_CFG)
    seen = []

    def live_flush():
        # what verify_batch_jax asks when rows arrive on another thread
        seen.append((batch._flush_route(2048, on_device=True).path,
                     batch._flush_route(10000).path, dict(batch._PREP_CFG)))

    def warm(pk, m, s, *a, **kw):
        t = threading.Thread(target=live_flush)
        t.start()
        t.join()
        return np.ones(len(pk), dtype=bool)

    monkeypatch.setattr(batch, "verify_batch_jax", warm)
    monkeypatch.setattr(batch, "_verify_batch_rlc", warm)
    monkeypatch.setattr(batch, "_prewarm_survivor_mesh", lambda *a: None)
    batch.prewarm(4096)
    assert len(seen) == 3  # plain, cached-A, the chunk bucket
    for at_2048, at_10k, cfg in seen:
        assert (at_2048, at_10k) == ("rlc-pipelined", "rlc-pipelined")
        assert cfg == before
    assert batch._PREP_CFG == before


def test_prewarm_returns_where_the_route_is_the_host(small_table, monkeypatch):
    called = []
    monkeypatch.setattr(batch, "verify_batch_jax", lambda *a: called.append(a))
    monkeypatch.setattr(batch, "_verify_batch_rlc", lambda *a: called.append(a))
    batch.prewarm(6)  # auto, under _JAX_MIN_BATCH
    batch.prewarm(24, backend="cpu")
    _breaker_open(monkeypatch)
    batch.prewarm(24)
    assert not called


# Every TMTPU_* variable the package reads. A new one is a new switch: add it
# here on purpose, with the caller that needs a value of its own (ROADMAP C5).
TMTPU_VARIABLES = {
    "TMTPU_BISECT",  # bench.py's baseline arm, until bench.py goes
    "TMTPU_CRYPTO_BACKEND",
    "TMTPU_ED25519_MODE",
    "TMTPU_FAIL_INDEX",
    "TMTPU_FLEET_SEED",
    "TMTPU_FORENSICS_DIR",
    "TMTPU_FUSED_MSM",
    "TMTPU_HOME",
    "TMTPU_HOST_STRIPE",
    "TMTPU_MAX_FLUSH_LANES",
    "TMTPU_NATIVE",
    "TMTPU_PALLAS",
    "TMTPU_PREP_STREAM_FLOOR",
    "TMTPU_PREP_THREADS",
    "TMTPU_SECRET_CONNECTION_TRANSCRIPT",
    "TMTPU_SHARDED",
    "TMTPU_TRACE",
    "TMTPU_VERIFIED_MEMO_ROWS",
}


def test_the_packages_tmtpu_variables_are_the_listed_set():
    root = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                        "tendermint_tpu")
    found = set()
    for d, _, files in os.walk(root):
        for f in files:
            if f.endswith(".py"):
                with open(os.path.join(d, f), encoding="utf-8") as fh:
                    found |= set(re.findall(r"TMTPU_[A-Z_0-9]+", fh.read()))
    assert found == TMTPU_VARIABLES
