"""AOT artifact cache: cold-start without retracing.

The RLC kernels trace to ~400k jaxpr equations (every Pallas call site
inlines its kernel body), so a FRESH PROCESS pays ~70 s of pure Python
tracing/lowering per (kernel, shape bucket) — even when XLA's persistent
compile cache HITS (measured r4: 71 s first call on a cache hit, 27 s of
which was XLA; the rest tracing). jax.export solves this: the traced+
lowered StableHLO is serialized to disk once, and later processes
deserialize and call it directly — no tracing.

Artifacts live in .jax_cache/export/, keyed by kernel name + arg
shapes/dtypes + a hash of the kernel source files (so any kernel edit
invalidates them). XLA compilation of a deserialized artifact still goes
through the persistent compile cache, so a warm machine pays only
deserialize + device program load."""

from __future__ import annotations

import hashlib
import os
import tempfile
import threading
import time
from typing import Callable, Dict

import jax
import numpy as np

_LOCK = threading.Lock()
_MEM: Dict[str, Callable] = {}
_KEY_LOCKS: Dict[str, object] = {}
_SRC_HASH: str | None = None


def _src_hash() -> str:
    """Hash of the kernel-defining sources: edits invalidate artifacts."""
    global _SRC_HASH
    if _SRC_HASH is None:
        h = hashlib.sha256()
        base = os.path.dirname(os.path.abspath(__file__))
        for mod in (
            "fe25519.py",
            "ed25519_jax.py",
            "msm_jax.py",
            "pallas_fe.py",
            "pallas_msm.py",  # fused-pipeline kernels (traced into *_f keys)
            "ristretto_jax.py",  # traced into the mixed kernel
        ):
            with open(os.path.join(base, mod), "rb") as f:
                h.update(f.read())
        h.update(jax.__version__.encode())
        _SRC_HASH = h.hexdigest()[:16]
    return _SRC_HASH


def _machine_key() -> str:
    """Host machine fingerprint component of artifact keys. An artifact's
    first CALL compiles through XLA's persistent cache, whose CPU entries
    bake in host CPU features — loading a foreign-machine artifact then
    fails in cpu_aot_loader (the failure that killed every MULTICHIP round,
    MULTICHIP_r05.json). Keying on the fingerprint makes a foreign artifact
    a MISS — skipped and re-exported — never loaded. TPU programs are
    host-portable, so only the backend that compiles for the host CPU is
    scoped."""
    if jax.default_backend() != "cpu":
        return "anyhost"
    from tendermint_tpu.ops.cache_hardening import machine_fingerprint

    return machine_fingerprint()


def compile_cache_root() -> str:
    """JAX_COMPILATION_CACHE_DIR where it is set, else <checkout>/.jax_cache."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
        ".jax_cache",
    )


def configure_compile_cache(cpu_lane: bool = False) -> str:
    """The one compile-cache rule; every entry point calls it before its
    first compile (cli start, chip_smoke.py, bench.py children, the tools/
    scripts, __graft_entry__, tests/conftest.py) and nothing else sets the
    directory. Where JAX_COMPILATION_CACHE_DIR is set the cache lives there;
    where it is not, at <checkout>/.jax_cache. Both are fixed paths — the
    path is part of the cache key, so one built from a pid, the time or a
    temporary name would never hit. `cpu_lane` (the CPU test lane and the
    CPU-only multichip dry run) takes the per-machine subdirectory
    cpu/mach-<fingerprint> of that root: XLA:CPU executables bake in the
    compile host's CPU features (ops/cache_hardening.py), and the name is a
    function of those features alone. Returns the directory in use;
    _cache_dir() derives export/ from it."""
    from tendermint_tpu.ops import cache_hardening

    d = compile_cache_root()
    if cpu_lane:
        d = cache_hardening.machine_scoped_cache_dir(os.path.join(d, "cpu"))
    jax.config.update("jax_compilation_cache_dir", d)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    # JAX 0.9.0's LRUCache.put still writes entries with a plain
    # write_bytes: a killed writer must not leave a truncated executable
    cache_hardening.harden()
    return d


def _cache_dir() -> str | None:
    d = jax.config.jax_compilation_cache_dir or os.environ.get(
        "JAX_COMPILATION_CACHE_DIR"
    )
    if not d:
        return None
    return os.path.join(d, "export")


def _arg_key(args) -> str:
    h = hashlib.sha256()
    for leaf in jax.tree_util.tree_leaves(args):
        h.update(str(np.shape(leaf)).encode())
        h.update(str(np.asarray(leaf).dtype if not hasattr(leaf, "dtype") else leaf.dtype).encode())
    return h.hexdigest()[:16]


_REGISTERED = False


def _register_pytrees() -> None:
    """The kernel arg NamedTuples must be registered for export
    serialization (once per process)."""
    global _REGISTERED
    if _REGISTERED:
        return
    from jax import export as jexport

    from tendermint_tpu.ops.ed25519_jax import FieldCtx
    from tendermint_tpu.ops.msm_jax import SmallCtx

    for t in (FieldCtx, SmallCtx):
        try:
            jexport.register_namedtuple_serialization(
                t, serialized_name=f"tendermint_tpu.{t.__name__}"
            )
        except ValueError:
            pass  # already registered
    _REGISTERED = True


def call(name: str, jit_fn, *args):
    """Call `jit_fn(*args)` through the AOT artifact cache.

    First use on a machine: traces + exports + serializes (background cost,
    same as before). Later processes: deserialize (~1 s) instead of
    retracing (~70 s). Falls back to the plain jit call on any export
    machinery failure. On every backend, the CPU included: the test suite's
    kernel lane was retracing ~400k-eq jaxprs in every process; artifacts
    are keyed per backend, so CPU and TPU never collide."""
    key = (
        f"{name}-{jax.default_backend()}-{_machine_key()}-"
        f"{_src_hash()}-{_arg_key(args)}"
    )
    fn = _MEM.get(key)
    if fn is not None:
        return fn(*args)
    # per-key in-flight guard: the prewarm thread and the event loop must
    # not both pay the ~70s export trace for the same kernel
    with _LOCK:
        klock = _KEY_LOCKS.setdefault(key, __import__("threading").Lock())
    with klock:
        fn = _MEM.get(key)
        if fn is not None:
            return fn(*args)
        return _call_locked(name, key, jit_fn, *args)


def _record_aot(result: str) -> None:
    """Artifact-cache outcome into the mesh telemetry (hit / miss /
    corrupt): machine-scoped keys mean a foreign host's artifacts surface
    here as misses instead of the cpu_aot_loader failures that killed
    MULTICHIP r04/r05 — the counter is how a round proves which it was."""
    try:
        from tendermint_tpu.parallel import telemetry as _mesh_tm

        _mesh_tm.record_aot(result)
    except Exception:  # telemetry must never fail a kernel call
        pass


def _call_locked(name, key, jit_fn, *args):
    from tendermint_tpu.libs import trace as _trace

    try:
        from jax import export as jexport

        _register_pytrees()
        d = _cache_dir()
        path = os.path.join(d, key + ".bin") if d else None
        exp = None
        corrupt = False
        if path and os.path.exists(path):
            try:
                _t0 = time.perf_counter()
                with open(path, "rb") as f:
                    exp = jexport.deserialize(bytearray(f.read()))
                _trace.record_compile(
                    name, time.perf_counter() - _t0, "deserialize"
                )
                _record_aot("hit")
            except Exception:
                # Corrupted artifact: delete it and fall through to a fresh
                # export — permanently disabling the AOT path for this key
                # (the old behavior) made every future process repay both
                # the failed deserialize AND the ~70 s retrace.
                import logging

                logging.getLogger("tendermint_tpu.ops.aot").warning(
                    "corrupt AOT artifact %s; deleting and re-exporting", path
                )
                try:
                    os.unlink(path)
                except OSError:
                    pass
                _record_aot("corrupt")
                exp = None
                corrupt = True
        if exp is None:
            if not corrupt:
                # hit/miss/corrupt are disjoint outcomes per call — a
                # corrupt artifact is NOT also a miss
                _record_aot("miss")
            _t0 = time.perf_counter()
            exp = jexport.export(jit_fn)(*args)
            # trace+lower+export wall time — the "compile" half of the
            # compile-vs-execute split (XLA's own compile of the artifact
            # happens inside the first wrapped call, below)
            _trace.record_compile(name, time.perf_counter() - _t0, "export")
            if path:
                os.makedirs(d, exist_ok=True)
                blob = exp.serialize()
                fd, tmp = tempfile.mkstemp(dir=d, prefix=".aot-")
                with os.fdopen(fd, "wb") as f:
                    f.write(blob)
                os.replace(tmp, path)
        wrapped = jax.jit(exp.call)
    except Exception:
        import logging

        logging.getLogger("tendermint_tpu.ops.aot").exception(
            "AOT export cache failed for %s; using plain jit", name
        )
        with _LOCK:
            _MEM[key] = jit_fn
        return jit_fn(*args)
    with _LOCK:
        _MEM[key] = wrapped
    # Outside the try: a RUNTIME error here (device OOM, transient device
    # failure) must propagate as itself, not be mislabeled as an export
    # failure and permanently disable the AOT path for this key.
    _t0 = time.perf_counter()
    out = wrapped(*args)
    # The first call pays XLA compilation (or persistent-cache load) of the
    # artifact; recorded as its own kind so compile-vs-execute splits stay
    # honest — later calls on this key skip _call_locked entirely.
    _trace.record_compile(name, time.perf_counter() - _t0, "first_call")
    return out
