"""Per-shard mesh telemetry for the multi-chip paths (parallel/sharded.py).

The sharded 8-chip path had ZERO instrumentation while every MULTICHIP round
died opaquely. This module is the aggregation half: `parallel/sharded.py`
(and `ops/aot_cache.py` for artifact hits/misses) record into a
process-global store + the `tendermint_mesh_*` Prometheus series
(libs/metrics.py MeshMetrics, process-global registry), and two read
surfaces serve it: the `mesh` block of `GET /debug/verify_stats` and the
dedicated `GET /debug/mesh` route (rpc/server.py). The multichip dryrun
(__graft_entry__) prints the same snapshot so even an rc-124 round leaves
per-shard evidence in its captured tail.

Deliberately jax-free: importable by the RPC layer / verify_stats on
CPU-only nodes without dragging in the sharded machinery.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Dict, List, Optional

_LOCK = threading.Lock()


def _fresh() -> Dict[str, Any]:
    return {
        "mesh": None,  # {"devices": [...], "shape": {...}, "platform"}
        "flushes": {},  # kind -> count
        "totals": {
            "submit_seconds": 0.0,
            "finish_seconds": 0.0,
            "all_gathers": 0,
            "all_gather_bytes": 0,
            "prep_seconds": 0.0,
            "prep_calls": 0,
        },
        "last_flush": None,
        "last_prep": None,
        "aot_cache": {},  # result -> count (hit / miss / corrupt)
        # Elastic mesh (ISSUE 19): per-device health + degrade ladder
        "health": None,  # parallel/health.MeshHealthManager.snapshot()
        "ladder": None,  # "full" | "survivor" | "single" | "host"
        "rebuilds": 0,
        "last_rebuild": None,
    }


_STATS: Dict[str, Any] = _fresh()


def _metrics():
    from tendermint_tpu.libs import metrics as _m

    return _m.mesh_metrics()


def record_mesh(axis_names, shape, devices, platform: str) -> None:
    """The mesh a sharded runner was built over (sharded_verify /
    sharded_rlc_check / sharded_commit_step construction time)."""
    info = {
        "axes": dict(zip(list(axis_names), [int(s) for s in shape])),
        "devices": [str(d) for d in devices],
        "n_devices": len(devices),
        "platform": platform,
    }
    with _LOCK:
        _STATS["mesh"] = info
    try:
        _metrics().devices.set(len(devices))
    except Exception:  # telemetry must never fail the verify path
        pass


def record_prepare(ndev: int, lanes_per_shard: int, seconds: float) -> None:
    """Host-side shard prep (prepare_rlc_shards): per-shard window sort +
    bucket boundaries."""
    with _LOCK:
        t = _STATS["totals"]
        t["prep_seconds"] += seconds
        t["prep_calls"] += 1
        _STATS["last_prep"] = {
            "shards": ndev,
            "lanes_per_shard": lanes_per_shard,
            "seconds": round(seconds, 6),
            "ts": time.time(),
        }
    try:
        _metrics().prep_seconds.inc(seconds)
    except Exception:
        pass


def record_pad(requested_lanes: int, padded_lanes: int) -> None:
    """Lane padding chosen by the routing layer (crypto/batch
    _verify_batch_rlc_sharded knows the real batch size; sharded.py only
    ever sees the padded arrays)."""
    waste = (
        (padded_lanes - requested_lanes) / padded_lanes if padded_lanes else 0.0
    )
    with _LOCK:
        last = _STATS.setdefault("last_pad", {})
        last.update(
            requested_lanes=requested_lanes,
            padded_lanes=padded_lanes,
            pad_waste_fraction=round(waste, 4),
        )
    try:
        _metrics().pad_waste_fraction.set(waste)
    except Exception:
        pass


def record_flush(
    kind: str,
    *,
    ndev: int,
    shard_lanes: int,
    submit_s: float,
    finish_s: float,
    all_gather_bytes: int = 0,
    devices: Optional[List[str]] = None,
    ok: Optional[bool] = None,
) -> None:
    """One sharded flush completed: `submit_s` = wall blocked dispatching
    the shard_map program, `finish_s` = wall blocked syncing its result
    (per-shard skew hides inside the finish)."""
    with _LOCK:
        _STATS["flushes"][kind] = _STATS["flushes"].get(kind, 0) + 1
        t = _STATS["totals"]
        t["submit_seconds"] += submit_s
        t["finish_seconds"] += finish_s
        if all_gather_bytes:
            t["all_gathers"] += 1
            t["all_gather_bytes"] += all_gather_bytes
        _STATS["last_flush"] = {
            "kind": kind,
            "shards": ndev,
            "lanes_per_shard": shard_lanes,
            "lanes_total": shard_lanes * ndev,
            "submit_ms": round(submit_s * 1e3, 3),
            "finish_ms": round(finish_s * 1e3, 3),
            "all_gather_bytes": all_gather_bytes,
            "devices": list(devices) if devices else None,
            "ok": ok,
            "ts": time.time(),
        }
    try:
        m = _metrics()
        m.flushes.labels(kind).inc()
        m.submit_seconds.inc(submit_s)
        m.finish_seconds.inc(finish_s)
        if all_gather_bytes:
            m.all_gathers.inc()
            m.all_gather_bytes.inc(all_gather_bytes)
        for i in range(ndev):
            dev = devices[i] if devices and i < len(devices) else str(i)
            m.shard_lanes.labels(dev).set(shard_lanes)
    except Exception:
        pass
    try:
        from tendermint_tpu.libs.trace import tracer

        if tracer.enabled:
            tracer.event(
                "mesh.flush",
                kind=kind,
                shards=ndev,
                lanes_per_shard=shard_lanes,
                submit_ms=round(submit_s * 1e3, 3),
                finish_ms=round(finish_s * 1e3, 3),
            )
    except Exception:
        pass


def record_rebuild(from_devices: int, to_devices: int, seconds: float) -> None:
    """One mesh rebuild (crypto/batch._sharded_env): the topology changed
    size — a device died (shrink) or re-joined after clean probes (grow)."""
    with _LOCK:
        _STATS["rebuilds"] += 1
        _STATS["last_rebuild"] = {
            "from_devices": int(from_devices),
            "to_devices": int(to_devices),
            "seconds": round(seconds, 6),
            "ts": time.time(),
        }
    try:
        _metrics().rebuilds.inc()
    except Exception:
        pass
    try:
        from tendermint_tpu.libs.trace import tracer

        if tracer.enabled:
            tracer.event(
                "mesh.rebuild",
                from_devices=int(from_devices),
                to_devices=int(to_devices),
                seconds=round(seconds, 6),
            )
    except Exception:
        pass


# Encoded ladder rungs for the tendermint_tpu_mesh_ladder_state gauge; keep
# in sync with parallel/health.LADDER_GAUGE.
_LADDER_GAUGE = {"full": 0, "survivor": 1, "single": 2, "host": 3}


def record_mesh_health(snapshot: dict, ladder: str) -> None:
    """Per-device health + ladder rung (crypto/batch._publish_mesh_health).
    The device-health gauge is replace_series'd: a departed device's series
    DROPS from /metrics instead of freezing at its last value."""
    with _LOCK:
        _STATS["health"] = snapshot
        _STATS["ladder"] = ladder
    try:
        m = _metrics()
        values = {}
        for dev, st in (snapshot.get("devices") or {}).items():
            if st.get("state") == "healthy":
                v = 1.0
            elif st.get("clean_probes", 0) > 0:
                v = 0.5  # dead but probing clean: mid-rejoin
            else:
                v = 0.0
            values[(dev,)] = v
        m.device_health.replace_series(values)
        m.ladder_state.set(_LADDER_GAUGE.get(ladder, 2))
    except Exception:
        pass


def record_aot(result: str) -> None:
    """AOT artifact-cache outcome (ops/aot_cache.py): `hit` (deserialized),
    `miss` (fresh export), `corrupt` (deleted + re-exported). Machine-scoped
    keys mean a foreign host's artifacts show up here as misses — the
    observable that distinguishes a healthy cold start from the
    cpu_aot_loader mismatch that killed MULTICHIP r04/r05."""
    with _LOCK:
        _STATS["aot_cache"][result] = _STATS["aot_cache"].get(result, 0) + 1
    try:
        _metrics().aot_cache.labels(result).inc()
    except Exception:
        pass


def mesh_stats() -> dict:
    """Snapshot for /debug/mesh, the verify_stats `mesh` block, and the
    multichip dryrun tail."""
    with _LOCK:
        out = {
            "mesh": dict(_STATS["mesh"]) if _STATS["mesh"] else None,
            "flushes": dict(_STATS["flushes"]),
            "totals": {
                k: (round(v, 6) if isinstance(v, float) else v)
                for k, v in _STATS["totals"].items()
            },
            "last_flush": dict(_STATS["last_flush"]) if _STATS["last_flush"] else None,
            "last_prep": dict(_STATS["last_prep"]) if _STATS["last_prep"] else None,
            "last_pad": dict(_STATS.get("last_pad") or {}) or None,
            "aot_cache": dict(_STATS["aot_cache"]),
            "ladder": _STATS.get("ladder"),
            "rebuilds": _STATS.get("rebuilds", 0),
            "last_rebuild": (
                dict(_STATS["last_rebuild"]) if _STATS.get("last_rebuild") else None
            ),
        }
    # Health reads LIVE from the manager (jax-free) so /debug/mesh shows
    # probe streaks as they advance, not the last pushed snapshot.
    try:
        from tendermint_tpu.parallel.health import MESH_HEALTH

        out["health"] = MESH_HEALTH.snapshot()
    except Exception:
        out["health"] = _STATS.get("health")
    return out


def reset() -> None:
    """Test hook: zero the aggregated mesh telemetry (not the metrics)."""
    global _STATS
    with _LOCK:
        _STATS = _fresh()
