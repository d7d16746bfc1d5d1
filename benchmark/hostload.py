"""What else the machine was doing during the window, for the record of
each run: the host's clock carries every end-to-end metric, and a one-chip
machine shares its host's cores with other tenants. Nothing here enters a
metric; the result line carries it under `host`, so that a run that reads
far off can be held against the load it ran beside."""

from __future__ import annotations

import os
import resource

_TICK = os.sysconf("SC_CLK_TCK")


def _machine() -> dict:
    """Seconds of all cores together since boot, from /proc/stat's first
    line: busy (all but idle and iowait) and stolen by the hypervisor."""
    try:
        with open("/proc/stat") as f:
            v = [int(x) for x in f.readline().split()[1:]]
    except (OSError, ValueError):
        return {}
    v += [0] * (10 - len(v))
    return {"busy_s": (sum(v[:8]) - v[3] - v[4]) / _TICK, "steal_s": v[7] / _TICK}


def snapshot() -> dict:
    t, ru = os.times(), resource.getrusage(resource.RUSAGE_SELF)
    return {"own_cpu_s": t.user + t.system, "switched_out": ru.ru_nivcsw,
            "loadavg_1m": os.getloadavg()[0], **_machine()}


def between(a: dict, b: dict, seconds: float) -> dict:
    """Cores kept busy over the window by this process and by everything
    else, seconds stolen, and how often this process was switched out
    against its will."""
    out = {"cores": os.cpu_count(), "cores_allowed": len(os.sched_getaffinity(0)),
           "own_busy_cores": (b["own_cpu_s"] - a["own_cpu_s"]) / seconds,
           "switched_out": b["switched_out"] - a["switched_out"],
           "loadavg_1m_start": a["loadavg_1m"], "loadavg_1m_end": b["loadavg_1m"]}
    if b.get("busy_s", 0) > a.get("busy_s", 0):  # a sealed machine's /proc/stat may stand still
        out["others_busy_cores"] = (b["busy_s"] - a["busy_s"]) / seconds - out["own_busy_cores"]
        out["steal_s"] = b["steal_s"] - a["steal_s"]
    return out
