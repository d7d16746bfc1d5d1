"""Offline profiler-trace analyzer: capture directory → per-stage time table.

Turns a `libs/profiler.py` capture (or any jax/TensorBoard profile dump)
into the PERF.md-style attribution table — per-kernel and per-fused-stage
(uptree, fenwick_reduce, bucket_fold, persig) totals — in one command
instead of an afternoon of perfetto spelunking:

    python tools/profile_report.py <capture-dir-or-file> [--top N] [--json OUT]

Two input forms, no external deps:

- `*.trace.json.gz` — the perfetto/chrome trace jax writes next to the
  xplane file: `X` (complete) events with per-thread nesting; process and
  thread names from `M` metadata events.
- `*.xplane.pb` — the XSpace protobuf, parsed with a minimal protobuf
  wire-format walker (tensorflow/tensorboard are NOT importable in this
  container, and the schema needed here is 4 small messages: XSpace →
  XPlane → XLine → XEvent + the id→name metadata maps).

Times are reported as **total** (event wall span, includes children) and
**self** (total minus nested children on the same thread) — `self` is the
honest per-stage cost; `total` localises where a wall-clock budget went.
Python host-tracing events (`$`-prefixed) are folded into one `host_python`
stage so device/runtime rows aren't swamped.

Device operations classify by the stage of the fused MSM that made them:
the `jax.named_scope`s of ops/msm_jax.py (`decompress`, `row_gather`,
`uptree`, `top_tree`, `fenwick_gather`, `fenwick_reduce`, `bucket_fold`,
`window_combine`, `identity_check`) arrive as the operation's `tf_op` path
in an xplane capture, and the Pallas kernels carry their own names
(`msm_uptree`, `msm_fenwick_reduce`, `msm_bucket_fold`, `fe_padd`, `fe_pdbl`,
`fe_fsq`). While a capture runs, libs/trace.py mirrors every span of the
flight recorder as a `tm:<span>` annotation on the host plane, on the
profiler's clock; the last table puts each idle gap of the device down to
the innermost `tm:` span open on the calling thread at that time.
"""

from __future__ import annotations

import glob
import gzip
import json
import os
import re
import sys
from typing import Dict, Iterable, List, Optional, Tuple

from tendermint_tpu.libs.trace import ANNOTATION_PREFIX as TM_PREFIX  # "tm:"

# Stage classification, first match wins (case-insensitive). Kernel names
# surface differently per backend (Pjit wrappers on host, fusion names on
# device planes), so patterns match the stable substrings our kernels carry
# (ops/pallas_msm.py, ops/msm_jax.py, ops/ed25519_jax.py).
STAGE_PATTERNS: List[Tuple[str, str]] = [
    # the fused MSM's named scopes and kernels (ops/msm_jax.py,
    # ops/pallas_msm.py); the narrower name before the one it contains
    ("row_gather", r"row_gather"),
    ("top_tree", r"top_tree"),
    ("uptree", r"uptree"),
    ("fenwick_gather", r"fenwick_gather"),
    ("fenwick_reduce", r"fenwick"),
    ("bucket_fold", r"bucket"),
    ("window_combine", r"window_combine"),
    ("identity_check", r"identity_check"),
    ("persig", r"persig|verify_prepared|verify_core|ladder"),
    ("decompress", r"decompress|ristretto"),
    # a field kernel outside every named scope (ops/pallas_fe.py)
    ("field_kernels", r"fe_padd|fe_pdbl|fe_fsq"),
    ("msm_other", r"rlc|msm|pallas|pippenger"),
    (
        "compile",
        r"backend_compile|compile|codegen|llvm|hlo passes|lower|"
        r"trace_to_jaxpr|optimization|emit",
    ),
    (
        "transfer",
        r"transferto|transferfrom|device_put|copyto|bufferfromhost|"
        r"toliteral|h2d|d2h|copy_to|transfer",
    ),
    (
        "dispatch",
        r"pjitfunction|executesharded|execute|runthunks|thunk|"
        r"parsearguments|donate",
    ),
    ("host_python", r"^\$"),
]
_COMPILED = [(stage, re.compile(pat, re.IGNORECASE)) for stage, pat in STAGE_PATTERNS]


def classify(name: str, scope: str = "") -> str:
    """Stage of an event: by its scope path (an xplane operation's `tf_op`,
    which holds the named scopes) where there is one, then by its name."""
    for text in (scope, name) if scope else (name,):
        for stage, rx in _COMPILED:
            if rx.search(text):
                return stage
    return "other"


# ---------------------------------------------------------------------------
# Input discovery


def find_capture_files(path: str) -> List[str]:
    """Resolve a run dir / capture dir / single file to trace artifacts,
    preferring the newest capture and the richer json form."""
    if os.path.isfile(path):
        return [path]
    jsons = sorted(glob.glob(os.path.join(path, "**", "*.trace.json.gz"), recursive=True))
    xplanes = sorted(
        glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True)
        + glob.glob(os.path.join(path, "**", "*.xplane.pb.gz"), recursive=True)
    )
    picked = []
    if jsons:
        picked.append(jsons[-1])
    elif xplanes:
        picked.append(xplanes[-1])
    return picked


# ---------------------------------------------------------------------------
# chrome-trace (.trace.json.gz) parsing


def _load_chrome_trace(path: str):
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt") as f:
        data = json.load(f)
    evs = data.get("traceEvents", data if isinstance(data, list) else [])
    pnames: Dict[int, str] = {}
    tnames: Dict[Tuple[int, int], str] = {}
    out = []
    for e in evs:
        ph = e.get("ph")
        if ph == "M":
            if e.get("name") == "process_name":
                pnames[e.get("pid")] = e.get("args", {}).get("name", "")
            elif e.get("name") == "thread_name":
                tnames[(e.get("pid"), e.get("tid"))] = e.get("args", {}).get("name", "")
        elif ph == "X":
            out.append(
                {
                    "name": e.get("name", ""),
                    "ts_us": float(e.get("ts", 0.0)),
                    "dur_us": float(e.get("dur", 0.0)),
                    "pid": e.get("pid"),
                    "tid": e.get("tid"),
                }
            )
    for e in out:
        e["plane"] = pnames.get(e["pid"], str(e["pid"]))
        e["thread"] = tnames.get((e["pid"], e["tid"]), str(e["tid"]))
    return out


# ---------------------------------------------------------------------------
# xplane (.xplane.pb) parsing — minimal protobuf wire walker


def _walk(buf: bytes, pos: int = 0, end: Optional[int] = None):
    """Yield (field_no, wire_type, value) triples from a protobuf buffer.
    Varints decode to int; length-delimited fields yield memoryview slices."""
    view = memoryview(buf)
    if end is None:
        end = len(buf)
    while pos < end:
        tag = 0
        shift = 0
        while True:
            b = view[pos]
            pos += 1
            tag |= (b & 0x7F) << shift
            shift += 7
            if not b & 0x80:
                break
        fno, wt = tag >> 3, tag & 7
        if wt == 0:
            v = 0
            shift = 0
            while True:
                b = view[pos]
                pos += 1
                v |= (b & 0x7F) << shift
                shift += 7
                if not b & 0x80:
                    break
            yield fno, wt, v
        elif wt == 2:
            ln = 0
            shift = 0
            while True:
                b = view[pos]
                pos += 1
                ln |= (b & 0x7F) << shift
                shift += 7
                if not b & 0x80:
                    break
            yield fno, wt, view[pos : pos + ln]
            pos += ln
        elif wt == 5:
            yield fno, wt, view[pos : pos + 4]
            pos += 4
        elif wt == 1:
            yield fno, wt, view[pos : pos + 8]
            pos += 8
        else:  # groups (3/4) never appear in xplane
            raise ValueError(f"unsupported wire type {wt} at {pos}")


def _svarint(v: int) -> int:
    """Protobuf int64 fields arrive as two's-complement varints."""
    return v - (1 << 64) if v >= 1 << 63 else v


def _load_xplane(path: str):
    """XSpace → flat event list. Schema (xplane.proto): XSpace.planes=1;
    XPlane{name=2, lines=3, event_metadata=4 map<i64,XEventMetadata{name=2,
    stats=5}>, stat_metadata=5 map<i64,XStatMetadata{name=2}>};
    XStat{metadata_id=1, str_value=5};
    XLine{id=1, name=2, timestamp_ns=3, events=4, display_name=11};
    XEvent{metadata_id=1, offset_ps=2, duration_ps=3}. An operation's
    `tf_op` stat (its op_name path, named scopes and all) becomes the
    event's `scope`. Reads `.xplane.pb` and `.xplane.pb.gz`."""
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        buf = f.read()
    out = []
    for fno, _wt, plane_buf in _walk(buf):
        if fno != 1:
            continue
        plane_name = ""
        lines = []
        ev_names: Dict[int, str] = {}
        ev_stats: Dict[int, list] = {}  # metadata id -> [(stat id, str)]
        stat_names: Dict[int, str] = {}
        for pf, _pwt, pv in _walk(plane_buf):
            if pf == 2:
                plane_name = bytes(pv).decode(errors="replace")
            elif pf == 3:
                lines.append(pv)
            elif pf == 4:  # map entry {key=1 varint, value=2 XEventMetadata}
                key, name, stats = None, "", []
                for mf, _mwt, mv in _walk(pv):
                    if mf == 1:
                        key = _svarint(mv)
                    elif mf == 2:
                        for ef, ewt, ev in _walk(mv):
                            if ef == 2:
                                name = bytes(ev).decode(errors="replace")
                            elif ef == 5 and ewt == 2:
                                sid, sval = None, None
                                for sf, swt, sv in _walk(ev):
                                    if sf == 1:
                                        sid = sv
                                    elif sf == 5 and swt == 2:
                                        sval = bytes(sv).decode(errors="replace")
                                if sid is not None and sval is not None:
                                    stats.append((sid, sval))
                if key is not None:
                    ev_names[key] = name
                    if stats:
                        ev_stats[key] = stats
            elif pf == 5:  # map entry {key=1, value=2 XStatMetadata{name=2}}
                key, name = None, ""
                for mf, _mwt, mv in _walk(pv):
                    if mf == 1:
                        key = _svarint(mv)
                    elif mf == 2:
                        for ef, _ewt, ev in _walk(mv):
                            if ef == 2:
                                name = bytes(ev).decode(errors="replace")
                if key is not None:
                    stat_names[key] = name
        tf_op = next((k for k, v in stat_names.items() if v == "tf_op"), None)
        scopes = {
            mid: next((v for sid, v in stats if sid == tf_op), "")
            for mid, stats in ev_stats.items()
        } if tf_op is not None else {}
        for line_buf in lines:
            line_name = ""
            line_id = 0
            line_ts_ns = 0
            events = []
            for lf, _lwt, lv in _walk(line_buf):
                if lf == 1:
                    line_id = _svarint(lv)
                elif lf == 2:
                    line_name = bytes(lv).decode(errors="replace")
                elif lf == 11 and not line_name:
                    line_name = bytes(lv).decode(errors="replace")
                elif lf == 3:
                    line_ts_ns = _svarint(lv)
                elif lf == 4:
                    events.append(lv)
            for ev_buf in events:
                mid = offset_ps = dur_ps = 0
                for ef, _ewt, ev in _walk(ev_buf):
                    if ef == 1:
                        mid = _svarint(ev)
                    elif ef == 2:
                        offset_ps = _svarint(ev)
                    elif ef == 3:
                        dur_ps = _svarint(ev)
                out.append(
                    {
                        "name": ev_names.get(mid, f"metadata:{mid}"),
                        "scope": scopes.get(mid, ""),
                        "ts_us": line_ts_ns / 1e3 + offset_ps / 1e6,
                        "dur_us": dur_ps / 1e6,
                        "pid": plane_name,
                        # Python's threads all show as a line named
                        # "python": the line's id tells them apart
                        "tid": (line_name, line_id),
                        "plane": plane_name,
                        "thread": line_name,
                    }
                )
    return out


def load_events(path: str) -> List[dict]:
    if path.endswith((".xplane.pb", ".xplane.pb.gz")):
        return _load_xplane(path)
    return _load_chrome_trace(path)


# ---------------------------------------------------------------------------
# Aggregation


def _with_self_times(events: List[dict]) -> None:
    """Annotate each event with `self_us` = dur minus same-thread nested
    children (stack sweep per thread; chrome/xplane events nest properly)."""
    by_thread: Dict[Tuple, List[dict]] = {}
    for e in events:
        e["self_us"] = e["dur_us"]
        by_thread.setdefault((e["pid"], e["tid"]), []).append(e)
    for evs in by_thread.values():
        evs.sort(key=lambda e: (e["ts_us"], -e["dur_us"]))
        stack: List[dict] = []
        for e in evs:
            while stack and stack[-1]["ts_us"] + stack[-1]["dur_us"] <= e["ts_us"] + 1e-9:
                stack.pop()
            if stack:
                stack[-1]["self_us"] -= e["dur_us"]
            stack.append(e)


_PROFILER_SELF = re.compile(r"(start|stop)_trace$")


def analyze(events: List[dict]) -> dict:
    """Events → {wall_ms, stages: [...], ops: [...], planes: [...]} with
    stages/ops sorted by self time descending. The profiler's own
    start/stop_trace wrapper events span the whole capture window and would
    swamp the host_python stage, so they are dropped first."""
    events = [e for e in events if not _PROFILER_SELF.search(e["name"])]
    _with_self_times(events)
    ops: Dict[str, dict] = {}
    stages: Dict[str, dict] = {}
    planes: Dict[str, dict] = {}
    t_min, t_max = float("inf"), 0.0
    for e in events:
        t_min = min(t_min, e["ts_us"])
        t_max = max(t_max, e["ts_us"] + e["dur_us"])
        stage = classify(e["name"], e.get("scope", ""))
        o = ops.setdefault(
            e["name"], {"stage": stage, "count": 0, "total_us": 0.0, "self_us": 0.0}
        )
        o["count"] += 1
        o["total_us"] += e["dur_us"]
        o["self_us"] += max(0.0, e["self_us"])
        s = stages.setdefault(stage, {"count": 0, "total_us": 0.0, "self_us": 0.0})
        s["count"] += 1
        s["total_us"] += e["dur_us"]
        s["self_us"] += max(0.0, e["self_us"])
        p = planes.setdefault(e["plane"], {"events": 0, "self_us": 0.0})
        p["events"] += 1
        p["self_us"] += max(0.0, e["self_us"])
    wall_us = (t_max - t_min) if events else 0.0
    self_total = sum(s["self_us"] for s in stages.values()) or 1.0

    def _row(name, d):
        return {
            "name": name,
            **{k: (round(v, 3) if isinstance(v, float) else v) for k, v in d.items()},
            "share": round(d["self_us"] / self_total, 4),
        }

    return {
        "events": len(events),
        "wall_ms": round(wall_us / 1e3, 3),
        "stages": sorted(
            (_row(k, v) for k, v in stages.items()),
            key=lambda r: -r["self_us"],
        ),
        "ops": sorted(
            (_row(k, v) for k, v in ops.items()), key=lambda r: -r["self_us"]
        ),
        "planes": [
            {"plane": k, **{kk: round(vv, 3) for kk, vv in v.items()}}
            for k, v in sorted(planes.items())
        ],
    }


DEVICE_OP_LINES = ("XLA Ops",)  # the other lines of a device plane repeat or group these
NO_SPAN = "(no tm: span open)"


def _union(intervals) -> List[List[float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def idle_by_span(events: List[dict]) -> dict:
    """Each idle gap of the device plane put down to the innermost `tm:`
    span open on the host at that time.

    The device is busy during the union of its operations' intervals (the
    first `/device:` plane that has any). The host's timeline is the thread
    that carries the most `tm:` time (the caller's: the prep worker's spans
    nest under it by id, not by thread), flattened so that every instant
    belongs to its innermost open span. The window runs from that thread's
    first `tm:` span to its last. Returns {} where the capture holds no
    device operation or no `tm:` span (a CPU capture, an old program)."""
    dev_planes = sorted({e["plane"] for e in events if e["plane"].startswith("/device:")})
    busy: List[List[float]] = []
    for plane in dev_planes:
        ops = [
            e for e in events
            if e["plane"] == plane and e["dur_us"] > 0 and e["thread"] in DEVICE_OP_LINES
        ] or [e for e in events if e["plane"] == plane and e["dur_us"] > 0]
        if ops:
            busy = _union((e["ts_us"], e["ts_us"] + e["dur_us"]) for e in ops)
            break
    by_thread: Dict[Tuple, List[dict]] = {}
    for e in events:
        if e["name"].startswith(TM_PREFIX) and not e["plane"].startswith("/device:"):
            by_thread.setdefault((e["pid"], e["tid"]), []).append(e)
    if not busy or not by_thread:
        return {}
    spans = max(
        by_thread.values(),
        key=lambda evs: sum(
            e - s for s, e in _union((x["ts_us"], x["ts_us"] + x["dur_us"]) for x in evs)
        ),
    )
    spans = sorted(spans, key=lambda e: (e["ts_us"], -e["dur_us"]))
    lo = spans[0]["ts_us"]
    hi = max(e["ts_us"] + e["dur_us"] for e in spans)
    # flatten the nested spans: (start, end, innermost name), in time order
    flat: List[Tuple[float, float, str]] = []
    stack: List[Tuple[float, str]] = []  # (end, name)
    at = lo

    def emit(until: float) -> None:
        nonlocal at
        if until > at:
            flat.append((at, until, stack[-1][1] if stack else NO_SPAN))
            at = until

    for e in spans:
        while stack and stack[-1][0] <= e["ts_us"]:
            emit(stack[-1][0])
            stack.pop()
        emit(e["ts_us"])
        stack.append((e["ts_us"] + e["dur_us"], e["name"][len(TM_PREFIX):]))
    while stack:
        emit(stack[-1][0])
        stack.pop()
    idle, cur = [], lo
    for s, e in busy:
        if e <= lo or s >= hi:
            continue
        if s > cur:
            idle.append((cur, s))
        cur = max(cur, e)
    if hi > cur:
        idle.append((cur, hi))
    rows: Dict[str, dict] = {}
    i = 0
    for g0, g1 in idle:
        while i < len(flat) and flat[i][1] <= g0:
            i += 1
        j = i
        while j < len(flat) and flat[j][0] < g1:
            part = min(g1, flat[j][1]) - max(g0, flat[j][0])
            if part > 0:
                r = rows.setdefault(flat[j][2], {"idle_us": 0.0, "gaps": 0})
                r["idle_us"] += part
                r["gaps"] += 1
            j += 1
    idle_us = sum(e - s for s, e in idle)
    busy_us = sum(min(e, hi) - max(s, lo) for s, e in busy if e > lo and s < hi)
    return {
        "window_ms": round((hi - lo) / 1e3, 3),
        "busy_ms": round(busy_us / 1e3, 3),
        "idle_ms": round(idle_us / 1e3, 3),
        "calls": sum(1 for e in spans if e["name"] == TM_PREFIX + "commit.verify"),
        "rows": [
            {"span": k, "idle_ms": round(v["idle_us"] / 1e3, 3), "gaps": v["gaps"],
             "share": round(v["idle_us"] / idle_us, 4) if idle_us else 0.0}
            for k, v in sorted(rows.items(), key=lambda kv: -kv[1]["idle_us"])
        ],
    }


def device_stages(events: List[dict]) -> List[dict]:
    """Device time by stage of the fused MSM: the operations of the first
    device plane's `XLA Ops` line (they never overlap), classified by scope
    path and name, with the costliest operation of each stage."""
    planes = sorted({e["plane"] for e in events if e["plane"].startswith("/device:")})
    stages: Dict[str, dict] = {}
    for plane in planes:
        ops = [e for e in events
               if e["plane"] == plane and e["thread"] in DEVICE_OP_LINES and e["dur_us"] > 0]
        if not ops:
            continue
        per_op: Dict[Tuple[str, str], float] = {}
        for e in ops:
            stage = classify(e["name"], e.get("scope", ""))
            st = stages.setdefault(stage, {"ops": 0, "us": 0.0})
            st["ops"] += 1
            st["us"] += e["dur_us"]
            head = e["name"].split(" = ", 1)[0].strip()
            per_op[(stage, head)] = per_op.get((stage, head), 0.0) + e["dur_us"]
        total = sum(st["us"] for st in stages.values()) or 1.0
        rows = []
        for stage, st in sorted(stages.items(), key=lambda kv: -kv[1]["us"]):
            top_op, top_us = max(
                ((op, us) for (sg, op), us in per_op.items() if sg == stage),
                key=lambda kv: kv[1],
            )
            rows.append({"stage": stage, "ops": st["ops"], "ms": round(st["us"] / 1e3, 3),
                         "share": round(st["us"] / total, 4), "top_op": top_op,
                         "top_op_ms": round(top_us / 1e3, 3)})
        return rows
    return []


def report(path: str, top: int = 25) -> dict:
    """Full report for a capture dir or trace file."""
    files = find_capture_files(path)
    if not files:
        raise FileNotFoundError(
            f"no *.trace.json.gz or *.xplane.pb under {path!r}"
        )
    events = []
    for f in files:
        events.extend(load_events(f))
    out = analyze(events)
    out["device_stages"] = device_stages(events)
    out["idle_by_span"] = idle_by_span(events)
    out["capture"] = files
    out["ops"] = out["ops"][: max(0, top)]
    return out


def render_markdown(rep: dict) -> str:
    lines = [
        f"# Profile report — {len(rep.get('capture', []))} artifact(s), "
        f"{rep['events']} events, {rep['wall_ms']:.1f} ms wall",
        "",
        "## Per-stage (self time; total includes nested children)",
        "",
        "| stage | events | self ms | total ms | share |",
        "|---|---:|---:|---:|---:|",
    ]
    for s in rep["stages"]:
        lines.append(
            f"| {s['name']} | {s['count']} | {s['self_us']/1e3:.3f} "
            f"| {s['total_us']/1e3:.3f} | {s['share']*100:.1f}% |"
        )
    lines += [
        "",
        "## Top ops",
        "",
        "| op | stage | count | self ms | total ms |",
        "|---|---|---:|---:|---:|",
    ]
    for o in rep["ops"]:
        name = o["name"] if len(o["name"]) <= 72 else o["name"][:69] + "..."
        lines.append(
            f"| `{name}` | {o['stage']} | {o['count']} "
            f"| {o['self_us']/1e3:.3f} | {o['total_us']/1e3:.3f} |"
        )
    if rep.get("device_stages"):
        lines += [
            "",
            "## Device time by stage (the device plane's XLA operations)",
            "",
            "| stage | ops | ms | share | costliest operation | its ms |",
            "|---|---:|---:|---:|---|---:|",
        ]
        for r in rep["device_stages"]:
            lines.append(
                f"| {r['stage']} | {r['ops']} | {r['ms']:.3f} | {r['share']*100:.1f}% "
                f"| `{r['top_op']}` | {r['top_op_ms']:.3f} |"
            )
    gaps = rep.get("idle_by_span")
    if gaps:
        lines += [
            "",
            "## Device idle, by the innermost `tm:` span open on the host",
            "",
            f"{gaps['calls']} `commit.verify` call(s); window {gaps['window_ms']:.3f} ms,"
            f" device busy {gaps['busy_ms']:.3f} ms, idle {gaps['idle_ms']:.3f} ms.",
            "",
            "| span | idle ms | share of idle | gaps |",
            "|---|---:|---:|---:|",
        ]
        for r in gaps["rows"]:
            lines.append(
                f"| `{r['span']}` | {r['idle_ms']:.3f} | {r['share']*100:.1f}% | {r['gaps']} |"
            )
    if rep.get("planes"):
        lines += ["", "## Planes", ""]
        for p in rep["planes"]:
            lines.append(
                f"- `{p['plane']}`: {p['events']} events, "
                f"{p['self_us']/1e3:.1f} ms self"
            )
    return "\n".join(lines) + "\n"


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("path", help="capture directory (or a single trace file)")
    ap.add_argument("--top", type=int, default=25, help="top-N ops to list")
    ap.add_argument("--json", help="also write the full report as JSON here")
    args = ap.parse_args(argv)
    try:
        rep = report(args.path, top=args.top)
    except FileNotFoundError as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    sys.stdout.write(render_markdown(rep))
    if args.json:
        with open(args.json, "w") as f:
            json.dump(rep, f, indent=1)
        print(f"\nJSON report: {args.json}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
