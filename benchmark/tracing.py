"""The profiler's trace of a short slice of the window, and its reduction to
numbers: device busy time (the union of the intervals in which an operation
ran), time by operation, and the idle gaps named by what the host was doing
in them. Reads the .xplane.pb with jax.profiler.ProfileData alone."""

from __future__ import annotations

import bisect
import glob
import os
import shutil
import tempfile

SPAN_PREFIX = "bench:"
# lines of a device plane that repeat or group the operations' own line
GROUP_LINES = ("Steps", "XLA Modules", "XLA TraceMe", "Framework Ops",
               "Framework Name Scope", "Source code")
OP_LINE = "XLA Ops"


class Tracer:
    """start() ... stop() around the traced calls; span(name) marks what the
    host is doing on the profiler's own clock."""

    def __init__(self):
        self.dir = None

    def start(self) -> None:
        import jax

        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # the benchmark's spans only: small and cheap
        opts.host_tracer_level = 2
        opts.enable_hlo_proto = False  # the programs' HLO is tens of MB a slice
        jax.profiler.start_trace(self.dir, profiler_options=opts)

    @staticmethod
    def span(name: str):
        import jax

        return jax.profiler.TraceAnnotation(name)

    def stop(self) -> str:
        import jax

        jax.profiler.stop_trace()
        found = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"), recursive=True)
        if not found:
            raise RuntimeError(f"the profiler wrote no .xplane.pb under {self.dir}")
        return found[-1]

    def discard(self) -> None:
        if self.dir:
            shutil.rmtree(self.dir, ignore_errors=True)


def _varint(buf: bytes, at: int):
    n = shift = 0
    while True:
        b = buf[at]
        at += 1
        n |= (b & 0x7F) << shift
        shift += 7
        if not b & 0x80:
            return n, at


def without_hlo(xspace: bytes) -> bytes:
    """The serialized XSpace without its `/host:metadata` plane, which holds
    the traced programs' whole HLO (tens of MB) and nothing the reduction
    reads. Walks the top-level fields by the wire format alone (XSpace:
    planes = 1; XPlane: name = 2), so a kept slice is small enough to keep."""
    out, at = bytearray(), 0
    while at < len(xspace):
        start = at
        key, at = _varint(xspace, at)
        if key & 7 != 2:
            raise ValueError(f"XSpace field {key >> 3} is not length-delimited")
        size, at = _varint(xspace, at)
        body, at = xspace[at:at + size], at + size
        if key >> 3 == 1:
            k, j = _varint(body, 0)
            while k & 7 == 0:  # XPlane.id, a varint, comes before the name
                _, j = _varint(body, j)
                k, j = _varint(body, j)
            if k == (2 << 3 | 2):
                n, j = _varint(body, j)
                if body[j:j + n] == b"/host:metadata":
                    continue
        out += xspace[start:at]
    return bytes(out)


def load_xplane(path: str, rehearsal: bool = False):
    """(device planes, host spans): per device plane its name and operations
    as (start_ns, end_ns, name); the benchmark's own spans as
    (start_ns, end_ns, name without the prefix). In a rehearsal on the CPU
    there is no device plane, and XLA:CPU's operations on the host's threads
    stand in so that the rest can be walked."""
    import gzip

    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            data = ProfileData.from_serialized_xspace(f.read())
    else:
        data = ProfileData.from_file(path)
    devices, spans, host_ops = [], [], []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            lines = list(plane.lines)
            ops_lines = [ln for ln in lines if ln.name == OP_LINE] or [
                ln for ln in lines if ln.name not in GROUP_LINES
            ]
            ops = [
                (e.start_ns, e.start_ns + e.duration_ns, short_name(e.name))
                for ln in ops_lines for e in ln.events if e.duration_ns > 0
            ]
            if ops:
                devices.append((plane.name, sorted(ops)))
        else:
            for ln in plane.lines:
                for e in ln.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.start_ns, e.start_ns + e.duration_ns,
                                      e.name[len(SPAN_PREFIX):]))
                    elif rehearsal and e.duration_ns > 0 and "hlo_op" in dict(e.stats):
                        host_ops.append((e.start_ns, e.start_ns + e.duration_ns, e.name))
    if rehearsal and not devices and host_ops:
        devices.append(("rehearsal: XLA:CPU on host threads", sorted(host_ops)))
    return devices, sorted(spans)


def short_name(name: str) -> str:
    """An XLA operation is named by its whole HLO line; keep the
    instruction's name, and say where it is a Pallas/Mosaic kernel."""
    head = name.split(" = ", 1)[0].strip()
    return head + " (tpu_custom_call)" if "tpu_custom_call" in name else head


def union(intervals) -> list:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _within(sorted_intervals, starts, lo, hi):
    """The parts of disjoint sorted intervals that fall in [lo, hi)."""
    out = []
    i = max(bisect.bisect_right(starts, lo) - 1, 0)
    while i < len(sorted_intervals) and sorted_intervals[i][0] < hi:
        s, e = sorted_intervals[i]
        if e > lo:
            out.append((max(s, lo), min(e, hi)))
        i += 1
    return out


def _top(totals: dict, n: int = 10) -> list:
    return [[k, v] for k, v in sorted(totals.items(), key=lambda kv: -kv[1])[:n]]


BETWEEN_CALLS = "harness: between calls (flush record, ring hand-over)"


def _phases(spans, lo, hi):
    """The window cut into what the host was doing, from the benchmark's
    spans: call = the entry, flush = verify_batch as the entry sees it."""
    calls = [(s, e) for s, e, n in spans if n == "call"]
    flushes = [(s, e) for s, e, n in spans if n == "flush"]
    out, at = [], lo
    for c0, c1 in calls:
        if c0 > at:
            out.append((at, c0, BETWEEN_CALLS))
        inner = [(s, e) for s, e in flushes if s >= c0 and e <= c1]
        if inner:
            f0, f1 = inner[0][0], inner[-1][1]
            out.append((c0, f0, "entry: sign bytes and row gathering, before the flush"))
            out.append((f0, f1, "flush"))
            out.append((f1, c1, "entry: tally, after the flush"))
        else:
            out.append((c0, c1, "entry: call with no flush span"))
        at = c1
    if hi > at:
        out.append((at, hi, BETWEEN_CALLS))
    return out


def reduce(devices, spans) -> dict:
    """busy_s and window_s (averaged over the device planes), seconds by
    operation, the device seconds of each call, and the idle gaps by name.
    The window is the benchmark's `slice` span where there is one."""
    if not devices:
        raise RuntimeError("the trace holds no operation on a device")
    whole = [(s, e) for s, e, n in spans if n == "slice"]
    if whole:
        lo, hi = whole[0]
    else:
        lo = min(ops[0][0] for _, ops in devices)
        hi = max(max(e for _, e, _ in ops) for _, ops in devices)
    busy_ns, op_seconds = 0.0, {}
    busy_of = []
    for _, ops in devices:
        merged = union((max(s, lo), min(e, hi)) for s, e, _ in ops if e > lo and s < hi)
        busy_of.append(merged)
        busy_ns += sum(e - s for s, e in merged)
        for s, e, name in ops:
            if e > lo and s < hi:
                op_seconds[name] = op_seconds.get(name, 0.0) + (min(e, hi) - max(s, lo)) / 1e9
    busy = busy_of[0]  # gaps and per-call time are read on the first chip
    busy_starts = [s for s, _ in busy]
    idle, at = [], lo
    for s, e in busy:
        if s > at:
            idle.append((at, s))
        at = max(at, e)
    if hi > at:
        idle.append((at, hi))
    idle_starts = [s for s, _ in idle]
    gaps: dict = {}
    for p0, p1, name in _phases(spans, lo, hi):
        inside = _within(busy, busy_starts, p0, p1) if name == "flush" else []
        for g0, g1 in _within(idle, idle_starts, p0, p1):
            label = name
            if name == "flush":
                label = ("flush: host prep and upload before the first dispatch"
                         if not inside or g1 <= inside[0][0] else
                         "flush: sync, mask fetch and flush record"
                         if g0 >= inside[-1][1] else
                         "flush: between device operations (dispatch, next chunk's prep)")
            gaps[label] = gaps.get(label, 0.0) + (g1 - g0) / 1e9
    calls = [(s, e) for s, e, n in spans if n == "call"]
    per_call = [
        sum(e - s for s, e in _within(busy, busy_starts, c0, c1)) / 1e9 for c0, c1 in calls
    ]
    return {
        "busy_s": busy_ns / 1e9 / len(devices),
        "window_s": (hi - lo) / 1e9,
        "device_planes": [name for name, _ in devices],
        "device_ops": _top(op_seconds),
        "op_seconds_total": sum(op_seconds.values()),
        "idle_gaps": _top(gaps),
        "calls": len(calls),
        "device_s_per_call": per_call,
    }


def describe(path: str) -> list:
    """Planes and lines with their event counts: for a look by hand."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        for ln in plane.lines:
            evs = list(ln.events)
            out.append({"plane": plane.name, "line": ln.name, "events": len(evs),
                        "first": [e.name for e in evs[:5]]})
    return out
