#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json:

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, no child. It exits non-zero, with no result line, when JAX
picks no TPU or another number of chips than the cell asks for. It builds
the cell's data from --seed, warms the cell's own shapes (set-up), drives
the entry for --seconds, then holds what the timed path produced against
the plain reference and prints the result object as its last line. One
call verifies one item of the ring: a commit, or a run of as many commits
as the mix states, judged by the verdict rule the configuration names.

Harness-only flags: --rehearse N runs N validators on whatever JAX has (the
sandbox's CPU, the program's host backend) to walk the control flow; its
readings stand under `rehearsal_readings`, never under a metric's name.
--control NAME breaks a guarantee, to show that `correct` comes out false: a
stand-in built on the reference (controls.py) in the program's place, or the
program's own path with rows left unseen (the entry driver's
PROGRAM_CONTROLS)."""

from __future__ import annotations

import time

T0 = time.perf_counter()  # set-up is counted from here: the process's start

import argparse
import contextlib
import functools
import gc
import gzip
import json
import logging
import os
import sys
import types

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for p in (ROOT, HERE):
    if p not in sys.path:
        sys.path.insert(0, p)

import data  # noqa: E402
import hostload  # noqa: E402
import reference  # noqa: E402
import spec  # noqa: E402
import stats  # noqa: E402
import tracing  # noqa: E402
import work  # noqa: E402


# What run.py itself reads of an entry driver's flush reading (README.md has
# the whole protocol): the spans whose medians every result line carries under
# `spans_p50`, and what it shows of the last call's reading under `flush`.
# Besides these: `rows_valid`, `compile_ms`, `path` and `jax_path`.
READING_SPANS = ("total_ms", "prep_ms", "transfer_ms")
READING_SHOWN = ("backend", "path", "chunks", "chunk_lanes", "lane_bucket", "fused")


def require_device(chips: int, rehearse: bool) -> dict:
    """First thing, before any data is built: the device JAX picked."""
    import jax

    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}
    if rehearse:
        return dev
    if dev["platform"] != "tpu":
        sys.exit(f"benchmark: JAX picked platform {dev['platform']!r}, not a TPU")
    if dev["count"] != chips:
        sys.exit(f"benchmark: {dev['count']} chip(s) visible, the cell asks for {chips}")
    return dev


class Alarm(logging.Handler):
    """Every rung of the program's degrade ladder (fused off, RLC fell back,
    flush degraded, AOT export or native build failed) logs a WARNING under
    `tendermint_tpu`; one during a run is counted against it."""

    def __init__(self):
        super().__init__(level=logging.WARNING)
        self.records: list = []

    def emit(self, record):
        self.records.append(f"{record.name}: {record.getMessage()}")


class CompileLog:
    """Seconds of cold start by where they went: the AOT cache's events
    (libs/trace.record_compile: export / deserialize / first_call per
    program) and JAX's own trace / lower / backend-compile durations, which
    also see the programs that bypass that cache."""

    STAGES = ("jaxpr_trace", "jaxpr_to_mlir_module", "backend_compile")

    def __init__(self):
        import jax.monitoring

        self.jax_seconds = dict.fromkeys(self.STAGES, 0.0)
        self.backend_compiles = 0
        self.aot: dict = {}
        self._seen = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)

    def _on_duration(self, event: str, secs: float, **_kw) -> None:
        stage = event.rsplit("/", 1)[-1].removesuffix("_duration")
        if stage in self.jax_seconds:
            self.jax_seconds[stage] += secs
            self.backend_compiles += stage == "backend_compile"

    def drain_aot(self) -> None:
        """Before the flight recorder's ring can roll over them."""
        from tendermint_tpu.libs import trace

        for e in trace.tracer.dump():
            if e["name"].startswith("aot.") and e["ts"] > self._seen:
                self._seen = e["ts"]
                kind = e["name"][4:]
                per = self.aot.setdefault(e["attrs"]["kernel"], {})
                per[kind] = per.get(kind, 0.0) + e["attrs"]["seconds"]

    def snapshot(self) -> dict:
        self.drain_aot()
        return {
            "aot_programs": {k: dict(v) for k, v in self.aot.items()},
            "aot_seconds": sum(s for v in self.aot.values() for s in v.values()),
            "jax_seconds": dict(self.jax_seconds),
            "backend_compiles": self.backend_compiles,
        }


def run_window(entry, state, ring_len, seconds, traffic, tracer, keep_trace, rehearsal):
    """The measured window: a closed loop of one caller, or arrivals at a
    fixed rate served by one caller (latency then counts from when the call
    was due). Returns the calls and, when tracing, the reduced trace."""
    calls, reduced = [], None
    rate = float(traffic.get("rate_per_s", 0)) if traffic.get("loop") == "open" else 0.0
    trace_calls = int(traffic.get("trace_calls", 0)) if tracer else 0
    stack = contextlib.ExitStack()
    if trace_calls:
        tracer.start()
        stack.enter_context(entry.flush_spans(tracer.span))
        stack.enter_context(tracer.span("bench:slice"))

    def end_slice():
        stack.close()
        path = tracer.stop()
        out = tracing.reduce(*tracing.load_xplane(path, rehearsal))
        if keep_trace:
            os.makedirs(keep_trace, exist_ok=True)
            with open(path, "rb") as f, gzip.open(
                    os.path.join(keep_trace, "slice.xplane.pb.gz"), "wb") as g:
                g.write(tracing.without_hlo(f.read()))
            with open(os.path.join(keep_trace, "slice.describe.json"), "w") as f:
                json.dump(tracing.describe(path), f, indent=1)
        tracer.discard()
        return out

    i = 0
    t_start = time.perf_counter()
    while True:
        due = t_start + i / rate if rate else time.perf_counter()
        if due - t_start >= seconds:
            break
        late = time.perf_counter() - due
        if late < 0:
            time.sleep(-late)
        with tracer.span("bench:call") if trace_calls else contextlib.nullcontext():
            verdict = entry.call(state, i % ring_len)
        end = time.perf_counter()
        calls.append({"ring": i % ring_len, "start": due, "end": end,
                      "late_s": max(late, 0.0), "verdict": verdict,
                      "flush": entry.flush_reading()})
        i += 1
        if trace_calls and i == trace_calls:
            reduced, trace_calls = end_slice(), 0
    if trace_calls:  # the window closed first: the slice is what it held
        reduced = end_slice()
    return calls, reduced


def recovery_fault(entry, reading: dict, expect_recovery: dict | None, rows: int) -> str | None:
    """Of a call on a forged item: why its recovery left the configuration's
    `expect_recovery`; None where it kept to it, or where no contract is
    stated. A driver without `recovery_fault` cannot be held to one."""
    if expect_recovery is None:
        return None
    check = getattr(entry, "recovery_fault", None)
    if check is None:
        return "the entry driver has no recovery_fault"
    return check(reading, expect_recovery, rows)


def judge(cell, entry, state, vals, ring, eprobes, calls, expect, seed, compiles_in_window,
          alarm, expect_recovery=None):
    """What the timed path produced against the plain reference, each number
    beside its limit. Runs after the window has closed. An item's rows are
    one list in block order; its verdict is the configuration's rule over
    the reference's mask of them. A call on a forged item (the reference's
    mask not all valid) is held to `expect_recovery` where the
    configuration states it, under the same `flush_off_path`."""
    config, traffic = cell.config, cell.traffic
    rule = cell.rule()

    def ref_verdict(item, mask, signers):
        return rule(mask, signers, vals.powers, vals.total_power, data.blocks_of(item))

    ref_rows = [data.rows_of(config, vals, item) for item in ring]
    ref_masks = [reference.verify_rows(pk, ms, sg) for _, pk, ms, sg in ref_rows]
    ref_verdicts = [ref_verdict(item, m, row[0])
                    for item, m, row in zip(ring, ref_masks, ref_rows)]
    verdict_mismatch = off_path = rows_short = 0
    for c in calls:
        c["wrong"] = c["verdict"] != ref_verdicts[c["ring"]]
        verdict_mismatch += c["wrong"]
        want = len(ref_rows[c["ring"]][0])
        clean = all(ref_masks[c["ring"]])
        c["forged"] = not clean
        fault = entry.flush_fault(c["flush"], expect, want) if clean else \
            recovery_fault(entry, c["flush"], expect_recovery, want)
        c["fault"] = fault
        off_path += fault is not None
        rows_short += clean and c["flush"]["rows_valid"] != sum(ref_masks[c["ring"]])
    mask_mismatch = 0
    for (_, pk, ms, sg), want in zip(ref_rows, ref_masks):
        got = entry.mask(pk, ms, sg)
        mask_mismatch += sum(1 for a, b in zip(got, want) if a != b) + abs(len(got) - len(want))
    path = calls[-1]["flush"]["path"] if calls else ""
    clean_declined = sum(1 for (_, pk, ms, sg), want in zip(ref_rows, ref_masks)
                         if all(want) and not entry.passes_clean(path, pk, ms, sg))
    probes = data.probes(seed, int(traffic["probes"]), ring)
    accepted = void = 0
    for r, pos, kind in probes:
        _, pk, ms, sg = ref_rows[r]
        sg = list(sg)
        sg[pos] = data.flip_bit(sg[pos], kind)
        if reference.verify_rows(pk[pos:pos + 1], ms[pos:pos + 1], sg[pos:pos + 1])[0]:
            void += 1  # the reference accepts the altered row: no probe
        elif not entry.rejects(path, pk, ms, sg, pos):
            accepted += 1
    # what the entry itself has to refuse, in the reference's words
    entry_mismatch, entry_said = 0, {}
    for j, (label, item) in enumerate(eprobes):
        idx, pk, ms, sg = data.rows_of(config, vals, item)
        want = ref_verdict(item, reference.verify_rows(pk, ms, sg), idx)
        got = entry.call(state, len(ring) + j)
        entry_said[label] = {"want": want, "got": got, "path": entry.flush_reading()["jax_path"]}
        entry_mismatch += got != want or want == "accepted"
    faults = entry.process_faults()
    checks = {
        "verdict_mismatch": [verdict_mismatch, 0],
        "flush_off_path": [off_path, 0],
        "rows_valid_short": [rows_short, 0],
        "mask_mismatch": [mask_mismatch, 0],
        "clean_declined": [clean_declined, 0],
        "probes_accepted": [accepted, 0],
        "probes_void": [void, 0],
        "entry_verdict_mismatch": [entry_mismatch, 0],
        "compiles_in_window": [compiles_in_window, 0],
        "process_faults": [len(faults), 0],
        "program_warnings": [len(alarm.records), 0],
    }
    notes = {"probes": len(probes), "calls": len(calls),
             "rows_compared": sum(len(m) for m in ref_masks),
             "entry_probes": entry_said, "faults": faults, "warnings": alarm.records[:5],
             "first_off_path": next((c["fault"] for c in calls if c["fault"]), None)}
    forged = [c for c in calls if c["forged"]]
    if forged:  # what the recovery ladder cost; a later reader has it in ctx.calls too
        walked = [c["flush"].get("recovery_flushes") or 0 for c in forged]
        walls = [(c["end"] - c["start"]) * 1e3 for c in forged]
        clean = [(c["end"] - c["start"]) * 1e3 for c in calls if not c["forged"]] or [0.0]
        notes.update(forged_calls=len(forged), recovery_flushes_p50=stats.percentile(walked, 50),
                     recovery_flushes_max=max(walked), forged_ms_p50=stats.percentile(walls, 50),
                     forged_ms_max=max(walls), clean_ms_p50=stats.percentile(clean, 50),
                     forged_paths=sorted({str(c["flush"]["path"]) for c in forged}))
    return checks, notes


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", type=int, default=0, metavar="N")
    ap.add_argument("--control", default="")
    ap.add_argument("--keep-trace", default="", metavar="DIR")
    args = ap.parse_args(argv)

    cell = spec.Cell(spec.load_benchmark(ROOT), args.workload)
    if args.rehearse:
        os.environ.setdefault("TMTPU_CRYPTO_BACKEND", "cpu")
    device = require_device(cell.chips, bool(args.rehearse))

    import jax

    from tendermint_tpu.ops.aot_cache import configure_compile_cache

    alarm = Alarm()
    logging.getLogger("tendermint_tpu").addHandler(alarm)
    configure_compile_cache()
    compiles = CompileLog()
    entry = cell.entry()
    if not entry.native_ready():
        sys.exit("benchmark: the program's native prep library did not build or load")
    entry.configure(cell.traffic)
    expect = dict(cell.config["expect_flush"])
    expect_recovery = cell.config.get("expect_recovery")
    if args.rehearse:
        expect = {"backend": os.environ["TMTPU_CRYPTO_BACKEND"],
                  "paths": ["cpu", "rlc", "rlc-pipelined"]}
        if expect_recovery:  # the host backend's ladder, under the stated count
            expect_recovery = dict(expect_recovery, backend=expect["backend"],
                                   paths=["cpu", "rlc-bisect", "persig"])

    # set-up: the data from the seed, the program's objects, every shape warm
    split = {"imports_and_device_s": time.perf_counter() - T0}
    vals = data.make_validators(args.seed, cell.config, args.rehearse or None)
    ring = data.make_ring(args.seed, cell.config, cell.traffic, vals)
    split["data_s"] = time.perf_counter() - T0 - sum(split.values())
    eprobes = data.entry_probes(args.seed, cell.config, cell.traffic, ring, vals)
    state = entry.build(cell.config, vals, ring + [item for _, item in eprobes])
    split["build_s"] = time.perf_counter() - T0 - sum(split.values())
    if args.control:
        import controls

        if args.control in getattr(entry, "PROGRAM_CONTROLS", {}):
            entry.PROGRAM_CONTROLS[args.control]()
        else:
            entry.install_verifier(functools.partial(getattr(controls, args.control), vals))
    for i in range(int(cell.traffic["warmup_calls"])):
        entry.call(state, i % len(ring))
        entry.flush_reading()  # its first reading costs 11 ms once; not the window's to pay
    split["warmup_s"] = time.perf_counter() - T0 - sum(split.values())
    at_warm = compiles.snapshot()
    gc.collect()  # once, so that set-up's garbage is not the window's to collect
    setup_s = time.perf_counter() - T0
    load_before, t_window = hostload.snapshot(), time.perf_counter()

    tracer = tracing.Tracer() if args.trace else None
    calls, reduced = run_window(entry, state, len(ring), args.seconds, cell.traffic,
                                tracer, args.keep_trace, bool(args.rehearse))
    load = hostload.between(load_before, hostload.snapshot(), time.perf_counter() - t_window)
    after = compiles.snapshot()
    peak = max(((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in jax.devices()),
               default=0)
    compiles_in_window = (after["backend_compiles"] - at_warm["backend_compiles"]) + sum(
        1 for c in calls if c["flush"]["compile_ms"])

    t_judge = time.perf_counter()
    checks, notes = judge(cell, entry, state, vals, ring, eprobes, calls, expect, args.seed,
                          compiles_in_window, alarm, expect_recovery)
    notes["judge_s"] = time.perf_counter() - t_judge
    if reduced and notes.get("forged_calls"):  # the traced calls' device time, forged and clean
        per_call = list(zip(calls, reduced["device_s_per_call"]))
        notes["forged_device_ms"] = [s * 1e3 for c, s in per_call if c["forged"]]
        notes["clean_device_ms_p50"] = stats.percentile(
            [s * 1e3 for c, s in per_call if not c["forged"]] or [0.0], 50)
    correct = all(v <= limit for v, limit in checks.values())
    item_rows = [data.n_rows(item) for item in ring]
    rows = item_rows[0]  # of one call: what the per-layer readers and work.py reckon by
    for c in calls:  # a call is credited the signatures of its own item, or none
        c["good"] = not c["wrong"] and c["fault"] is None and not c["flush"]["compile_ms"]
    window = stats.window_metrics(
        [(c["start"], c["end"], item_rows[c["ring"]] * c["good"]) for c in calls])
    readings = {"sigs_per_s": window["sigs_per_s"], "verify_ms_p50": window["verify_ms_p50"],
                "verify_ms_p95": window["verify_ms_p95"], "setup_s": setup_s}
    wanted = cell.per_layer if args.trace else cell.end_to_end
    if args.trace:
        with open(os.path.join(HERE, "peaks.json")) as f:
            peaks = json.load(f)
        if device["kind"] not in peaks and not args.rehearse:
            sys.exit(f"benchmark: no peaks for device kind {device['kind']!r} in peaks.json")
        # what a per-layer reader may read
        ctx = types.SimpleNamespace(calls=calls, trace=reduced, rows=rows, setup_s=setup_s,
                                    compile_at_warm=at_warm, compile_after=after, work=work,
                                    peaks=peaks.get(device["kind"]), window=window,
                                    config=cell.config, traffic=cell.traffic)
        readings = {}
        for m in wanted:
            v = cell.reader(m["name"]).read(ctx)
            if v is not None:
                readings[m["name"]] = float(v)
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
    device["memory_peak_bytes"] = int(peak)
    metrics = {m["name"]: {"value": readings[m["name"]], "unit": m["unit"]}
               for m in wanted if m["name"] in readings}

    # the host's spans as medians, in every run: where a run that reads far off lost its time
    walls = [(c["end"] - c["start"]) * 1e3 for c in calls]
    spans = {k: stats.percentile([c["flush"][k] or 0.0 for c in calls], 50)
             for k in READING_SPANS}
    spans["outside_flush_ms"] = stats.percentile(
        [w - (c["flush"]["total_ms"] or 0.0) for w, c in zip(walls, calls)], 50)
    last = calls[-1]["flush"]
    result = {"correct": correct, "attempted": len(calls),
              "failed": sum(not c["good"] for c in calls)}
    result["rehearsal_readings" if args.rehearse else "metrics"] = metrics
    if args.rehearse:
        result["metrics"] = {}
    result["device"] = device
    if reduced:
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    result.update(workload=cell.name, seed=args.seed, rows_per_call=rows, samples=window["calls"],
                  window_s=window["window_s"], late_s_max=max(c["late_s"] for c in calls),
                  flush={k: last[k] for k in READING_SHOWN},
                  setup_split=split, host=load, spans_p50=spans,
                  cold={"aot_seconds": at_warm["aot_seconds"],
                        "jax_seconds": at_warm["jax_seconds"],
                        "programs": sorted(at_warm["aot_programs"])},
                  notes=notes, checks=checks)
    print(f"benchmark: {cell.name} seed {args.seed}: {window['calls']} calls in "
          f"{window['window_s']:.3f} s, set-up {setup_s:.2f} s", file=sys.stderr)
    for name, (value, limit) in checks.items():
        print(f"check {name} {value} limit {limit}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
