"""Exposition-format and wiring lints for libs/metrics.py.

Three guards around the node's ~60 Prometheus series:

1. a promtool-style strict lint of `Registry.expose()` output (HELP/TYPE
   ordering, histogram `+Inf` bucket presence, `_sum`/`_count` consistency,
   bucket monotonicity) run over a fully-populated NodeMetrics exposition;
2. a "no dead series" static check: every metric registered on a subsystem
   metrics set must have a write site somewhere in `tendermint_tpu/`
   (catches gauges that get registered but never fed — the original sin
   this PR fixes for the p2p flowrate Monitors);
3. the standalone PrometheusServer and the RPC `/metrics` route must render
   IDENTICAL output for the same NodeMetrics (they share `.expose()` by
   convention only; this pins the convention).
"""

import os
import re

os.environ.setdefault("TMTPU_CRYPTO_BACKEND", "cpu")

from tendermint_tpu.libs import metrics as M


def _populated_node_metrics() -> M.NodeMetrics:
    nm = M.NodeMetrics()
    c = nm.consensus
    c.height.set(7)
    c.rounds.set(1)
    c.total_txs.inc(3)
    c.block_interval_seconds.observe(0.5)
    c.step_duration_seconds.labels("propose").observe(0.01)
    c.step_duration_seconds.labels("prevote").observe(0.2)
    c.step_duration_seconds.labels("prevote").observe(4.0)
    c.round_duration_seconds.observe(0.7)
    c.quorum_prevote_delay.set(0.05)
    c.proposal_receive_count.labels("accepted").inc()
    c.late_votes.labels("prevote").inc()
    c.block_parts.labels("true").inc(2)
    c.block_gossip_receive_latency.observe(0.02)
    nm.mempool.size.set(5)
    nm.mempool.size_bytes.set(512)
    nm.mempool.tx_size_bytes.observe(100)
    nm.p2p.peers.set(3)
    nm.p2p.send_rate_bytes.set(1024.5)
    nm.p2p.peer_send_bytes_total.labels("0x22").inc(10)
    nm.state.block_processing_time.observe(0.004)
    nm.blocksync.syncing.set(1)
    nm.blocksync.verify_seconds.observe(0.1)
    nm.statesync.chunks_applied_total.inc()
    # overload-protection series (ISSUE 5)
    nm.mempool.evicted_txs.inc(2)
    nm.mempool.rejected_txs.labels("quota").inc()
    nm.mempool.full.set(1)
    nm.p2p.rate_limited_msgs.labels("0x30").inc(5)
    nm.p2p.oversized_msgs.labels("0x30").inc()
    nm.rpc.inflight_requests.set(3)
    nm.rpc.shed_requests.labels("broadcast_tx_sync").inc()
    nm.overload.pressure_level.set(1)
    nm.overload.pressure.labels("mempool").set(0.8)
    nm.overload.transitions.labels("up").inc()
    nm.blocksync.peer_timeouts.inc()
    return nm


def _lint_exposition(text: str) -> None:
    """Strict promtool-style lint. Raises AssertionError with the offending
    line on any violation."""
    lines = [l for l in text.splitlines() if l.strip()]
    helped, typed = {}, {}
    sample_re = re.compile(r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(\{.*\})? (\S+)$")
    prev_help = None
    for line in lines:
        if line.startswith("# HELP "):
            name = line.split(" ", 3)[2]
            assert name not in helped, f"duplicate HELP for {name}"
            helped[name] = True
            prev_help = name
        elif line.startswith("# TYPE "):
            parts = line.split(" ")
            name, kind = parts[2], parts[3]
            assert prev_help == name, f"TYPE {name} not directly after its HELP"
            assert name not in typed, f"duplicate TYPE for {name}"
            assert kind in ("counter", "gauge", "histogram", "untyped"), line
            typed[name] = kind
            prev_help = None
        else:
            m = sample_re.match(line)
            assert m, f"unparseable sample line: {line!r}"
            name = m.group(1)
            family = name
            for suffix in ("_bucket", "_sum", "_count"):
                if name.endswith(suffix) and typed.get(name[: -len(suffix)]) == "histogram":
                    family = name[: -len(suffix)]
            assert family in typed and family in helped, (
                f"sample {name} has no preceding HELP/TYPE"
            )
            if typed[family] == "histogram":
                assert name != family, (
                    f"histogram {family} exposes a bare sample (want _bucket/_sum/_count)"
                )

    # histogram consistency from the parsed form
    fams = M.parse_exposition(text)
    for family, fam in fams.items():
        if fam["type"] != "histogram":
            continue
        series = {}
        for name, labels, value in fam["samples"]:
            key = tuple(sorted((k, v) for k, v in labels.items() if k != "le"))
            entry = series.setdefault(key, {"buckets": [], "sum": None, "count": None})
            if name.endswith("_bucket"):
                le = labels.get("le")
                assert le is not None, f"{family}: bucket sample without le"
                entry["buckets"].append(
                    (float("inf") if le == "+Inf" else float(le), value)
                )
            elif name.endswith("_sum"):
                entry["sum"] = value
            elif name.endswith("_count"):
                entry["count"] = value
        for key, entry in series.items():
            assert entry["buckets"], f"{family}{dict(key)}: no buckets"
            les = [le for le, _ in entry["buckets"]]
            assert les == sorted(les), f"{family}{dict(key)}: les out of order"
            assert les[-1] == float("inf"), f"{family}{dict(key)}: missing +Inf bucket"
            counts = [c for _, c in entry["buckets"]]
            assert counts == sorted(counts), (
                f"{family}{dict(key)}: bucket counts not cumulative"
            )
            assert entry["count"] is not None, f"{family}{dict(key)}: missing _count"
            assert entry["sum"] is not None, f"{family}{dict(key)}: missing _sum"
            assert counts[-1] == entry["count"], (
                f"{family}{dict(key)}: +Inf bucket != _count"
            )


def test_exposition_format_lint():
    nm = _populated_node_metrics()
    # the node exposition appends the process-global batch-verify series —
    # lint the combined output a scraper actually sees
    _lint_exposition(nm.expose())


def test_exposition_lint_catches_violations():
    """The lint itself must reject malformed expositions (else satellite 1
    is a rubber stamp)."""
    import pytest

    good = _populated_node_metrics().expose()
    # drop every +Inf bucket line
    broken = "\n".join(
        l for l in good.splitlines() if 'le="+Inf"' not in l
    )
    with pytest.raises(AssertionError):
        _lint_exposition(broken)
    # sample with no metadata
    with pytest.raises(AssertionError):
        _lint_exposition("tm_unannounced_total 3\n")


METRICS_SETS = (
    M.ConsensusMetrics,
    M.MempoolMetrics,
    M.P2PMetrics,
    M.StateMetrics,
    M.BlockSyncMetrics,
    M.StateSyncMetrics,
    M.RPCMetrics,
    M.OverloadMetrics,
    M.BatchVerifyMetrics,
    M.PubSubMetrics,
    M.ChaosMetrics,
    # device/mesh observatory (ISSUE 7): the tendermint_mesh_* series fed by
    # parallel/telemetry.py and the profiler/forensics usage counters
    M.MeshMetrics,
    M.ObservatoryMetrics,
    # SLO burn-rate engine (ISSUE 8): tendermint_slo_* fed by libs/slo.py,
    # plus the cross-node propagation series on ConsensusMetrics/P2PMetrics
    # (proposal/vote_propagation_seconds, clock_skew_seconds) which ride the
    # classes above
    M.SLOMetrics,
    # light-client-as-a-service (ISSUE 9): tendermint_light_* fed by
    # light/service.py (requests by outcome, cache hits, coalesced lanes
    # per flush, sheds, conflicting-header detections)
    M.LightServiceMetrics,
    # transaction & request observatory (ISSUE 10): tendermint_tx_* fed by
    # libs/txtrace.py (stage latencies + terminal outcomes), plus the
    # per-method tendermint_rpc_request_* series which ride RPCMetrics above
    M.TxLifecycleMetrics,
    # global verification scheduler (ISSUE 11): tendermint_verify_lane_*
    # fed by crypto/scheduler.py (per-lane depth, queue waits, rows per
    # combined flush, vote-lane preemptions)
    M.SchedulerMetrics,
    # fleet referee (ISSUE 17): tendermint_fleet_* fed by chaos/fleet.py
    # (nodes per role) and tools/fleet_referee.py (safety-audit comparisons,
    # verdicts handed down)
    M.FleetMetrics,
    # the process's own runtime (ISSUE 38): tendermint_process_gc_pause_seconds,
    # read from libs/trace.py's collector totals at scrape time
    M.ProcessMetrics,
)


def test_no_dead_series():
    """Every series registered on a metrics set must be WRITTEN somewhere in
    tendermint_tpu/ (via .attr.inc/.set/.dec/.observe/.labels). A metric
    nobody feeds silently exposes 0 forever — worse than absent, because
    dashboards trust it."""
    root = os.path.join(os.path.dirname(__file__), "..", "tendermint_tpu")
    sources = []
    for dirpath, _, files in os.walk(os.path.abspath(root)):
        for fn in files:
            if fn.endswith(".py") and fn != "metrics.py":
                with open(os.path.join(dirpath, fn)) as f:
                    sources.append(f.read())
    blob = "\n".join(sources)

    dead = []
    for cls in METRICS_SETS:
        reg = M.Registry()
        inst = cls(reg)
        for attr, val in vars(inst).items():
            if not isinstance(val, M._Metric):
                continue
            pattern = rf"\.{re.escape(attr)}\.(inc|set|dec|observe|labels|replace_series)\("
            if not re.search(pattern, blob):
                dead.append(f"{cls.__name__}.{attr} ({val.name})")
    assert not dead, f"registered but never written anywhere: {dead}"


def test_chain_metrics_delta_from_expositions():
    """tools/loadtest.py's chain-side scrape: _chain_metrics_delta isolates
    the load window by subtracting two /metrics expositions, and degrades
    to None when a scrape is missing (instrumentation disabled)."""
    from tendermint_tpu.tools.loadtest import _chain_metrics_delta

    nm = M.NodeMetrics()
    nm.consensus.block_interval_seconds.observe(1.0)
    nm.consensus.step_duration_seconds.labels("propose").observe(0.25)
    t0 = nm.expose()
    nm.consensus.block_interval_seconds.observe(3.0)
    nm.consensus.block_interval_seconds.observe(1.0)
    nm.consensus.step_duration_seconds.labels("propose").observe(0.75)
    nm.consensus.step_duration_seconds.labels("prevote").observe(0.5)
    t1 = nm.expose()

    cm = _chain_metrics_delta(t0, t1)
    assert cm["block_intervals_observed"] == 2
    assert abs(cm["block_interval_avg_s"] - 2.0) < 1e-6
    assert abs(cm["step_duration_avg_s"]["propose"] - 0.75) < 1e-6
    assert abs(cm["step_duration_avg_s"]["prevote"] - 0.5) < 1e-6
    assert _chain_metrics_delta(None, t1) is None
    assert _chain_metrics_delta(t0, None) is None


def test_registry_snapshot_compact():
    """Registry.snapshot(): only written series, histograms as count+sum —
    the shape bench.py attaches as extra.node_metrics."""
    nm = _populated_node_metrics()
    snap = nm.snapshot()
    assert snap["tendermint_consensus_height"] == {
        "type": "gauge", "series": {"": 7.0}
    }
    sd = snap["tendermint_consensus_step_duration_seconds"]
    assert sd["type"] == "histogram"
    assert sd["series"]['step="prevote"'] == {"count": 2, "sum": 4.2}
    # never-written series are omitted
    assert "tendermint_consensus_missing_validators" not in snap
    assert M.NodeMetrics.latest() is nm


def test_prometheus_server_and_rpc_route_render_identically():
    """The dedicated PrometheusServer listener and the RPC /metrics route
    must serve byte-identical expositions for the same NodeMetrics."""
    import asyncio
    from types import SimpleNamespace

    from tendermint_tpu.config.config import test_config
    from tendermint_tpu.libs.prometheus_server import PrometheusServer
    from tendermint_tpu.rpc.server import RPCServer

    nm = _populated_node_metrics()
    cfg = test_config()
    cfg.rpc.laddr = "tcp://127.0.0.1:0"
    cfg.instrumentation.prometheus = True
    node = SimpleNamespace(config=cfg, metrics=nm)

    async def run():
        rpc = RPCServer(node)
        prom = PrometheusServer(nm, "127.0.0.1:0")
        rpc_resp = await rpc._handle_metrics(None)
        prom_resp = await prom._handle(None)
        assert rpc_resp.text == prom_resp.text
        assert "tendermint_consensus_step_duration_seconds_bucket" in rpc_resp.text
        assert rpc_resp.content_type == prom_resp.content_type == "text/plain"

    asyncio.run(run())
