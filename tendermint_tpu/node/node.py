"""Node assembly (reference: node/node.go:613 NewNode, :840 OnStart).

Wires: DBs → state → proxy app (4 conns) → handshake/replay → event bus +
indexer → mempool → evidence pool → block executor → consensus → RPC.
P2P wiring is added by the switch/reactor layer when peers are configured."""

from __future__ import annotations

import asyncio
import logging
import os
from typing import Optional

from tendermint_tpu.abci.client import ABCIClient
from tendermint_tpu.abci.kvstore import (
    CounterApplication,
    KVStoreApplication,
    PersistentKVStoreApplication,
)
from tendermint_tpu.config.config import Config
from tendermint_tpu.consensus.cs_state import ConsensusState
from tendermint_tpu.consensus.replay import Handshaker
from tendermint_tpu.consensus.wal import WAL
from tendermint_tpu.evidence.pool import EvidencePool
from tendermint_tpu.libs.kvdb import KVDB, MemDB, SQLiteDB
from tendermint_tpu.mempool.mempool import Mempool
from tendermint_tpu.privval.file_pv import FilePV
from tendermint_tpu.proxy.multi import AppConns, local_client_creator
from tendermint_tpu.state.execution import BlockExecutor
from tendermint_tpu.state.sm_state import State, state_from_genesis
from tendermint_tpu.state.store import StateStore
from tendermint_tpu.state.txindex import IndexerService, KVTxIndexer
from tendermint_tpu.store.blockstore import BlockStore
from tendermint_tpu.types.event_bus import EventBus
from tendermint_tpu.types.genesis import GenesisDoc

logger = logging.getLogger("tendermint_tpu.node")


def _open_db(cfg: Config, name: str) -> KVDB:
    if cfg.base.db_backend == "memdb" or not cfg.root_dir:
        return MemDB()
    return SQLiteDB(os.path.join(cfg.root_dir, "data", f"{name}.db"))


def _parse_host_stripe(v):
    """`[crypto] prep_host_stripe` accepts "auto"/"1"/"0" (or a bool from
    programmatic configs); None leaves the process-global setting alone."""
    if v is None or v == "auto":
        return v
    if isinstance(v, str):
        return v not in ("0", "false", "off")
    return bool(v)


def default_app(name: str):
    if name == "kvstore":
        return KVStoreApplication()
    if name == "persistent_kvstore":
        return PersistentKVStoreApplication()
    if name == "counter":
        return CounterApplication()
    if name == "signed_kvstore":
        from tendermint_tpu.abci.kvstore import SignedKVStoreApplication

        return SignedKVStoreApplication()
    raise ValueError(f"unknown in-proc app {name!r}")


class Node:
    def __init__(
        self,
        config: Config,
        genesis: GenesisDoc,
        priv_validator: Optional[FilePV] = None,
        app=None,
        client_creator=None,
        state_provider=None,
    ):
        self.config = config
        self.genesis = genesis
        # Apply the chain's verification predicate before any key is checked
        # (cofactorless = reference-exact interop mode; see config.BaseConfig
        # and crypto/keys.set_verify_mode). Unconditional: the mode is
        # process-global, so a "cofactored" config must actively reset any
        # "cofactorless" left by env or an earlier Node in this process
        # (and set_verify_mode validates the string either way).
        from tendermint_tpu.crypto.keys import set_verify_mode

        set_verify_mode(getattr(config.base, "ed25519_verify_mode", "cofactored"))
        # verify-path circuit breaker knobs (process-global, same model as
        # the verify mode: the crypto pipeline is shared by every in-process
        # node, and the last Node constructed wins)
        from tendermint_tpu.crypto import batch as _batch

        _batch.configure_breaker(
            enabled=config.crypto.breaker_enabled,
            failure_threshold=config.crypto.breaker_failure_threshold,
            flush_deadline_s=config.crypto.breaker_flush_deadline,
            probe_interval_base=config.crypto.breaker_probe_base,
            probe_interval_max=config.crypto.breaker_probe_max,
        )
        # streamed flush planner budget (same process-global model)
        _batch.configure_planner(
            max_flush_lanes=getattr(config.crypto, "max_flush_lanes", None)
        )
        # stage-overlapped host prep + verified-row memo (ISSUE 18; same
        # process-global, last-node-wins model as the planner/breaker)
        _batch.configure_prep(
            prep_threads=getattr(config.crypto, "prep_threads", None),
            stream_floor=getattr(config.crypto, "prep_stream_floor", None),
            host_stripe=_parse_host_stripe(
                getattr(config.crypto, "prep_host_stripe", None)
            ),
        )
        _batch.configure_verified_memo(
            rows=getattr(config.crypto, "verified_memo_rows", None)
        )
        # elastic mesh health model (ISSUE 19; same process-global model)
        _batch.configure_mesh_health(
            enabled=getattr(config.crypto, "mesh_health_enabled", None),
            fail_threshold=getattr(config.crypto, "mesh_health_fail_threshold", None),
            stall_threshold_s=getattr(
                config.crypto, "mesh_health_stall_threshold", None
            ),
            rejoin_probes=getattr(config.crypto, "mesh_health_rejoin_probes", None),
            probe_interval_s=getattr(
                config.crypto, "mesh_health_probe_interval", None
            ),
        )
        self._owns_priv_validator = False
        if priv_validator is None and config.base.priv_validator_addr:
            # dial the remote signer (reference: node/node.go:658
            # createAndStartPrivValidatorSocketClient)
            from tendermint_tpu.privval.remote import SignerClient

            host, port = self._parse_laddr(config.base.priv_validator_addr)
            priv_validator = SignerClient(host, port)
            self._owns_priv_validator = True
        self.priv_validator = priv_validator

        # metrics (reference: node/node.go:106 DefaultMetricsProvider)
        from tendermint_tpu.libs.metrics import NodeMetrics

        self.metrics = NodeMetrics()

        # flight recorder (libs/trace.py): process-global, same model as the
        # verify mode above — apply this node's [instrumentation] knobs
        from tendermint_tpu.libs import trace as _trace

        _trace.tracer.configure(
            enabled=config.instrumentation.trace_enabled,
            ring_size=config.instrumentation.trace_ring_size,
        )

        # stall forensics (libs/forensics.py): heartbeat the device entry
        # points + write FORENSICS_*.json captures under [instrumentation]
        # forensics_dir (default ./forensics — never the app root); relative
        # paths resolve under root_dir; process-global like the tracer (the
        # env default TMTPU_FORENSICS_DIR already applied at import if set).
        # Heartbeat rings left by DEAD pids are swept at configure time.
        fdir = getattr(config.instrumentation, "forensics_dir", "")
        if fdir:
            from tendermint_tpu.libs import forensics as _forensics

            if not os.path.isabs(fdir) and config.root_dir:
                fdir = os.path.join(config.root_dir, fdir)
            _forensics.configure(fdir)

        # SLO engine (libs/slo.py): declared latency budgets + burn-rate
        # guards, served at GET /debug/slo and as tendermint_slo_* series.
        # Node-local, but the batch-verify flush feed is process-global
        # (set_default: last node wins, same model as the tracer).
        self.slo = None
        if getattr(config, "slo", None) is not None and config.slo.enabled:
            from tendermint_tpu.libs import slo as _slo

            self.slo = _slo.SLOEngine(config.slo, metrics=self.metrics.slo)
            _slo.set_default(self.slo)

        # global verification scheduler (crypto/scheduler.py, ROADMAP item
        # 2): the one device coordinator EVERY verification consumer submits
        # to — votes preempt, light serves within its coalescing window,
        # CheckTx admission batches, blocksync/evidence soak idle capacity.
        # Node-local instance (its lanes carry this node's SLO + metrics),
        # ALSO registered process-global (last node wins, the tracer model)
        # for the deep consumers with no wiring path: types/vote_set.py and
        # evidence/pool.py.
        self.scheduler = None
        if getattr(config, "scheduler", None) is not None and config.scheduler.enabled:
            from tendermint_tpu.crypto import scheduler as _sched

            self.scheduler = _sched.VerifyScheduler(
                config.scheduler,
                metrics=self.metrics.scheduler,
                slo=self.slo,
            )
            _sched.set_default(self.scheduler)

        # tx lifecycle tracker (libs/txtrace.py, ISSUE 10): the bounded
        # per-tx journey ring behind tx_status / GET /debug/tx_trace.
        # Node-local; recording follows the tracer's enabled flag, and the
        # committed stage feeds the tx_commit_latency SLO budget.
        self.tx_tracker = None
        if getattr(config.instrumentation, "txtrace_enabled", True):
            from tendermint_tpu.libs.txtrace import TxTracker

            self.tx_tracker = TxTracker(
                max_txs=getattr(config.instrumentation, "txtrace_ring", 8192),
                metrics=self.metrics.txtrace,
                slo=self.slo,
            )

        # per-height/round consensus timeline ring (consensus/timeline.py) —
        # node-local (unlike the tracer), served by /debug/consensus_timeline;
        # recording is gated on the tracer's enabled flag in cs_state
        from tendermint_tpu.consensus.timeline import ConsensusTimeline

        self.timeline = ConsensusTimeline(
            max_heights=config.instrumentation.timeline_heights
        )

        # databases
        self.block_db = _open_db(config, "blockstore")
        self.state_db = _open_db(config, "state")
        self.evidence_db = _open_db(config, "evidence")
        self.block_store = BlockStore(self.block_db)
        self.state_store = StateStore(self.state_db)

        # state from store or genesis
        state = self.state_store.load()
        if state is None:
            genesis.validate_and_complete()
            state = state_from_genesis(genesis)

        # ABCI app (4 logical connections); an external proxy_app address
        # selects the socket/grpc transport (reference: proxy/client.go)
        remote_app = bool(config.base.proxy_app)
        if client_creator is None:
            if remote_app:
                from tendermint_tpu.proxy.multi import default_client_creator

                client_creator = default_client_creator(
                    config.base.proxy_app, config.base.abci,
                    call_timeout=config.base.abci_call_timeout,
                )
            else:
                app = app or default_app(config.base.abci)
                client_creator = local_client_creator(app)
        self.app = app
        # remote apps get reconnect-with-backoff on the non-consensus conns
        # (an app restart must not crash the node); the consensus conn stays
        # fatal-loud either way
        self.proxy_app = AppConns(
            client_creator,
            resilient=remote_app,
            attempts=config.base.abci_reconnect_attempts,
            base_delay=config.base.abci_reconnect_base_delay,
            max_delay=config.base.abci_reconnect_max_delay,
        )

        # event bus + tx indexer
        self.event_bus = EventBus()
        self.tx_indexer = KVTxIndexer(_open_db(config, "tx_index"))
        self.indexer_service = IndexerService(self.tx_indexer, self.event_bus)

        # handshake: sync app with chain
        handshaker = Handshaker(self.state_store, state, self.block_store, genesis, self.event_bus)
        state = handshaker.handshake(self.proxy_app)
        self.state = state

        # mempool
        self.mempool = Mempool(
            self.proxy_app.mempool,
            max_txs=config.mempool.size,
            max_txs_bytes=config.mempool.max_txs_bytes,
            cache_size=config.mempool.cache_size,
            keep_invalid_txs_in_cache=config.mempool.keep_invalid_txs_in_cache,
            recheck=config.mempool.recheck,
            metrics=self.metrics.mempool,
            wal_path=(
                os.path.join(config.root_dir, config.mempool.wal_dir, "wal")
                if config.mempool.wal_dir and config.root_dir
                else ""
            ),
            max_tx_bytes=config.mempool.max_tx_bytes,
            ttl_num_blocks=config.mempool.ttl_num_blocks,
            ttl_seconds=config.mempool.ttl_seconds,
            eviction=config.mempool.eviction,
            max_txs_per_sender=config.mempool.max_txs_per_sender,
            tx_tracker=self.tx_tracker,
            # device-batched tx admission (crypto/scheduler.py admission
            # lane + the RequestCheckTx.sig_precheck ABCI split)
            scheduler=self.scheduler,
            sig_precheck=(
                self.scheduler is not None
                and config.scheduler.admission_precheck
            ),
        )

        # evidence pool
        self.evidence_pool = EvidencePool(self.evidence_db, self.state_store, self.block_store)
        self.evidence_pool.set_state(state)

        # block executor
        self.block_exec = BlockExecutor(
            self.state_store,
            self.proxy_app.consensus,
            self.mempool,
            self.evidence_pool,
            event_bus=self.event_bus,
            block_store=self.block_store,
            metrics=self.metrics.state,
            tx_tracker=self.tx_tracker,
        )

        # consensus
        if os.path.isabs(config.consensus.wal_path):
            wal_path = config.consensus.wal_path
        elif config.root_dir:
            wal_path = os.path.join(config.root_dir, config.consensus.wal_path)
        else:
            wal_path = os.path.join(os.getcwd(), ".tmp_wal", "wal")
        self.wal = WAL(
            wal_path,
            group_commit=config.consensus.wal_group_commit,
            group_commit_max_latency=config.consensus.wal_group_commit_max_latency,
        )
        self.consensus = ConsensusState(
            config.consensus,
            state,
            self.block_exec,
            self.block_store,
            self.mempool,
            self.evidence_pool,
            self.wal,
            event_bus=self.event_bus,
            priv_validator=priv_validator,
            metrics=self.metrics.consensus,
            timeline=self.timeline,
            slo=self.slo,
            tx_tracker=self.tx_tracker,
        )

        self.rpc_server = None
        self.grpc_server = None
        self.prometheus_server = None
        self._running = False

        # light-client-as-a-service (light/service.py, ROADMAP item 3):
        # answers light_verify/light_block RPC requests from a verified-
        # header cache with single-flight dedupe, coalescing distinct-height
        # misses into shared cross-height device flushes. Constructed
        # eagerly (cheap: no background work until the first request);
        # served by the light_* RPC routes + GET /debug/light.
        self.light_service = None
        if getattr(config, "light_service", None) is not None and config.light_service.enabled:
            from tendermint_tpu.light.service import LightService, LocalNodeProvider

            self.light_service = LightService(
                genesis.chain_id,
                LocalNodeProvider(self),
                config.light_service,
                metrics=self.metrics.light,
                slo=self.slo,
                scheduler=self.scheduler,
                # [scheduler] enabled=false means NO lane engine anywhere —
                # the service must not spin up a private one behind the
                # operator's back (it degrades to per-window-body flushes)
                own_scheduler_if_missing=False,
            )

        # overload controller (node/overload.py): samples queue depths into
        # a pressure level and flips the shed switches (mempool gossip, RPC
        # gate, evidence walk) — never the vote path
        from tendermint_tpu.node.overload import OverloadController

        self.overload = OverloadController(
            self, config.overload, metrics=self.metrics.overload
        )

        # p2p (reference: node/node.go:754-793 createTransport/createSwitch)
        self.switch = None
        self.node_key = None
        self.consensus_reactor = None
        self.mempool_reactor = None
        self.blocksync_reactor = None
        self.statesync_reactor = None
        self.addr_book = None
        self.pex_reactor = None
        self.fast_sync = False
        # state sync only makes sense on an empty chain
        # (reference: node/node.go:672 decide stateSync)
        self.state_sync = bool(config.statesync.enable) and self.block_store.height == 0
        self._state_provider = state_provider
        self._statesync_task = None
        if config.p2p.laddr:
            from tendermint_tpu.consensus.reactor import ConsensusReactor
            from tendermint_tpu.evidence.reactor import EvidenceReactor
            from tendermint_tpu.mempool.reactor import MempoolReactor
            from tendermint_tpu.p2p import (
                MultiplexTransport,
                NodeInfo,
                NodeKey,
                Switch,
            )

            if Switch is None:
                # the package gates the networked pieces when the
                # `cryptography` wheel is absent; keep the old loud failure
                # for nodes that actually configured a p2p listener
                raise ImportError(
                    "p2p.laddr is configured but the p2p transport is "
                    "unavailable (missing `cryptography` wheel)"
                )
            if not config.p2p.plaintext:
                from tendermint_tpu.p2p.conn.secret_connection import (
                    HAVE_CRYPTOGRAPHY,
                )

                if not HAVE_CRYPTOGRAPHY:
                    raise ImportError(
                        "p2p.laddr is configured with secret connections but "
                        "the `cryptography` wheel is missing; set "
                        "p2p.plaintext=true for unauthenticated in-process "
                        "test nets"
                    )
            if config.root_dir:
                self.node_key = NodeKey.load_or_gen(
                    os.path.join(config.root_dir, "config", "node_key.json")
                )
            else:
                self.node_key = NodeKey.generate()
            node_info = NodeInfo(
                node_id=self.node_key.id,
                listen_addr=config.p2p.laddr,
                network=genesis.chain_id,
                moniker=config.base.moniker,
            )
            fuzz_cfg = None
            if config.p2p.test_fuzz:
                from tendermint_tpu.p2p.fuzz import FuzzConfig

                # seeded => every fuzzed connection's fault sequence replays
                # from [p2p] fuzz_seed (see transport._upgrade's derivation)
                fuzz_cfg = FuzzConfig(seed=config.p2p.fuzz_seed)
            transport = MultiplexTransport(
                self.node_key,
                node_info,
                use_secret_conn=not config.p2p.plaintext,
                fuzz_config=fuzz_cfg,
            )
            trust_path = (
                os.path.join(config.root_dir, "data", "trust_metrics.json")
                if config.root_dir
                else None
            )
            recv_limit = None
            if config.p2p.recv_rate_limit:
                from tendermint_tpu.p2p.conn.connection import RecvRateLimit

                recv_limit = RecvRateLimit(
                    bytes_per_s=config.p2p.recv_rate_bytes_per_channel,
                    msgs_per_s=config.p2p.recv_rate_msgs_per_channel,
                    strikes=config.p2p.recv_rate_strikes,
                    strike_window=config.p2p.recv_rate_strike_window,
                )
            self.switch = Switch(
                transport, metrics=self.metrics.p2p, trust_store_path=trust_path,
                recv_limit=recv_limit,
            )
            # fast sync is pointless when we are the only validator
            # (reference: node/node.go onlyValidatorIsUs)
            only_us = (
                priv_validator is not None
                and state.validators.size() == 1
                and state.validators.validators[0].address
                == priv_validator.get_pub_key().address()
            )
            self.fast_sync = bool(config.base.fast_sync) and not only_us
            self.consensus_reactor = ConsensusReactor(
                self.consensus, wait_sync=self.fast_sync or self.state_sync
            )
            self.switch.add_reactor("CONSENSUS", self.consensus_reactor)
            self.mempool_reactor = MempoolReactor(
                self.mempool, broadcast=config.mempool.broadcast,
                metrics=self.metrics.overload,
            )
            self.switch.add_reactor("MEMPOOL", self.mempool_reactor)
            self.switch.add_reactor("EVIDENCE", EvidenceReactor(self.evidence_pool))
            from tendermint_tpu.blocksync.reactor import BlocksyncReactor

            # a state-sync node starts blocksync only after the snapshot
            # restore (switch_to_blocksync handoff)
            # crash-resume checkpoints (ISSUE 12): only nodes with a real
            # root dir persist them (memdb test nodes re-fetch, always safe)
            catchup_ckpt = (
                os.path.join(config.root_dir, "data", "catchup_checkpoint.json")
                if config.root_dir
                else None
            )
            restore_ckpt = (
                os.path.join(config.root_dir, "data", "statesync_checkpoint.json")
                if config.root_dir
                else None
            )
            self.blocksync_reactor = BlocksyncReactor(
                state, self.block_exec, self.block_store,
                consensus_reactor=self.consensus_reactor,
                active=self.fast_sync and not self.state_sync,
                metrics=self.metrics.blocksync,
                peer_timeout=config.fastsync.peer_timeout,
                retry_sleep=config.fastsync.retry_sleep,
                scheduler=self.scheduler,
                checkpoint_path=catchup_ckpt,
            )
            self.switch.add_reactor("BLOCKSYNC", self.blocksync_reactor)
            from tendermint_tpu.statesync.reactor import StatesyncReactor

            self.statesync_reactor = StatesyncReactor(
                self.proxy_app.snapshot, self.proxy_app.query, active=self.state_sync,
                metrics=self.metrics.statesync,
                checkpoint_path=restore_ckpt,
            )
            self.switch.add_reactor("STATESYNC", self.statesync_reactor)
            if config.p2p.pex:
                from tendermint_tpu.p2p.pex import AddrBook, PexReactor

                book_file = (
                    os.path.join(config.root_dir, "config", "addrbook.json")
                    if config.root_dir
                    else None
                )
                self.addr_book = AddrBook(book_file)
                seeds = [s.strip() for s in config.p2p.seeds.split(",") if s.strip()]
                self.pex_reactor = PexReactor(
                    self.addr_book,
                    seeds=seeds,
                    max_outbound=config.p2p.max_num_outbound_peers,
                    seed_mode=config.p2p.seed_mode,
                )
                self.switch.add_reactor("PEX", self.pex_reactor)
        else:
            self.state_sync = False

    async def start(self) -> None:
        self._running = True
        self._start_crypto_prewarm()
        await self.indexer_service.start()
        if not (self.switch is not None and (self.fast_sync or self.state_sync)):
            # with fast/state sync active, consensus starts at the blocksync
            # handoff (reference: node/node.go:897 startStateSync -> SwitchToConsensus)
            await self.consensus.start()
        if self.switch is not None:
            await self.switch.start()
            host, port = self._parse_laddr(self.config.p2p.laddr)
            self.p2p_addr = await self.switch.transport.listen(host, port)
            if self.config.p2p.persistent_peers:
                peers = [a.strip() for a in self.config.p2p.persistent_peers.split(",") if a.strip()]
                await self.switch.dial_peers_async(peers, persistent=True)
        if self.config.rpc.laddr:
            from tendermint_tpu.rpc.server import RPCServer

            self.rpc_server = RPCServer(self)
            await self.rpc_server.start()
        if self.config.rpc.grpc_laddr:
            from tendermint_tpu.rpc.grpc_api import GrpcBroadcastServer

            self.grpc_server = GrpcBroadcastServer(self, self.config.rpc.grpc_laddr)
            self.grpc_server.start()
        if self.config.instrumentation.prometheus:
            from tendermint_tpu.libs.prometheus_server import PrometheusServer

            self.prometheus_server = PrometheusServer(
                self.metrics, self.config.instrumentation.prometheus_listen_addr
            )
            await self.prometheus_server.start()
        if self.state_sync:
            self._statesync_task = asyncio.create_task(
                self._run_state_sync(), name="statesync"
            )
        if self.config.overload.enabled:
            self.overload.start()
        self._install_punish_hook()
        logger.info("node started (chain %s)", self.genesis.chain_id)

    def _install_punish_hook(self) -> None:
        """Route suspicion-scorer punishments (crypto/provenance.py) into
        the existing enforcement machinery: a punished ``peer:<id>`` feeds
        the p2p trust scorer a BAD_MESSAGE report (repeated reports drop the
        peer below the trust threshold and disconnect it), and a punished
        ``sender:<id>`` collapses that sender's mempool quota. The callback
        fires on a verify thread, so p2p reports hop to the event loop."""
        from tendermint_tpu.crypto import provenance as _prov

        loop = asyncio.get_event_loop()

        def punish(source: str, info: dict) -> None:
            if source.startswith("peer:"):
                if self.switch is None:
                    return
                from tendermint_tpu.p2p.behaviour import BAD_MESSAGE, PeerBehaviour

                pb = PeerBehaviour(
                    source[len("peer:"):], BAD_MESSAGE,
                    f"signature poisoning ({info.get('offenses', 0)} bad rows in quarantine)",
                )

                def _report():
                    asyncio.ensure_future(self.switch.reporter.report(pb))

                loop.call_soon_threadsafe(_report)
            elif source.startswith("sender:"):
                self.mempool.penalize_sender(source[len("sender:"):])

        self._punish_cb = punish
        _prov.default_scorer().add_punish_callback(punish)

    async def _run_state_sync(self) -> None:
        """Restore from a peer snapshot, bootstrap the stores, then hand off
        to block sync (reference: node/node.go:560 startStateSync)."""
        cfg = self.config.statesync
        provider = self._state_provider
        if provider is None:
            from tendermint_tpu.rpc.client import HTTPClient
            from tendermint_tpu.statesync.stateprovider import (
                LightClientStateProvider,
            )

            provider = LightClientStateProvider(
                self.genesis.chain_id,
                [HTTPClient(u) for u in cfg.rpc_servers],
                cfg.trust_height,
                bytes.fromhex(cfg.trust_hash),
                int(cfg.trust_period * 1_000_000_000),
            )
        try:
            state, commit = await self.statesync_reactor.sync(
                provider,
                cfg.discovery_time,
                chunk_fetchers=cfg.chunk_fetchers,
                chunk_timeout=cfg.chunk_request_timeout,
                chunk_retries=cfg.chunk_retries,
                chunk_backoff=cfg.chunk_backoff,
            )
        except asyncio.CancelledError:
            raise
        except Exception:
            # STRUCTURED fallback (ISSUE 12): when every snapshot/peer is
            # exhausted (the retry ladder's ErrNoSnapshots terminus) — or
            # anything else goes wrong — fall back to block sync from
            # genesis rather than wedging the node in wait_sync forever
            logger.exception("state sync failed; falling back to block sync")
            if self.metrics is not None:
                self.metrics.statesync.fallbacks_total.inc()
            await self.blocksync_reactor.switch_to_blocksync(self.state)
            return
        self.state_store.bootstrap(state)
        self.block_store.save_seen_commit(state.last_block_height, commit)
        self.state = state
        self.evidence_pool.set_state(state)
        logger.info(
            "state synced to height %d; switching to block sync",
            state.last_block_height,
        )
        await self.blocksync_reactor.switch_to_blocksync(state)

    @staticmethod
    def _parse_laddr(laddr: str) -> tuple:
        addr = laddr.split("://", 1)[-1]
        host, _, port = addr.rpartition(":")
        return host or "127.0.0.1", int(port)

    def _start_crypto_prewarm(self) -> None:
        """Compile the steady-state verification kernels for THIS chain's
        validator-set size in a daemon thread (crypto/batch.prewarm): a node
        cold-starting into a vote storm must not stall its receive loop on a
        first-call kernel compile (round-3 finding: minutes per shape)."""
        import threading

        from tendermint_tpu.crypto import batch as _batch

        try:
            vals = self.consensus.rs.validators
            n_vals = vals.size()
            pubkeys = [v.pub_key.bytes() for v in vals.validators]
            # BLS buckets warm only when the valset actually carries BLS
            # keys (flag-gated; zero cost on pure-ed25519 chains)
            has_bls = any(
                v.pub_key.type_name() == "bls12_381" for v in vals.validators
            )
        except Exception:
            n_vals, pubkeys, has_bls = 0, None, False
        if n_vals <= 0 or (_batch.backend_default() != "jax" and not has_bls):
            return

        def run():
            try:
                _batch.prewarm(n_vals, pubkeys=pubkeys, bls=has_bls)
            except Exception:  # prewarm is best-effort; first caller compiles
                import logging

                logging.getLogger("tendermint_tpu.node").exception(
                    "crypto kernel prewarm failed"
                )

        threading.Thread(target=run, name="crypto-prewarm", daemon=True).start()

    async def stop(self) -> None:
        self._running = False
        if getattr(self, "_punish_cb", None) is not None:
            from tendermint_tpu.crypto import provenance as _prov

            _prov.default_scorer().remove_punish_callback(self._punish_cb)
            self._punish_cb = None
        if self.light_service is not None:
            self.light_service.close()
        if self.scheduler is not None:
            from tendermint_tpu.crypto import scheduler as _sched

            # last-node-wins model: only deregister if still ours; close()
            # drains queued work so no consumer blocks into its fallback
            if _sched.default_scheduler() is self.scheduler:
                _sched.set_default(None)
            self.scheduler.close()
        await self.overload.stop()
        if self._statesync_task is not None:
            self._statesync_task.cancel()
        if self.rpc_server is not None:
            await self.rpc_server.stop()
        if self.grpc_server is not None:
            self.grpc_server.stop()
        if self.prometheus_server is not None:
            await self.prometheus_server.stop()
        if self.switch is not None:
            await self.switch.stop()
        await self.consensus.stop()
        await self.indexer_service.stop()
        if self._owns_priv_validator:
            self.priv_validator.close()
        self.mempool.close_wal()
        self.proxy_app.stop()
        if self.slo is not None:
            from tendermint_tpu.libs import slo as _slo

            # don't leave a dead engine as the process-global flush feed
            # (last-node-wins model: only deregister if it's still ours)
            if _slo.default_engine() is self.slo:
                _slo.set_default(None)
        for db in (self.block_db, self.state_db, self.evidence_db):
            db.close()

    # convenience for tests / RPC
    async def wait_for_height(self, height: int, timeout: float = 30.0) -> None:
        deadline = asyncio.get_event_loop().time() + timeout
        while self.block_store.height < height:
            if asyncio.get_event_loop().time() > deadline:
                raise TimeoutError(
                    f"timed out waiting for height {height} (at {self.block_store.height})"
                )
            await asyncio.sleep(0.02)
