"""RPC server routes + HTTP/local clients
(reference models: rpc/core tests, rpc/client tests)."""

import asyncio
import os

from tendermint_tpu.abci.kvstore import KVStoreApplication
from tendermint_tpu.config.config import test_config
from tendermint_tpu.crypto import gen_ed25519, tmhash
from tendermint_tpu.node.node import Node
from tendermint_tpu.privval.file_pv import FilePV
from tendermint_tpu.rpc.client import HTTPClient, LocalClient
from tendermint_tpu.types.genesis import GenesisDoc, GenesisValidator

os.environ.setdefault("TMTPU_CRYPTO_BACKEND", "cpu")


def make_node(tmp_path, rpc_port=0):
    cfg = test_config()
    cfg.base.db_backend = "memdb"
    cfg.rpc.laddr = f"tcp://127.0.0.1:{rpc_port}" if rpc_port else ""
    cfg.root_dir = ""
    cfg.consensus.wal_path = str(tmp_path / "wal")
    priv = FilePV(gen_ed25519(b"\x81" * 32))
    gen = GenesisDoc(chain_id="rpc-chain", validators=[GenesisValidator(priv.get_pub_key(), 10)])
    return Node(cfg, gen, priv_validator=priv, app=KVStoreApplication())


def test_rpc_routes_via_local_client(tmp_path):
    async def run():
        node = make_node(tmp_path)
        await node.start()
        try:
            client = LocalClient(node)
            # commit a tx and wait for it
            res = await client.broadcast_tx_commit(tx="0x" + b"rpc=local".hex())
            assert res["deliver_tx"]["code"] == 0
            height = int(res["height"])

            # tx + tx_search by height and by app event
            h = tmhash.sum256(b"rpc=local").hex()
            tx = await client.tx(hash=h)
            assert int(tx["height"]) == height
            found = await client.tx_search(query=f"tx.height={height}")
            assert int(found["total_count"]) >= 1

            # block_search over a range
            await node.wait_for_height(height + 1, timeout=30)
            bs = await client.block_search(query=f"block.height >= {height} AND block.height <= {height}")
            assert int(bs["total_count"]) == 1
            assert bs["blocks"][0]["block"]["header"]["height"] == str(height)

            # block_results carries the deliver_tx result
            br = await client.block_results(height=height)
            assert br["txs_results"][0]["code"] == 0

            # block_by_hash round-trips
            blk = await client.block(height=height)
            byh = await client.block_by_hash(hash=blk["block_id"]["hash"])
            assert byh["block"]["header"]["height"] == str(height)

            # consensus introspection
            dcs = await client.dump_consensus_state()
            assert int(dcs["round_state"]["height"]) >= height
            cp = await client.consensus_params()
            assert int(cp["consensus_params"]["block"]["max_bytes"]) > 0
        finally:
            await node.stop()

    asyncio.run(run())


def test_rpc_http_client_end_to_end(tmp_path):
    async def run():
        node = make_node(tmp_path, rpc_port=0)
        # pick a free port
        import socket as s

        sock = s.socket()
        sock.bind(("127.0.0.1", 0))
        port = sock.getsockname()[1]
        sock.close()
        node.config.rpc.laddr = f"tcp://127.0.0.1:{port}"
        await node.start()
        client = HTTPClient(f"http://127.0.0.1:{port}")
        try:
            st = await client.status()
            assert st["node_info"]["network"] == "rpc-chain"
            res = await client.broadcast_tx_commit(b"rpc=http")
            assert res["deliver_tx"]["code"] == 0
            q = await client.abci_query("/store", b"rpc")
            import base64

            assert base64.b64decode(q["response"]["value"]) == b"http"
            ni = await client.net_info()
            assert ni["n_peers"] == "0"
            # error surfaces as RPCError
            try:
                await client.call("nonexistent_route")
                assert False
            except Exception as e:
                assert "not found" in str(e)
        finally:
            await client.close()
            await node.stop()

    asyncio.run(run())


def test_check_tx_route(tmp_path):
    """check_tx runs CheckTx against the app without mempool admission
    (reference: rpc/core/routes.go:26, rpc/core/mempool.go CheckTx)."""

    async def run():
        node = make_node(tmp_path)
        await node.start()
        try:
            client = LocalClient(node)
            res = await client.call("check_tx", tx="0x" + b"k=v".hex())
            assert res["code"] == 0
            # the tx must NOT have entered the mempool
            assert node.mempool.size() == 0
            # kvstore rejects empty txs with code 1
            bad = await client.call("check_tx", tx="")
            assert bad["code"] == 1
        finally:
            await node.stop()

    asyncio.run(run())


def test_broadcast_evidence_route(tmp_path):
    async def run():
        node = make_node(tmp_path)
        await node.start()
        try:
            import dataclasses
            import time

            from tendermint_tpu.types.basic import BlockID, PartSetHeader, SignedMsgType
            from tendermint_tpu.types.evidence import DuplicateVoteEvidence
            from tendermint_tpu.types.vote import Vote

            await node.wait_for_height(1, timeout=30)
            priv = node.priv_validator
            addr = priv.get_pub_key().address()
            psh = PartSetHeader(total=1, hash=b"\x41" * 32)

            def mkvote(bid):
                v = Vote(
                    type=SignedMsgType.PREVOTE, height=node.consensus.rs.height, round=0,
                    block_id=bid, timestamp_ns=time.time_ns(),
                    validator_address=addr, validator_index=0,
                )
                sig = priv.priv_key.sign(v.sign_bytes("rpc-chain"))
                return dataclasses.replace(v, signature=sig)

            va = mkvote(BlockID(b"\x42" * 32, psh))
            vb = mkvote(BlockID(b"\x43" * 32, psh))
            ev = DuplicateVoteEvidence.from_votes(
                va, vb, time.time_ns(),
                node.state.validators.total_voting_power(), 10,
            )
            client = LocalClient(node)
            out = await client.broadcast_evidence(evidence="0x" + ev.encode().hex())
            assert out["hash"] == ev.hash().hex().upper()
            assert len(node.evidence_pool.pending_evidence(-1)) == 1
        finally:
            await node.stop()

    asyncio.run(run())


def test_unsafe_routes_gated_and_mempool_wal(tmp_path):
    """dial_seeds/unsafe_flush_mempool refuse unless rpc.unsafe=true; the
    mempool WAL logs admitted txs (reference: rpc/core/net.go UnsafeDialSeeds,
    mempool InitWAL)."""

    async def run():
        node = make_node(tmp_path)
        await node.start()
        try:
            client = LocalClient(node)
            try:
                await client.call("unsafe_flush_mempool")
                assert False, "unsafe route should be gated"
            except Exception as e:
                assert "unsafe" in str(e)
            node.config.rpc.unsafe = True
            node.mempool.check_tx(b"w=1")
            assert node.mempool.size() == 1
            await client.call("unsafe_flush_mempool")
            assert node.mempool.size() == 0
        finally:
            await node.stop()

        # mempool WAL records admitted txs
        from tendermint_tpu.mempool.mempool import Mempool

        class OkApp:
            def check_tx(self, req):
                from tendermint_tpu.abci import types as abci

                return abci.ResponseCheckTx(code=abci.CODE_TYPE_OK)

        wal = str(tmp_path / "mwal" / "wal")
        mp = Mempool(OkApp(), wal_path=wal)
        mp.check_tx(b"tx-one")
        mp.check_tx(b"tx-two")
        mp.close_wal()
        raw = open(wal, "rb").read()
        txs = []
        while raw:
            n = int.from_bytes(raw[:4], "big")
            txs.append(raw[4 : 4 + n])
            raw = raw[4 + n :]
        assert txs == [b"tx-one", b"tx-two"]

    asyncio.run(run())


def test_unsafe_profile_dump_routes(tmp_path):
    """unsafe_dump_stacks / unsafe_dump_heap: the debug dump's pprof analogs
    (reference: cmd/tendermint/commands/debug/dump.go:117-125)."""

    async def run():
        node = make_node(tmp_path)
        await node.start()
        try:
            client = LocalClient(node)
            try:
                await client.call("unsafe_dump_stacks")
                assert False, "should be gated"
            except Exception as e:
                assert "unsafe" in str(e)
            node.config.rpc.unsafe = True

            stacks = await client.call("unsafe_dump_stacks")
            assert stacks["threads"]  # at least the main thread
            assert stacks["tasks"]  # consensus receive loop etc.
            assert any("cs_state" in s or "receive" in s for s in stacks["tasks"].values())

            first = await client.call("unsafe_dump_heap")
            assert first["tracing_started"] is True
            second = await client.call("unsafe_dump_heap", top=10)
            assert second["tracing_started"] is False
            assert second["traced_current_bytes"] > 0
            assert len(second["top"]) <= 10
            assert all("file" in s and "size_bytes" in s for s in second["top"])
            import tracemalloc

            tracemalloc.stop()
        finally:
            await node.stop()

    asyncio.run(run())


def test_debug_trace_and_verify_stats_routes(tmp_path):
    """Acceptance: a CPU-backend verify_batch flush leaves a span tree
    retrievable via GET /debug/trace (naming path choice and batch size) and
    aggregated telemetry via /debug/verify_stats — no device needed."""
    import aiohttp

    from tendermint_tpu.crypto import batch as B

    async def run():
        import socket as s

        sock = s.socket()
        sock.bind(("127.0.0.1", 0))
        port = sock.getsockname()[1]
        sock.close()
        node = make_node(tmp_path)
        node.config.rpc.laddr = f"tcp://127.0.0.1:{port}"
        node.config.instrumentation.trace_enabled = True
        await node.start()
        try:
            # one real CPU-backend flush through the production entry point
            priv = node.priv_validator
            pk = priv.get_pub_key().bytes()
            msgs = [b"dbg-%d" % i for i in range(7)]
            sigs = [priv.priv_key.sign(m) for m in msgs]
            assert B.verify_batch([pk] * 7, msgs, sigs, backend="cpu").all()

            async with aiohttp.ClientSession() as sess:
                async with sess.get(
                    f"http://127.0.0.1:{port}/debug/trace"
                ) as resp:
                    assert resp.status == 200
                    body = (await resp.json())["result"]
                assert body["enabled"] is True
                assert body["ring_size"] == node.config.instrumentation.trace_ring_size
                assert body["count"] <= body["ring_size"]
                spans = [e for e in body["events"] if e["name"] == "verify_batch"]
                flush = next(
                    e for e in spans if e.get("attrs", {}).get("n") == 7
                )
                # the span names the chosen path and the batch size
                assert flush["attrs"]["path"] == "cpu"
                assert flush["attrs"]["backend"] == "cpu"
                assert "dur_ms" in flush and "span" in flush
                # its flush event sits under it (span tree), inside the
                # record's own span; all three share the request's root
                children = [
                    e for e in body["events"] if e.get("parent") == flush["span"]
                ]
                record = next(e for e in children if e["name"] == "flush.record")
                assert any(
                    e["name"] == "batch_verify.flush"
                    and e["parent"] == record["span"]
                    and e["root"] == flush["root"]
                    for e in body["events"]
                )

                # ?limit=N truncates to the newest N
                async with sess.get(
                    f"http://127.0.0.1:{port}/debug/trace?limit=2"
                ) as resp:
                    limited = (await resp.json())["result"]
                assert limited["count"] <= 2

                async with sess.get(
                    f"http://127.0.0.1:{port}/debug/verify_stats"
                ) as resp:
                    assert resp.status == 200
                    stats = (await resp.json())["result"]
                assert stats["totals"]["cpu/cpu"]["flushes"] >= 1
                # last_flush tracks whatever flushed most recently (the
                # running node keeps verifying its own commits): assert
                # shape, not identity
                assert {"backend", "path", "n", "total_ms"} <= set(
                    stats["last_flush"]
                )
                assert "device" in stats and "stage_seconds" in stats

            # same routes over the JSON-RPC method table (LocalClient)
            client = LocalClient(node)
            dump = await client.call("debug_trace", limit=5)
            assert dump["count"] <= 5
            st = await client.call("debug_verify_stats")
            assert st["totals"]["cpu/cpu"]["sigs"] >= 7
        finally:
            await node.stop()

    asyncio.run(run())


def test_trace_config_applied_at_node_construction(tmp_path):
    """[instrumentation] trace_enabled/trace_ring_size are applied by
    Node.__init__ (process-global, like the verify mode)."""
    from tendermint_tpu.libs import trace

    cfg = test_config()
    cfg.base.db_backend = "memdb"
    cfg.rpc.laddr = ""
    cfg.root_dir = ""
    cfg.consensus.wal_path = str(tmp_path / "wal")
    cfg.instrumentation.trace_enabled = False
    cfg.instrumentation.trace_ring_size = 99
    priv = FilePV(gen_ed25519(b"\x82" * 32))
    gen = GenesisDoc(
        chain_id="trace-cfg",
        validators=[GenesisValidator(priv.get_pub_key(), 10)],
    )
    try:
        Node(cfg, gen, priv_validator=priv, app=KVStoreApplication())
        assert trace.tracer.enabled is False
        assert trace.tracer.ring_size == 99
    finally:
        trace.tracer.configure(
            enabled=True, ring_size=trace.DEFAULT_RING_SIZE
        )


def test_websocket_subscription_client(tmp_path):
    """WS event client (reference: rpc/client/http WSEvents): subscribe to
    NewBlock + Tx events over /websocket, client-side broadcast-and-wait."""

    async def run():
        node = make_node(tmp_path, rpc_port=0)
        import socket as s

        sock = s.socket()
        sock.bind(("127.0.0.1", 0))
        port = sock.getsockname()[1]
        sock.close()
        node.config.rpc.laddr = f"tcp://127.0.0.1:{port}"
        await node.start()
        client = HTTPClient(f"http://127.0.0.1:{port}")
        try:
            # event subscription: next block shows up
            sub = await client.subscribe("tm.event = 'NewBlock'")
            ev = await asyncio.wait_for(sub.next(), 30)
            assert ev["events"]["tm.event"] == ["NewBlock"]
            # plain RPC calls ride the same ws connection
            ws = await client._ws_events()
            st = await ws.call("status")
            assert st["node_info"]["network"] == "rpc-chain"
            # client-side broadcast_tx_commit wait (subscribe by tx.hash,
            # fire the tx, await its DeliverTx event)
            tx = b"ws=commit"
            waiter = asyncio.create_task(
                client.wait_for_tx(tmhash.sum256(tx), timeout=30)
            )
            await asyncio.sleep(0.05)  # subscription in flight first
            await client.broadcast_tx_sync(tx)
            ev = await waiter
            assert ev["events"]["tx.hash"] == [tmhash.sum256(tx).hex().upper()]
            # per-query unsubscribe leaves the NewBlock sub alive
            ev2 = await asyncio.wait_for(sub.next(), 30)
            assert ev2["events"]["tm.event"] == ["NewBlock"]
            await sub.unsubscribe()
        finally:
            await client.close()
            await node.stop()

    asyncio.run(run())
