"""RPC layer tests: an external client drives a live node end-to-end
(reference model: rpc/client/rpc_test.go, rpc/jsonrpc tests).

Boots a single-validator node with the RPC server on an ephemeral port,
then exercises the route surface over real HTTP and websocket
connections — info routes, the tx lifecycle (broadcast_tx_commit →
tx_search), ABCI passthrough, and event subscriptions.
"""

import asyncio
import base64
import json
import time

import pytest

from tendermint_tpu.config import Config
from tendermint_tpu.crypto.ed25519 import PrivKeyEd25519
from tendermint_tpu.node import make_node
from tendermint_tpu.privval import FilePV
from tendermint_tpu.rpc import HTTPClient, RPCClientError, WSClient
from tendermint_tpu.types.genesis import GenesisDoc, GenesisValidator
from tendermint_tpu.types.tx import tx_hash

CHAIN = "rpc-chain"


def run(coro):
    return asyncio.run(coro)


def _make_cfg(tmp_path) -> tuple[Config, PrivKeyEd25519]:
    priv = PrivKeyEd25519.from_seed(b"\x09" * 32)
    genesis = GenesisDoc(
        chain_id=CHAIN,
        genesis_time_ns=time.time_ns(),
        validators=[GenesisValidator(pub_key=priv.pub_key(), power=10)],
    )
    cfg = Config()
    cfg.base.home = str(tmp_path / "rpcnode")
    cfg.base.chain_id = CHAIN
    cfg.base.db_backend = "memdb"
    cfg.consensus.timeout_commit = 0.2
    cfg.rpc.laddr = "tcp://127.0.0.1:0"
    cfg.p2p.laddr = "tcp://127.0.0.1:0"  # a free port, not 26656
    cfg.ensure_dirs()
    genesis.save_as(cfg.base.path(cfg.base.genesis_file))
    FilePV.from_priv_key(
        priv,
        cfg.base.path(cfg.priv_validator.key_file),
        cfg.base.path(cfg.priv_validator.state_file),
    ).save()
    return cfg, priv


async def _boot(tmp_path):
    cfg, priv = _make_cfg(tmp_path)
    node = make_node(cfg)
    await node.start()
    await node.consensus.wait_for_height(2, timeout=60.0)
    addr = f"127.0.0.1:{node.rpc_server.bound_port}"
    return node, addr


def test_info_and_block_routes(tmp_path):
    async def go():
        node, addr = await _boot(tmp_path)
        c = HTTPClient(addr)
        try:
            # health + status
            assert await c.call("health") == {}
            st = await c.call("status")
            assert st["sync_info"]["latest_block_height"] >= 1
            assert st["validator_info"]["voting_power"] == 10
            assert not st["sync_info"]["catching_up"]

            # net_info (no peers on a solo node)
            ni = await c.call("net_info")
            assert ni["n_peers"] == 0

            # genesis round-trips the chain id
            gen = await c.call("genesis")
            assert gen["genesis"]["chain_id"] == CHAIN
            chunk = await c.call("genesis_chunked", chunk=0)
            data = base64.b64decode(chunk["data"])
            assert json.loads(data)["chain_id"] == CHAIN

            # block routes agree with the node's own store
            h = node.block_store.height()
            blk = await c.call("block", height=h)
            assert blk["block"]["header"]["height"] == h
            assert blk["block"]["header"]["chain_id"] == CHAIN
            expected_hash = node.block_store.load_block(h).hash().hex()
            assert blk["block_id"]["hash"] == expected_hash

            by_hash = await c.call("block_by_hash", hash=expected_hash)
            assert by_hash["block"]["header"]["height"] == h

            hdr = await c.call("header", height=h)
            assert hdr["header"]["height"] == h
            hdr2 = await c.call("header_by_hash", hash=expected_hash)
            assert hdr2["header"]["height"] == h

            chain = await c.call("blockchain", min_height=1, max_height=h)
            assert chain["last_height"] >= h
            assert chain["block_metas"][0]["header"]["height"] == h

            # commit: block h's canonical commit lands when block h+1 is
            # saved, i.e. once consensus starts height h+2
            await node.consensus.wait_for_height(h + 2, timeout=30.0)
            cm = await c.call("commit", height=h)
            assert cm["canonical"]
            assert cm["signed_header"]["commit"]["height"] == h

            vals = await c.call("validators", height=h)
            assert vals["total"] == 1
            assert vals["validators"][0]["voting_power"] == 10

            cp = await c.call("consensus_params", height=h)
            assert cp["consensus_params"]["block"]["max_bytes"] > 0

            # bulk stateless serving: light_blocks serves a verifiable
            # ascending page that agrees with the single-height route
            from tendermint_tpu.types.light import (
                LightBlock,
                LightBlocksResponse,
            )

            single = await c.call("light_block", height=h)
            lb_single = LightBlock.from_proto(
                bytes.fromhex(single["light_block"])
            )
            bulk = await c.call("light_blocks", min_height=1, max_height=h)
            page = LightBlocksResponse.from_proto(
                bytes.fromhex(bulk["light_blocks"])
            )
            assert bulk["count"] == len(page.light_blocks) >= 1
            assert [b.height for b in page.light_blocks] == list(
                range(1, 1 + bulk["count"])
            )
            for b in page.light_blocks:
                b.validate_basic(CHAIN)
            if bulk["count"] >= h:
                assert (
                    page.light_blocks[h - 1].signed_header.hash()
                    == lb_single.signed_header.hash()
                )
            # the node's registry carries the bulk-route series
            assert (
                node.rpc_env.metrics.light_blocks_requests._values[()]
                >= 1.0
            )

            cs = await c.call("consensus_state")
            assert cs["round_state"]["height"] >= h
            dump = await c.call("dump_consensus_state")
            assert dump["round_state"]["height"] >= h

            # abci passthrough
            info = await c.call("abci_info")
            assert info["response"]["last_block_height"] >= 1

            # unknown method
            with pytest.raises(RPCClientError):
                await c.call("no_such_method")
            # out-of-range height
            with pytest.raises(RPCClientError):
                await c.call("block", height=10_000)
        finally:
            await c.close()
            await node.stop()

    run(go())


def test_tx_lifecycle_commit_and_search(tmp_path):
    async def go():
        node, addr = await _boot(tmp_path)
        c = HTTPClient(addr, timeout=30.0)
        try:
            tx = b"rpckey=rpcvalue"
            res = await c.call(
                "broadcast_tx_commit", tx=base64.b64encode(tx).decode()
            )
            assert res["check_tx"]["code"] == 0
            assert res["deliver_tx"]["code"] == 0
            assert res["height"] >= 1
            assert res["hash"] == tx_hash(tx).hex()

            # the tx is queryable from the app over abci_query
            q = await c.call(
                "abci_query", data=b"rpckey".hex(), path="/key"
            )
            assert bytes.fromhex(q["response"]["value"]) == b"rpcvalue"

            # and from the kv indexer
            got = await c.call("tx", hash=tx_hash(tx).hex())
            assert got["height"] == res["height"]
            assert base64.b64decode(got["tx"]) == tx

            found = await c.call(
                "tx_search", query=f"tx.height={res['height']}"
            )
            assert found["total_count"] >= 1
            assert any(
                t["hash"] == tx_hash(tx).hex() for t in found["txs"]
            )

            # block_search by height event
            bs = await c.call(
                "block_search", query=f"block.height={res['height']}"
            )
            assert bs["total_count"] >= 1

            # block_results carries the DeliverTx result
            br = await c.call("block_results", height=res["height"])
            assert br["txs_results"][0]["code"] == 0

            # sync/async variants
            tx2 = b"k2=v2"
            r2 = await c.call(
                "broadcast_tx_sync", tx=base64.b64encode(tx2).decode()
            )
            assert r2["code"] == 0
            tx3 = b"k3=v3"
            r3 = await c.call(
                "broadcast_tx_async", tx=base64.b64encode(tx3).decode()
            )
            assert r3["hash"] == tx_hash(tx3).hex()

            # check_tx (query conn, no mempool insertion)
            r4 = await c.call(
                "check_tx", tx=base64.b64encode(b"k4=v4").decode()
            )
            assert r4["code"] == 0

            # unconfirmed_txs drains as blocks commit
            n0 = await c.call("num_unconfirmed_txs")
            assert n0["n_txs"] >= 0
            await c.call("unsafe_flush_mempool")
            n1 = await c.call("num_unconfirmed_txs")
            assert n1["n_txs"] == 0
        finally:
            await c.close()
            await node.stop()

    run(go())


def test_websocket_subscribe_new_block_and_tx(tmp_path):
    async def go():
        node, addr = await _boot(tmp_path)
        ws = WSClient(addr, timeout=30.0)
        try:
            await ws.connect()
            assert await ws.call("subscribe", query="tm.event='NewBlock'") == {}
            ev = await ws.next_event(timeout=30.0)
            assert ev["query"] == "tm.event='NewBlock'"
            h = ev["data"]["value"]["block"]["header"]["height"]
            assert h >= 1

            # a second subscription on the same socket: tx events
            assert await ws.call("subscribe", query="tm.event='Tx'") == {}
            tx = b"wskey=wsvalue"
            res = await ws.call(
                "broadcast_tx_sync", tx=base64.b64encode(tx).decode()
            )
            assert res["code"] == 0
            for _ in range(20):
                ev = await ws.next_event(timeout=30.0)
                if ev["query"] == "tm.event='Tx'":
                    break
            else:
                pytest.fail("no Tx event received")
            assert ev["data"]["value"]["tx"] == tx.hex()

            # unsubscribe stops the NewBlock feed eventually
            await ws.call("unsubscribe", query="tm.event='NewBlock'")
            await ws.call("unsubscribe_all")
        finally:
            await ws.close()
            await node.stop()

    run(go())


def test_uri_get_and_batch_post(tmp_path):
    """URI GET form + JSON-RPC batch POST (reference:
    rpc/jsonrpc/server/http_uri_handler.go)."""

    async def go():
        node, addr = await _boot(tmp_path)
        host, port = addr.split(":")
        try:
            reader, writer = await asyncio.open_connection(host, int(port))
            writer.write(
                f"GET /status HTTP/1.1\r\nHost: {host}\r\n\r\n".encode()
            )
            await writer.drain()
            line = await reader.readline()
            assert b"200" in line
            headers = {}
            while True:
                ln = await reader.readline()
                if ln in (b"\r\n", b"\n", b""):
                    break
                k, _, v = ln.decode().partition(":")
                headers[k.strip().lower()] = v.strip()
            body = await reader.readexactly(int(headers["content-length"]))
            obj = json.loads(body)
            assert obj["result"]["sync_info"]["latest_block_height"] >= 1

            # batch POST on the same keep-alive connection
            batch = json.dumps(
                [
                    {"jsonrpc": "2.0", "id": 1, "method": "health"},
                    {"jsonrpc": "2.0", "id": 2, "method": "status"},
                ]
            ).encode()
            writer.write(
                (
                    f"POST / HTTP/1.1\r\nHost: {host}\r\n"
                    "Content-Type: application/json\r\n"
                    f"Content-Length: {len(batch)}\r\n\r\n"
                ).encode()
                + batch
            )
            await writer.drain()
            line = await reader.readline()
            assert b"200" in line
            headers = {}
            while True:
                ln = await reader.readline()
                if ln in (b"\r\n", b"\n", b""):
                    break
                k, _, v = ln.decode().partition(":")
                headers[k.strip().lower()] = v.strip()
            body = await reader.readexactly(int(headers["content-length"]))
            arr = json.loads(body)
            assert [o["id"] for o in arr] == [1, 2]
            assert arr[1]["result"]["sync_info"]["latest_block_height"] >= 1
            writer.close()
        finally:
            await node.stop()

    run(go())


def test_local_client_matches_http(tmp_path):
    """LocalClient (in-process, no network hop) serves the same route
    surface and answers as the HTTP client (reference:
    rpc/client/local/local.go)."""
    from tendermint_tpu.rpc import LocalClient, RPCClientError

    async def go():
        node, addr = await _boot(tmp_path)
        http = HTTPClient(addr)
        local = LocalClient.from_node(node)
        try:
            await node.consensus.wait_for_height(2, timeout=60.0)
            h_status = await http.call("status")
            l_status = await local.call("status")
            assert l_status["node_info"] == h_status["node_info"]
            assert l_status["validator_info"] == h_status["validator_info"]
            l_block = await local.call("block", height=1)
            h_block = await http.call("block", height=1)
            assert l_block["block_id"] == h_block["block_id"]
            assert await local.call("health") == {}
            with pytest.raises(RPCClientError, match="websocket"):
                await local.call("subscribe", query="tm.event='NewBlock'")
            with pytest.raises(RPCClientError, match="unknown method"):
                await local.call("nope")
        finally:
            await http.close()
            await node.stop()

    run(go())
