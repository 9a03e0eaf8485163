"""gRPC ABCI transport tests (reference: abci/client/grpc_client.go,
abci/server/grpc_server.go — the third client/server variant)."""

import asyncio
import time

import pytest

grpc = pytest.importorskip("grpc")

from tendermint_tpu.abci import types as T  # noqa: E402
from tendermint_tpu.abci.grpc_transport import (  # noqa: E402
    GRPCClient,
    GRPCServer,
)
from tendermint_tpu.abci.kvstore import KVStoreApplication  # noqa: E402


def run(coro):
    return asyncio.run(coro)


def test_grpc_roundtrip_all_methods():
    """Every ABCI method over the wire against the kvstore app."""

    async def go():
        app = KVStoreApplication()
        srv = GRPCServer("127.0.0.1:0", app)
        await srv.start()
        client = GRPCClient(f"127.0.0.1:{srv.bound_port}")
        await client.start()
        try:
            assert (await client.echo("ping")).message == "ping"
            await client.flush()
            info = await client.info(T.RequestInfo())
            assert info.last_block_height == 0

            ct = await client.check_tx(T.RequestCheckTx(tx=b"k=v"))
            assert ct.is_ok
            await client.begin_block(T.RequestBeginBlock())
            dt = await client.deliver_tx(T.RequestDeliverTx(tx=b"k=v"))
            assert dt.is_ok
            await client.end_block(T.RequestEndBlock(height=1))
            commit = await client.commit()
            assert commit.data  # app hash

            q = await client.query(
                T.RequestQuery(path="/store", data=b"k")
            )
            assert q.value == b"v"

            snap = app.take_snapshot()
            snaps = await client.list_snapshots(T.RequestListSnapshots())
            assert any(s.height == snap.height for s in snaps.snapshots)
        finally:
            await client.stop()
            await srv.stop()

    run(go())


def test_node_runs_against_grpc_app(tmp_path):
    """A make_node validator with abci=grpc drives an out-of-process
    (separate event-loop-task) kvstore through the gRPC proxy and
    produces blocks."""
    from tendermint_tpu.config import Config
    from tendermint_tpu.crypto.ed25519 import PrivKeyEd25519
    from tendermint_tpu.node import make_node
    from tendermint_tpu.privval import FilePV
    from tendermint_tpu.types.genesis import GenesisDoc, GenesisValidator

    async def go():
        app_srv = GRPCServer("127.0.0.1:0", KVStoreApplication())
        await app_srv.start()

        priv = PrivKeyEd25519.from_seed(b"\x61" * 32)
        genesis = GenesisDoc(
            chain_id="grpc-chain",
            genesis_time_ns=time.time_ns(),
            validators=[
                GenesisValidator(pub_key=priv.pub_key(), power=10)
            ],
        )
        cfg = Config()
        cfg.base.home = str(tmp_path / "node")
        cfg.base.chain_id = "grpc-chain"
        cfg.base.db_backend = "memdb"
        cfg.base.abci = "grpc"
        cfg.base.proxy_app = f"127.0.0.1:{app_srv.bound_port}"
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
        node = make_node(cfg, genesis=genesis)
        await node.start()
        try:
            await node.consensus.wait_for_height(3, timeout=60.0)
            assert node.block_store.height() >= 2
        finally:
            await node.stop()
            await app_srv.stop()

    run(go())


def test_grpc_app_exception_maps_to_client_error():
    """An app that raises comes back as ABCIClientError with the
    ResponseException contract, matching the socket transport."""
    from tendermint_tpu.abci.client import ABCIClientError

    class Exploding(KVStoreApplication):
        def deliver_tx(self, req):
            raise RuntimeError("boom")

    async def go():
        srv = GRPCServer("127.0.0.1:0", Exploding())
        await srv.start()
        client = GRPCClient(f"127.0.0.1:{srv.bound_port}")
        await client.start()
        try:
            with pytest.raises(ABCIClientError, match="boom"):
                await client.deliver_tx(T.RequestDeliverTx(tx=b"x"))
            # transport survives the app exception
            assert (await client.echo("still-up")).message == "still-up"
        finally:
            await client.stop()
            await srv.stop()

    run(go())
