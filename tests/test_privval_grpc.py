"""gRPC remote signer tests (reference model:
privval/grpc/{client_test.go,server_test.go}): pubkey/vote/proposal
round-trips over a real gRPC channel, double-sign refusal as a
non-retryable error, transport failure as a retryable one, and a full
node signing through a gRPC signer (`grpc://` listen address,
reference: node/setup.go:586)."""

import asyncio
import time

import pytest

from tendermint_tpu.crypto.ed25519 import PrivKeyEd25519
from tendermint_tpu.privval import FilePV
from tendermint_tpu.privval.grpc import GRPCSignerClient, GRPCSignerServer
from tendermint_tpu.privval.signer import (
    RemoteSignerConnectionError,
    RemoteSignerError,
)
from tendermint_tpu.types.block_id import BlockID, PartSetHeader
from tendermint_tpu.types.canonical import PRECOMMIT_TYPE, PREVOTE_TYPE
from tendermint_tpu.types.proposal import Proposal
from tendermint_tpu.types.vote import Vote

CHAIN = "grpc-signer-chain"


def run(coro):
    return asyncio.run(coro)


def _file_pv(tmp_path, seed=b"\x41"):
    return FilePV.from_priv_key(
        PrivKeyEd25519.from_seed(seed * 32),
        str(tmp_path / "pv_key.json"),
        str(tmp_path / "pv_state.json"),
    )


def _block_id(tag: bytes = b"\xaa") -> BlockID:
    return BlockID(
        hash=tag * 32,
        part_set_header=PartSetHeader(total=1, hash=b"\xbb" * 32),
    )


async def _pair(tmp_path):
    pv = _file_pv(tmp_path)
    server = GRPCSignerServer("127.0.0.1:0", CHAIN, pv)
    await server.start()
    client = GRPCSignerClient(f"grpc://127.0.0.1:{server.bound_port}")
    await client.start()
    return pv, server, client


def test_pubkey_vote_proposal_roundtrip(tmp_path):
    async def go():
        pv, server, client = await _pair(tmp_path)
        try:
            pk = await client.get_pub_key()
            assert pk.bytes() == (await pv.get_pub_key()).bytes()

            vote = Vote(
                type=PREVOTE_TYPE,
                height=3,
                round=0,
                block_id=_block_id(),
                timestamp_ns=time.time_ns(),
                validator_address=pk.address(),
                validator_index=0,
            )
            await client.sign_vote(CHAIN, vote)
            assert vote.signature
            assert pk.verify_signature(
                vote.sign_bytes(CHAIN), vote.signature
            )

            prop = Proposal(
                height=4,
                round=0,
                pol_round=-1,
                block_id=_block_id(b"\xcc"),
                timestamp_ns=time.time_ns(),
            )
            await client.sign_proposal(CHAIN, prop)
            assert prop.signature
            assert pk.verify_signature(
                prop.sign_bytes(CHAIN), prop.signature
            )
        finally:
            await client.stop()
            await server.stop()

    run(go())


def test_double_sign_refused_not_retryable(tmp_path):
    async def go():
        pv, server, client = await _pair(tmp_path)
        try:
            pk = await client.get_pub_key()
            v1 = Vote(
                type=PRECOMMIT_TYPE,
                height=7,
                round=0,
                block_id=_block_id(b"\xaa"),
                timestamp_ns=time.time_ns(),
                validator_address=pk.address(),
                validator_index=0,
            )
            await client.sign_vote(CHAIN, v1)
            # same HRS, DIFFERENT block: the signer's FilePV refuses
            v2 = Vote(
                type=PRECOMMIT_TYPE,
                height=7,
                round=0,
                block_id=_block_id(b"\xdd"),
                timestamp_ns=time.time_ns(),
                validator_address=pk.address(),
                validator_index=0,
            )
            with pytest.raises(RemoteSignerError) as ei:
                await client.sign_vote(CHAIN, v2)
            # a refusal must NOT look like a retryable transport error
            assert not isinstance(ei.value, RemoteSignerConnectionError)
        finally:
            await client.stop()
            await server.stop()

    run(go())


def test_transport_failure_is_retryable_shaped(tmp_path):
    async def go():
        pv, server, client = await _pair(tmp_path)
        await server.stop()  # signer goes away
        try:
            client.timeout = 0.5
            with pytest.raises(RemoteSignerConnectionError):
                await client.get_pub_key()
        finally:
            await client.stop()

    run(go())


def test_node_with_grpc_signer_produces_blocks(tmp_path):
    """Full node whose key lives in an external gRPC signer process
    (in-process here): grpc:// listen address selects the client."""
    from tendermint_tpu.node.node import make_node

    from tests.test_node import make_genesis, make_home

    async def go():
        priv = PrivKeyEd25519.from_seed(b"\x61" * 32)
        genesis = make_genesis([priv])
        cfg = make_home(tmp_path, 0, genesis, None)
        cfg.p2p.laddr = "tcp://127.0.0.1:0"  # a free port, not 26656
        cfg.base.mode = "validator"

        pv = FilePV.from_priv_key(
            priv,
            str(tmp_path / "signer_key.json"),
            str(tmp_path / "signer_state.json"),
        )
        server = GRPCSignerServer("127.0.0.1:0", genesis.chain_id, pv)
        await server.start()
        cfg.priv_validator.listen_addr = (
            f"grpc://127.0.0.1:{server.bound_port}"
        )
        node = make_node(cfg)
        from tendermint_tpu.privval.signer import RetrySignerClient

        assert isinstance(node.privval, RetrySignerClient)
        assert isinstance(node.privval.inner, GRPCSignerClient)
        await node.start()
        try:
            await node.consensus.wait_for_height(3, timeout=60.0)
            assert node.block_store.height() >= 2
        finally:
            await node.stop()
            await server.stop()

    run(go())
