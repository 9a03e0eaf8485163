"""Remote signer tests (reference model: privval/signer_client_test.go,
signer_listener_endpoint_test.go): endpoint pairing over a real TCP
socket with SecretConnection, double-sign refusal through the wire,
reconnect behavior, and a full node producing blocks with its key held
by an external signer process."""

import asyncio
import time

import pytest

from tendermint_tpu.crypto.ed25519 import PrivKeyEd25519
from tendermint_tpu.privval import (
    FilePV,
    RemoteSignerError,
    RetrySignerClient,
    SignerListenerEndpoint,
    SignerServer,
)
from tendermint_tpu.types.block_id import BlockID, PartSetHeader
from tendermint_tpu.types.canonical import PRECOMMIT_TYPE, PREVOTE_TYPE
from tendermint_tpu.types.proposal import Proposal
from tendermint_tpu.types.vote import Vote

CHAIN = "signer-chain"


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


async def _pair(tmp_path, seed=b"\x41"):
    """Listener (node side) + signer server connected over loopback."""
    pv = _file_pv(tmp_path, seed)
    node_key = PrivKeyEd25519.from_seed(b"\x51" * 32)
    listener = SignerListenerEndpoint(
        "tcp://127.0.0.1:0", node_key, accept_timeout=10.0
    )
    await listener.start()
    signer = SignerServer(
        f"127.0.0.1:{listener.bound_port}", pv, redial_delay=0.1
    )
    await signer.start()
    return pv, listener, signer


def test_pubkey_vote_proposal_roundtrip(tmp_path):
    async def go():
        pv, listener, signer = await _pair(tmp_path)
        try:
            client = RetrySignerClient(listener, retries=10, delay=0.2)
            pk = await client.get_pub_key()
            assert pk.bytes() == pv.key.pub_key.bytes()

            vote = Vote(
                type=PREVOTE_TYPE,
                height=5,
                round=0,
                block_id=_block_id(),
                timestamp_ns=time.time_ns(),
                validator_address=pv.key.address,
                validator_index=0,
            )
            await client.sign_vote(CHAIN, vote)
            assert pk.verify_signature(vote.sign_bytes(CHAIN), vote.signature)

            prop = Proposal(
                height=6,
                round=0,
                pol_round=-1,
                block_id=_block_id(b"\xcc"),
                timestamp_ns=time.time_ns(),
            )
            await client.sign_proposal(CHAIN, prop)
            assert pk.verify_signature(
                prop.sign_bytes(CHAIN), prop.signature
            )
        finally:
            await signer.stop()
            await listener.stop()

    run(go())


def test_double_sign_refused_over_the_wire(tmp_path):
    """The signer's FilePV last-sign state must protect against
    conflicting votes exactly as a local key would
    (reference: privval/file.go:109 + signer request handler)."""

    async def go():
        pv, listener, signer = await _pair(tmp_path, seed=b"\x42")
        try:
            client = RetrySignerClient(listener, retries=10, delay=0.2)
            ts = time.time_ns()
            vote1 = Vote(
                type=PRECOMMIT_TYPE,
                height=9,
                round=0,
                block_id=_block_id(b"\x01"),
                timestamp_ns=ts,
                validator_address=pv.key.address,
                validator_index=0,
            )
            await client.sign_vote(CHAIN, vote1)
            # conflicting block at the same HRS: must be refused, and
            # the refusal must NOT be retried into success
            vote2 = Vote(
                type=PRECOMMIT_TYPE,
                height=9,
                round=0,
                block_id=_block_id(b"\x02"),
                timestamp_ns=ts,
                validator_address=pv.key.address,
                validator_index=0,
            )
            with pytest.raises(RemoteSignerError):
                await client.sign_vote(CHAIN, vote2)
            # same HRS and same block: signature is replayed, not re-signed
            vote3 = Vote(
                type=PRECOMMIT_TYPE,
                height=9,
                round=0,
                block_id=_block_id(b"\x01"),
                timestamp_ns=ts,
                validator_address=pv.key.address,
                validator_index=0,
            )
            await client.sign_vote(CHAIN, vote3)
            assert vote3.signature == vote1.signature
        finally:
            await signer.stop()
            await listener.stop()

    run(go())


def test_signer_reconnects_after_drop(tmp_path):
    async def go():
        pv, listener, signer = await _pair(tmp_path, seed=b"\x43")
        try:
            client = RetrySignerClient(listener, retries=20, delay=0.1)
            await client.get_pub_key()
            # kill the live connection; the signer's dial loop re-dials
            listener._conn.close()
            listener._conn = None
            listener._conn_ready.clear()
            pk = await client.get_pub_key()
            assert pk.bytes() == pv.key.pub_key.bytes()
        finally:
            await signer.stop()
            await listener.stop()

    run(go())


def test_node_with_remote_signer_produces_blocks(tmp_path):
    """A validator node whose privval is the remote-signer client, with
    the key living in an external SignerServer, reaches consensus
    (reference: the e2e harness's privval=tcp mode)."""
    from tendermint_tpu.config import Config
    from tendermint_tpu.node import make_node
    from tendermint_tpu.types.genesis import GenesisDoc, GenesisValidator

    async def go():
        priv = PrivKeyEd25519.from_seed(b"\x44" * 32)
        genesis = GenesisDoc(
            chain_id="rs-chain",
            genesis_time_ns=time.time_ns(),
            validators=[GenesisValidator(pub_key=priv.pub_key(), power=10)],
        )
        cfg = Config()
        cfg.base.home = str(tmp_path / "node")
        cfg.base.chain_id = "rs-chain"
        cfg.base.db_backend = "memdb"
        cfg.consensus.timeout_commit = 0.2
        cfg.rpc.laddr = "tcp://127.0.0.1:0"
        cfg.p2p.laddr = "tcp://127.0.0.1:0"  # a free port, not 26656
        cfg.priv_validator.listen_addr = "tcp://127.0.0.1:0"
        cfg.ensure_dirs()
        genesis.save_as(cfg.base.path(cfg.base.genesis_file))

        node = make_node(cfg)
        # external signer process holds the actual key
        signer_pv = FilePV.from_priv_key(
            priv,
            str(tmp_path / "signer_key.json"),
            str(tmp_path / "signer_state.json"),
        )
        # start the node; consensus blocks on get_pub_key until the
        # signer dials in
        start_task = asyncio.ensure_future(node.start())
        await asyncio.sleep(0.3)  # listener is up early in boot
        signer = SignerServer(
            f"127.0.0.1:{node.privval_listener.bound_port}",
            signer_pv,
            redial_delay=0.1,
        )
        await signer.start()
        await start_task
        try:
            await node.consensus.wait_for_height(3, timeout=60.0)
            assert node.block_store.height() >= 2
        finally:
            await node.stop()
            await signer.stop()

    run(go())


def test_signer_refuses_foreign_chain_id(tmp_path):
    """A chain-id-pinned SignerServer refuses sign requests for any
    other chain (reference: signer_requestHandler.go
    DefaultValidationRequestHandler chainID check) — a misconfigured
    node cannot pull signatures for a different network or advance the
    signer's last-sign state with foreign votes."""

    async def go():
        pv = _file_pv(tmp_path, b"\x47")
        node_key = PrivKeyEd25519.from_seed(b"\x52" * 32)
        listener = SignerListenerEndpoint(
            "tcp://127.0.0.1:0", node_key, accept_timeout=10.0
        )
        await listener.start()
        signer = SignerServer(
            f"127.0.0.1:{listener.bound_port}",
            pv,
            redial_delay=0.1,
            chain_id=CHAIN,
        )
        await signer.start()
        try:
            client = RetrySignerClient(listener, retries=10, delay=0.2)

            def vote():
                return Vote(
                    type=PREVOTE_TYPE,
                    height=5,
                    round=0,
                    block_id=_block_id(),
                    timestamp_ns=time.time_ns(),
                    validator_address=pv.key.address,
                    validator_index=0,
                )

            v = vote()
            with pytest.raises(Exception, match="serves"):
                await client.sign_vote("other-chain", v)
            assert v.signature is None or v.signature == b""
            # the pinned chain still signs, and the refusal didn't
            # burn the last-sign HRS state
            v2 = vote()
            await client.sign_vote(CHAIN, v2)
            pk = await client.get_pub_key()
            assert pk.verify_signature(v2.sign_bytes(CHAIN), v2.signature)
        finally:
            await signer.stop()
            await listener.stop()

    run(go())
