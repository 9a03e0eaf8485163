"""Entry `node.make_node` with block sync on: a full node catching up a
chain from its peers, block by block, as an operator's node does after
it was down or when it is new.

The node is the program's own, built from a `Config` as `cmd start`
builds it: `[tpu] enable = true` at the default section, the builtin
kvstore over ABCI, in-memory stores, `blocksync.enable = true`, no
validator key. Nothing of it is made by hand and nothing of it is
patched. What the driver brings is the other side: `peers` stand-ins on
a `p2p.transport.MemoryNetwork`, each a router with the blocksync
channel that answers a StatusRequest with the chain's height and a
BlockRequest with the generator's bytes (node_gen.py), holding the
answer while the height lies above what the running request has
released. The node decodes every block from those bytes on its own
receive path.

A request releases the next `blocks_per_request` heights and ends when
the node has executed them (its `NewBlock` event for the last of them);
the first request of a lap builds a fresh node from genesis inside the
request, as a restart does. One event loop, run until a request
completes. The outcome is the verdict in the reference's vocabulary
(reference/node_replay.py), read from the node's block store, its app
(ABCI Info) and its reactor's record of what it refused.
"""

from __future__ import annotations

import asyncio
import atexit
import re
import shutil
import tempfile

from chipbench import node_gen
from chipbench.reference import node_replay as N

_WRONG_SIG = re.compile(r"wrong signature \(#(\d+)\)")
NODE_ADDR = "fullnode:26656"


def setup(config: dict, traffic: dict, seed: int) -> "BlocksyncNodeDriver":
    return BlocksyncNodeDriver(config, traffic, seed)


class _Net:
    """One chain and the peers that hold it: what is released, what is
    held back, what was served to whom, and the one corrupted block the
    running lap has still to serve."""

    def __init__(self, chain, laps, fabric, peers: list, send, corrupted: bool = True) -> None:
        self.chain = chain
        self.laps = laps
        self.tip = len(chain.blocks)
        self.fabric = fabric  # the MemoryNetwork the node joins
        self.peers = peers
        self.names = {p.node_id: f"peer{i}" for i, p in enumerate(peers)}
        self.send = send  # (peer, to, message) -> None
        self.bad = chain.corrupted_response(laps.bad_height, laps.bad_index) if corrupted else None
        self.new_lap()

    def new_lap(self) -> None:
        self.released = 0
        # (asker, height) -> the peer that holds the answer: the last
        # one asked (a requester whose peer was banned, or that timed
        # out over the first touch's compile, has given the earlier one
        # up), so a height is answered once
        self.held: dict = {}
        self.bad_unserved = self.bad is not None
        self.log: list = []  # (peer's name, response bytes) of the running request

    def asked(self, peer, asker: str, height: int) -> None:
        if height > self.released:
            self.held.pop((asker, height), None)
            self.held[asker, height] = peer
        else:
            self.serve(peer, asker, height)

    def release(self, height: int) -> None:
        """Answer what was held up to `height`, lowest first. A peer the
        asker has hung up on since (no traffic through a compile of a
        minute: its keepalive gave up) answers nothing: the node asks
        again when it is back."""
        self.released = height
        for (asker, h), peer in sorted(self.held.items(), key=lambda held: held[0][1]):
            if h <= height:
                del self.held[asker, h]
                if asker in peer.router.peer_ids():
                    self.serve(peer, asker, h)

    def serve(self, peer, asker: str, height: int) -> None:
        """The first response to leave for the lap's bad height is the
        corrupted one; every later one is clean."""
        if height > self.tip:
            return
        response = self.chain.response(height)
        if height == self.laps.bad_height and self.bad_unserved:
            response, self.bad_unserved = self.bad, False
        self.log.append((self.names[peer.node_id], response))
        self.send(peer, asker, response)


class BlocksyncNodeDriver:
    def __init__(self, config: dict, traffic: dict, seed: int) -> None:
        # a program without the reactor's counters and its record of
        # refusals (a parent commit) cannot run this driver: the
        # harness reads the ImportError as a refusal, exit 2, at once
        from tendermint_tpu.abci.types import RequestInfo
        from tendermint_tpu.blocksync.metrics import BlocksyncMetrics  # noqa: F401
        from tendermint_tpu.blocksync.msgs import (
            BlockRequestMessage,
            BlocksyncCodec,
            StatusRequestMessage,
            StatusResponseMessage,
        )
        from tendermint_tpu.blocksync.reactor import BLOCKSYNC_CHANNEL
        from tendermint_tpu.config import MODE_FULL, Config
        from tendermint_tpu.crypto.keys import pubkey_from_type_and_bytes
        from tendermint_tpu.libs import rng
        from tendermint_tpu.node import make_node
        from tendermint_tpu.p2p.p2ptest import TestNode
        from tendermint_tpu.p2p.transport import MemoryNetwork, MemoryTransport
        from tendermint_tpu.p2p.types import ChannelDescriptor, Envelope
        from tendermint_tpu.pubsub.query import query_for_event
        from tendermint_tpu.types import events as E
        from tendermint_tpu.types.genesis import GenesisDoc, GenesisValidator

        if config["node"] != "full" or config["abci"] != "builtin":
            raise RuntimeError("this driver runs a full node over the builtin kvstore")
        self.per = traffic["blocks_per_request"]
        self.timeout_s = traffic["request_timeout_s"]
        self.warmup_timeout_s = traffic["warmup_timeout_s"]
        self.d = d = node_gen.Deployment(config, seed)
        unreleased = traffic["unreleased_tip_blocks"]
        self.lap_requests = (traffic["chain_blocks"] - 1) // self.per
        warm_requests = (traffic["warmup_blocks"] - 1) // self.per
        if traffic["corrupt_every"] != self.lap_requests or warm_requests != 2:
            raise RuntimeError("one corrupted request a lap, and a warm-up of two requests")
        window = node_gen.Chain(d, config, seed, "window", traffic["chain_blocks"] + unreleased)
        warm = node_gen.Chain(d, config, seed, "warm-up", traffic["warmup_blocks"] + unreleased)
        first_touch = node_gen.Chain(d, config, seed, "first-touch", self.per + 1 + unreleased)
        self.sign_bytes_len = window.sign_bytes_len
        self._reference = d.reference()
        self._expected: dict = {}  # request token -> the reference's verdict
        self._served: dict = {}  # request token -> what the peers served during it
        self._replays: dict = {}  # chain's tag -> the reference's replay of the running lap
        self.decode_s: list = []  # seconds of each request's decodes, warm-up included
        self._control = False
        rng.reseed(seed)  # the pool's peer picks

        self._genesis = GenesisDoc(
            chain_id=d.chain_id,
            genesis_time_ns=d.genesis_time_ns,
            initial_height=config["initial_height"],
            validators=[
                GenesisValidator(
                    pub_key=pubkey_from_type_and_bytes(v["kind"], v["pub"]), power=v["power"]
                )
                for v in d.validators
            ],
        )
        order = [v.pub_key.bytes() for v in self._genesis.validator_set().validators]
        if order != [v["pub"] for v in d.validators]:
            raise RuntimeError("the program orders the validator set otherwise")
        self._home = tempfile.mkdtemp(prefix="chipbench-node-")
        self.loop = asyncio.new_event_loop()
        self._node = self._sub = None
        self._new_block = query_for_event(E.EventValue.NEW_BLOCK)
        self._info_request = RequestInfo(version="chipbench")
        self._height_key = E.BLOCK_HEIGHT_KEY

        class RawCodec:
            """The program's codec; a response already on the wire goes
            out as it is."""

            decode = staticmethod(BlocksyncCodec.decode)

            @staticmethod
            def encode(msg) -> bytes:
                return msg if isinstance(msg, bytes) else BlocksyncCodec.encode(msg)

        descriptor = ChannelDescriptor(
            channel_id=BLOCKSYNC_CHANNEL, message_type=RawCodec, priority=5,
            send_queue_capacity=1000, recv_buffer_capacity=1024, name="blocksync",
        )  # fmt: skip

        def send(peer, to: str, message) -> None:
            peer.channel.try_send(Envelope(message=message, to=to))

        async def serve(net: _Net, peer) -> None:
            async for envelope in peer.channel:
                msg = envelope.message
                if isinstance(msg, StatusRequestMessage):
                    send(peer, envelope.from_peer, StatusResponseMessage(height=net.tip, base=1))
                elif isinstance(msg, BlockRequestMessage):
                    net.asked(peer, envelope.from_peer, msg.height)

        async def start_net(chain, laps, corrupted: bool = True) -> _Net:
            fabric = MemoryNetwork()
            peers = [TestNode(fabric, i, d.chain_id) for i in range(config["peers"])]
            net = _Net(chain, laps, fabric, peers, send, corrupted)
            for peer in peers:
                peer.channel = peer.open_channel(descriptor)
                await peer.router.start()
                peer.serving = self.loop.create_task(serve(net, peer))
            return net

        def make(net: _Net, moniker: str):
            cfg = Config()
            cfg.base.home = self._home
            cfg.base.chain_id = d.chain_id
            cfg.base.moniker = moniker
            cfg.base.mode = MODE_FULL
            cfg.base.db_backend = config["db_backend"]
            cfg.base.abci = config["abci"]
            cfg.blocksync.enable = True
            cfg.rpc.laddr = ""
            cfg.p2p.laddr = NODE_ADDR
            cfg.p2p.pex = False
            cfg.p2p.persistent_peers = ",".join(f"{p.node_id}@{p.addr}" for p in net.peers)
            if not cfg.tpu.enable:
                raise RuntimeError("the default [tpu] section has enable = false")
            cfg.ensure_dirs()
            return make_node(
                cfg, genesis=self._genesis, transport=MemoryTransport(net.fabric, NODE_ADDR)
            )

        self._make = make
        laps = node_gen.Laps(traffic, seed, "window", self.lap_requests, d.checked)
        warm_laps = node_gen.Laps(traffic, seed, "warm-up", warm_requests, d.checked, position=1)
        self._window_net = self.loop.run_until_complete(start_net(window, laps))
        self._warm_net = self.loop.run_until_complete(start_net(warm, warm_laps))
        one_clean = node_gen.Laps(traffic, seed, "first-touch", 1, d.checked, position=0)
        self._first_touch_net = self.loop.run_until_complete(
            start_net(first_touch, one_clean, corrupted=False)
        )
        atexit.register(self.close)  # a run's process ends with the node up

    # -- a request ----------------------------------------------------

    def _place(self, token: tuple) -> tuple:
        """(net, position in its lap) of a request: the window's count
        from 0, the warm-up's are the negative ones before them, and the
        first touch is the one before those."""
        ordinal = token[0]
        if ordinal >= 0:
            return self._window_net, ordinal % self.lap_requests
        warm = self._warm_net.laps.requests
        if ordinal < -warm:
            return self._first_touch_net, 0
        return self._warm_net, ordinal + warm

    def warmup_requests(self) -> list:
        """The first touch, then a clean request and a corrupted one
        through a node of its own over a chain the window never meets:
        every program, the CPU cross-examination of a bad lane, the
        refusal and the refetch have run before the window, and nothing
        of it is in the cache.

        The first touch is one clean request through a node and a chain
        of its own, and it is there to take the first dispatch, which
        compiles or loads the programs and blocks the node's event loop
        for most of a minute. Every open fetch times out over that
        (pool.REQUEST_TIMEOUT, 10 s) and is asked of a second peer, so
        heights arrive twice, from two peers, in an order the scheduler
        picks. A clean request reads the same whichever answer the pool
        took. A corrupted one does not (who is banned, and whether the
        bad block was taken at all), so it runs on a node that has never
        waited ten seconds for anything, as every node of the window."""
        n = self._warm_net.laps.requests
        return [(-n - 1, False)] + [(j - n, j == self._warm_net.laps.position) for j in range(n)]

    def window_request(self, i: int) -> tuple:
        return (i, i % self.lap_requests == self._window_net.laps.position)

    def run(self, token: tuple, annotate=None) -> str:
        """One request; the verdict as a string. The seconds the node
        spent decoding what it was sent are kept for `decode_host_ms`."""
        net, position = self._place(token)
        if self._control:
            return self._control_verdict(token, net, position)
        before = self._decoded()
        if annotate is None:
            verdict = self.loop.run_until_complete(self._request(token, net, position))
        else:
            with annotate("cb_entry"):
                verdict = self.loop.run_until_complete(self._request(token, net, position))
        # a lap's first request starts the node whose counter this is
        self.decode_s.append(self._decoded() - (0.0 if position == 0 else before))
        return verdict

    def _decoded(self) -> float:
        if self._node is None or self._node.blocksync_reactor is None:
            return 0.0
        return self._node.blocksync_reactor.metrics.decode_seconds.value()

    async def _restart(self, net: _Net) -> None:
        """What a restart does: the old node down, a fresh one up from
        genesis (InitChain, handshake, dialling its peers)."""
        if self._node is not None:
            await self._node.stop()
        net.new_lap()
        self._node = self._make(net, f"chipbench-{net.chain.tag}")
        self._sub = self._node.event_bus.subscribe("chipbench", self._new_block, limit=1024)
        await self._node.start()

    async def _request(self, token: tuple, net: _Net, position: int) -> str:
        if position == 0:
            await self._restart(net)
        net.log = self._served[token] = []
        refusals = len(self._node.blocksync_reactor.refusals)
        target = net.laps.target(position)
        net.release(net.laps.released(position))
        # a warm-up request compiles or loads every program it touches
        timeout = self.warmup_timeout_s if token[0] < 0 else self.timeout_s
        try:
            await asyncio.wait_for(self._applied(target), timeout)
        except asyncio.TimeoutError:
            return f"stalled:{self._node.block_store.height()}"
        return await self._verdict(net, refusals)

    async def _applied(self, target: int) -> None:
        while True:
            message = await self._sub.next()
            if int(message.events[self._height_key][0]) >= target:
                return

    async def _verdict(self, net: _Net, refusals_before: int) -> str:
        node = self._node
        height = node.block_store.height()
        meta = node.block_store.load_block_meta(height)
        info = await node.proxy.query.info(self._info_request)
        out = f"ok:{height}:{meta.block_id.hash.hex()}:{info.last_block_app_hash.hex()}"
        if info.last_block_height != height:
            out = f"invalid:{height}:app_at_{info.last_block_height}"
        for at, error, providers in list(node.blocksync_reactor.refusals)[refusals_before:]:
            m = _WRONG_SIG.search(error)
            banned = ",".join(sorted({net.names[p] for p in providers}))
            out += f";refused={at}#{m.group(1) if m else error};banned={banned}"
        base = node.block_store.base()
        return f"{out};stored={base}-{height}"

    # -- the control --------------------------------------------------

    def use_control(self) -> None:
        """Put the control in the program's place: the reference with
        the signature guarantee dropped, replaying what honest-but-one
        peers would have served. Nothing reaches the device;
        chipbench/control.py reads what the comparison makes of it."""
        self._control = True

    def _control_verdict(self, token: tuple, net: _Net, position: int) -> str:
        """The unchecked replay of one request from the state the
        checked one stands at, which then takes the request itself."""
        laps = net.laps
        first = 1 if position == 0 else laps.released(position - 1) + 1
        served = []
        for height in range(first, laps.released(position) + 1):
            bad = token[1] and height == laps.bad_height
            served.append(("peer0", net.bad if bad else net.chain.response(height)))
        if token[1]:  # refused at H: every block from H up comes again, clean
            served += [
                ("peer1", net.chain.response(h))
                for h in range(laps.refused_height, laps.released(position) + 1)
            ]
        self._served[token] = served
        self.decode_s.append(0.0)
        truth = self._replay_of(net, position)
        control = truth.fork(check_signatures=False)
        for peer, response in served:
            control.serve(peer, response)
        self._expected[token] = self._advance(truth, served)
        return control.verdict()

    # -- what the harness holds a request to --------------------------

    def _replay_of(self, net: _Net, position: int):
        if position == 0:
            self._replays[net.chain.tag] = N.Replay(self._reference)
        return self._replays[net.chain.tag]

    @staticmethod
    def _advance(replay, served: list) -> str:
        for peer, response in served:
            replay.serve(peer, response)
        return replay.verdict()

    def expected(self, tokens: list) -> list:
        """The reference's verdict of each request: its replay of what
        the peers served, lap by lap in the order of the requests."""
        for token in tokens:
            if token not in self._expected:
                net, position = self._place(token)
                replay = self._replay_of(net, position)
                self._expected[token] = self._advance(replay, self._served.pop(token))
        return [self._expected[t] for t in tokens]

    def _full_verifications(self, token: tuple) -> int:
        """Blocks of a request whose own LastCommit is verified in
        full: all but the chain's first, which carries none."""
        _net, position = self._place(token)
        return self.per - (position == 0)

    def sent(self, token: tuple, min_batch: int, chunk) -> tuple:
        """(device dispatches, signatures) one request must add to the
        program's counters. The light verification of a block sends the
        votes it checks; the full one of the same commit, a block
        later, finds those in the cache and sends the rest. A refused
        commit changes neither count: its dispatch stands for the
        block's, and the second try finds all but the wrong vote's in
        the cache and verifies that one on the CPU (one signature is
        under every min_batch)."""
        checked = self.d.checked
        rest = len(self.d.validators) - checked
        batches = sigs = 0
        for count, n in ((self.per, checked), (self._full_verifications(token), rest)):
            if n >= min_batch:
                batches += count * (-(-n // chunk) if chunk else 1)
                sigs += count * n
        return batches, sigs

    def hits(self, token: tuple) -> tuple:
        """(cache hits, memo hits): each full verification finds the
        light one's votes; the light one repeated after a refusal finds
        all but the one that was wrong. No commit is verified twice in
        one mode, so the memo never hits."""
        checked = self.d.checked
        return self._full_verifications(token) * checked + (checked - 1 if token[1] else 0), 0

    def work(self, token: tuple, work) -> dict:
        """int32 multiply-adds and bytes the signatures a clean request
        past a lap's first verifies on the device (chipbench/work.py)."""
        per = work.per_signature(self.d.validators[0]["kind"], self.sign_bytes_len)
        n = self.per * len(self.d.validators)
        return {"madds": n * per["madds"], "bytes": n * per["bytes"]}

    def close(self) -> None:
        """Stop what the driver started: the node, the peers, the loop."""
        if self.loop.is_closed():
            return

        async def stop() -> None:
            if self._node is not None:
                await self._node.stop()
            for net in (self._window_net, self._warm_net, self._first_touch_net):
                for peer in net.peers:
                    peer.serving.cancel()
                    await peer.router.stop()

        self.loop.run_until_complete(stop())
        self.loop.close()
        shutil.rmtree(self._home, ignore_errors=True)
