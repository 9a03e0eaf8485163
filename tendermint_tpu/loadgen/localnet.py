"""In-process multi-validator localnet with live RPC listeners.

The harness target: N validator Nodes over a MemoryNetwork (the e2e
runner's transport), each with a REAL TCP JSON-RPC listener on an
ephemeral 127.0.0.1 port — load flows over actual HTTP/websocket so the
per-route metrics recorded in rpc/jsonrpc.py measure the same code path
production traffic takes. The device verifier stays OFF
(`tpu.enable=false`): bench.py runs this harness in its jax-free CPU
block, and commits at this validator count never reach the batch
threshold anyway. No served benchmark has had the device on yet
(ROADMAP S3/D7); chip_smoke.py's node phase is the one place a served
node runs with it.
"""

from __future__ import annotations

import asyncio
import os
import time
from dataclasses import dataclass
from typing import List, Optional

from ..config import Config
from ..crypto.ed25519 import PrivKeyEd25519
from ..node import NodeKey, make_node
from ..p2p.transport import MemoryNetwork, MemoryTransport
from ..privval import FilePV
from ..types.genesis import GenesisDoc, GenesisValidator

__all__ = ["Localnet", "start_localnet"]


@dataclass
class Localnet:
    nodes: List[object]
    chain_id: str
    cfgs: List[Config]
    net: MemoryNetwork

    @property
    def rpc_addrs(self) -> List[str]:
        return [
            f"127.0.0.1:{n.rpc_server.bound_port}" for n in self.nodes
        ]

    def monikers(self) -> List[str]:
        """The nodes' net-fault-plane labels — what TM_TPU_PARTITION
        members name (loadgen nodes are load0, load1, ...)."""
        return [c.base.moniker for c in self.cfgs]

    async def wait_for_height(self, height: int, timeout: float = 60.0):
        await asyncio.gather(
            *(
                n.consensus.wait_for_height(height, timeout=timeout)
                for n in self.nodes
            )
        )

    async def restart(self, i: int, start_timeout: float = 60.0):
        """Crash-restart node i in place: tear the old instance down,
        boot a fresh Node from the same home + a fresh memory
        transport. With the default memdb backend the reborn node has
        EMPTY stores (crash with disk loss — it must blocksync-catch-up
        from its peers); with db_backend="sqlite" its stores survive
        like a real SIGKILL'd process. Returns the new node once
        started (NOT once caught up — that is the scenario's recovery
        measurement)."""
        cfg = self.cfgs[i]
        try:
            await self.nodes[i].stop()
        except Exception:
            pass  # a crashed node crashes; the restart is the point
        node = make_node(
            cfg,
            transport=MemoryTransport(self.net, cfg.p2p.laddr),
        )
        await asyncio.wait_for(node.start(), timeout=start_timeout)
        # tmlive: bounded= in-place replacement of slot i — the list
        # stays exactly n_nodes long for the Localnet's lifetime
        self.nodes[i] = node
        return node

    async def stop(self) -> None:
        for n in self.nodes:
            await n.stop()


async def start_localnet(
    n_nodes: int,
    home: str,
    chain_id: str = "loadnet",
    seed: int = 2026,
    timeout_commit: float = 0.2,
    trace_spans: bool = False,
    slo_exemplars: bool = False,
    profiler: bool = False,
    genesis_time_ns: Optional[int] = None,
    db_backend: str = "memdb",
    ping_interval: float = 30.0,
    pong_timeout: float = 15.0,
) -> Localnet:
    """Boot an N-validator in-process net and wait for height 1 on
    every node (traffic against a chain that hasn't committed yet
    measures boot, not serving)."""
    if n_nodes < 1:
        raise ValueError(f"n_nodes must be >= 1: {n_nodes}")
    privs = [
        PrivKeyEd25519.from_seed(
            seed.to_bytes(8, "big") + bytes([i]) * 24
        )
        for i in range(n_nodes)
    ]
    genesis = GenesisDoc(
        chain_id=chain_id,
        genesis_time_ns=(
            genesis_time_ns
            if genesis_time_ns is not None
            else time.time_ns()
        ),
        validators=[
            GenesisValidator(pub_key=p.pub_key(), power=10)
            for p in privs
        ],
    )
    net = MemoryNetwork()
    cfgs = []
    for i, priv in enumerate(privs):
        cfg = Config()
        cfg.base.home = os.path.join(home, f"load{i}")
        cfg.base.chain_id = chain_id
        # the moniker is the node's net-fault-plane label: what
        # TM_TPU_PARTITION members and p2p rule src=/dst= filters name
        cfg.base.moniker = f"load{i}"
        cfg.base.db_backend = db_backend
        cfg.tpu.enable = False  # the jax-free guarantee (module doc)
        cfg.consensus.timeout_propose = 2.0
        cfg.consensus.timeout_prevote = 1.0
        cfg.consensus.timeout_precommit = 1.0
        cfg.consensus.timeout_commit = timeout_commit
        cfg.consensus.peer_gossip_sleep_duration = 0.01
        cfg.rpc.laddr = "tcp://127.0.0.1:0"
        cfg.p2p.laddr = f"load{i}:26656"
        # snappy self-healing for an in-process net: boot dials race
        # node startup and chaos scenarios measure recovery in seconds
        # — a 20 s persistent-peer backoff cap would dominate both
        cfg.p2p.min_retry_time = 0.1
        cfg.p2p.max_retry_time_persistent = 2.0
        cfg.p2p.ping_interval = ping_interval
        cfg.p2p.pong_timeout = pong_timeout
        cfg.instrumentation.trace_spans = trace_spans
        cfg.instrumentation.slo_exemplars = slo_exemplars
        # the sampler is process-wide; the first node to start owns it
        # and stop-and-joins it at teardown (node/node.py _teardown)
        cfg.instrumentation.profiler = profiler and i == 0
        cfg.ensure_dirs()
        genesis.save_as(cfg.base.path(cfg.base.genesis_file))
        FilePV.from_priv_key(
            priv,
            cfg.base.path(cfg.priv_validator.key_file),
            cfg.base.path(cfg.priv_validator.state_file),
        ).save()
        cfgs.append(cfg)
    node_ids = [
        NodeKey.load_or_generate(
            c.base.path(c.base.node_key_file)
        ).node_id
        for c in cfgs
    ]
    for i, cfg in enumerate(cfgs):
        cfg.p2p.persistent_peers = ",".join(
            f"{node_ids[j]}@load{j}:26656"
            for j in range(n_nodes)
            if j != i
        )
    nodes = [
        make_node(
            c, transport=MemoryTransport(net, f"load{i}:26656")
        )
        for i, c in enumerate(cfgs)
    ]
    started = []
    try:
        for n in nodes:
            await n.start()
            started.append(n)
        ln = Localnet(
            nodes=nodes, chain_id=chain_id, cfgs=cfgs, net=net
        )
        # consensus height 2 = block 1 committed and stored everywhere
        # (height 1 is where consensus STARTS — waiting for it returns
        # immediately and load would then measure boot, not serving)
        await ln.wait_for_height(2, timeout=60.0)
        return ln
    except BaseException:
        for n in started:
            try:
                await n.stop()
            except Exception:
                pass
        raise
