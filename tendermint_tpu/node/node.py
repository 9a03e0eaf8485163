"""Node assembly — compose every subsystem into a runnable node.

reference: node/node.go:116-412 (makeNode), node/setup.go (initDBs,
createPeerManager, createRouter, create*Reactor), node/public.go (New).

Wiring order mirrors the reference: DBs → stores → genesis → device
verifier install → proxy app → event bus + indexer → privval → ABCI
handshake → peer manager / router → mempool/evidence/consensus/
blocksync/statesync reactors → start. The TPU-backed BatchVerifier is
installed from config *before* any verification path runs, so the
served path (consensus LastCommit checks, blocksync VerifyCommitLight,
statesync light-block verification) all dispatch through the device
seam (reference plugin boundary: crypto/crypto.go:53-61).
"""

from __future__ import annotations

import asyncio
import os
from typing import Optional

from ..abci.client import local_creator, socket_creator
from ..abci.kvstore import KVStoreApplication
from ..abci.proxy import AppConns
from ..config import (
    MODE_SEED,
    MODE_VALIDATOR,
    Config,
)
from ..consensus import ConsensusState
from ..consensus.reactor import (
    ConsensusReactor,
    consensus_channel_descriptors,
)
from ..consensus.replay import Handshaker
from ..consensus.wal import WAL
from ..crypto import tpu_verifier
from ..eventbus import EventBus, EventBusMetrics
from ..consensus.metrics import ConsensusMetrics
from ..evidence import (
    EvidenceMetrics,
    EvidencePool,
    EvidenceReactor,
    evidence_channel_descriptor,
)
from ..libs.log import get_logger
from ..libs.metrics import Registry
from ..libs.service import Service
from ..mempool import TxMempool
from ..mempool.metrics import MempoolMetrics
from ..mempool.reactor import MempoolReactor, mempool_channel_descriptor
from ..p2p.metrics import P2PMetrics
from ..p2p.peermanager import PeerManager, PeerManagerOptions
from ..p2p.router import Router, RouterOptions
from ..p2p.transport import TCPTransport, Transport
from ..p2p.types import NodeInfo
from ..privval import FilePV
from ..state import StateStore, state_from_genesis
from ..state.execution import BlockExecutor
from ..state.indexer import IndexerService, KVSink, NullSink
from ..state.metrics import StateMetrics
from ..store.block_store import BlockStore
from ..store.kv import open_db
from ..types.genesis import GenesisDoc
from .key import NodeKey

__all__ = ["Node", "make_node"]


class Node(Service):
    """A full node (validator or not), assembled from a Config.

    reference: node/node.go nodeImpl. Construction (make_node) is
    synchronous and cheap; everything with I/O ordering constraints
    (proxy start, ABCI handshake, reactor startup, sync orchestration)
    happens in on_start.
    """

    def __init__(
        self,
        cfg: Config,
        genesis: GenesisDoc,
        app=None,
        transport: Optional[Transport] = None,
    ) -> None:
        super().__init__(name="node", logger=get_logger("node"))
        self.cfg = cfg
        self.genesis = genesis
        genesis.validate_and_complete()

        # -- per-node metrics registry (reference: each subsystem's
        # go-kit Metrics struct threaded from node/setup.go). Every node
        # gets its own registry so in-process localnet embeddings scrape
        # disjoint series; process-global instruments (the device
        # verifier's tpu_* family) stay on DEFAULT_REGISTRY and are
        # merged into the scrape without duplication.
        self.metrics_registry = Registry()

        # span tracing is process-wide (one ring); any node asking for
        # it turns it on
        if cfg.instrumentation.trace_spans:
            from ..libs import trace

            trace.enable(capacity=cfg.instrumentation.trace_ring_capacity)
        # ditto the slow-request exemplar ring (SLO-breach span trees,
        # surfaced in the debug bundle; see docs/load.md)
        if cfg.instrumentation.slo_exemplars:
            from ..libs import trace

            trace.enable_exemplars(
                capacity=cfg.instrumentation.slo_exemplar_capacity
            )
        # wall-clock sampling profiler (libs/profiler.py): process-wide
        # like the trace ring. Arming labels alone is near-free and
        # lets a profile started later (RPC `profile` route) attribute
        # loop samples to the pumps spawned now; actually *sampling*
        # starts only when cfg asks. The enabling node owns the
        # stop-and-join at teardown.
        self._profiler_owner = False
        if cfg.instrumentation.profiler_labels or cfg.instrumentation.profiler:
            from ..libs import profiler

            profiler.arm_labels()
        if cfg.instrumentation.profiler:
            from ..libs import profiler

            # a cfg-owned profile is per-run: drop samples a previous
            # in-process run (bench A/B, back-to-back localnets) left
            profiler.reset()
            profiler.enable(
                hz=cfg.instrumentation.profiler_hz,
                max_stacks=cfg.instrumentation.profiler_max_stacks,
            )
            self._profiler_owner = True

        # -- device verifier install (the north-star seam) --
        # Done first so every later verification dispatches through it.
        # Install state is process-global (one device runtime per
        # process); warn when two in-process nodes disagree on policy.
        if cfg.tpu.enable:
            prior = tpu_verifier.installed()
            if prior is not None and prior != cfg.tpu.min_batch_size:
                self.logger.info(
                    "tpu verifier already installed with a different "
                    "min_batch; overriding process-wide",
                    prior=prior, new=cfg.tpu.min_batch_size,
                )
            from ..ops import compile_cache, merkle_kernel

            # before the first compile: without it a node recompiles
            # every bucket (about half a minute each on a v5e) at
            # every start
            compile_cache.enable()
            tpu_verifier.install(
                min_batch=cfg.tpu.min_batch_size,
                mesh=self._device_mesh(cfg.tpu.devices),
            )
            merkle_kernel.install()
        elif tpu_verifier.installed() is not None:
            self.logger.info(
                "tpu.enable=false but the device verifier is already "
                "installed process-wide by another node; it stays active"
            )

        # -- DBs + stores (reference: node/setup.go initDBs) --
        backend = cfg.base.db_backend
        db_dir = cfg.base.path(cfg.base.db_dir)
        self._dbs = []

        def _db(name: str):
            db = open_db(name, backend, db_dir)
            self._dbs.append(db)
            return db

        self.block_store = BlockStore(_db("blockstore"))
        self.state_store = StateStore(_db("state"))
        self._evidence_db = _db("evidence")

        # -- proxy app (reference: internal/proxy) --
        if cfg.base.abci == "builtin":
            self._app = app if app is not None else KVStoreApplication()
            creator = local_creator(self._app)
        elif cfg.base.abci == "socket":
            self._app = None
            creator = socket_creator(cfg.base.proxy_app, must_connect=True)
        elif cfg.base.abci == "grpc":
            from ..abci.grpc_transport import grpc_creator

            self._app = None
            creator = grpc_creator(cfg.base.proxy_app, must_connect=True)
        else:
            raise ValueError(f"unknown abci mode {cfg.base.abci!r}")
        self.proxy = AppConns(creator)

        # -- event bus + indexer --
        self.event_bus = EventBus(
            metrics=EventBusMetrics(self.metrics_registry)
        )
        sinks = []
        for kind in cfg.tx_index.indexer:
            if kind == "kv":
                sinks.append(KVSink(_db("tx_index")))
            elif kind == "null":
                sinks.append(NullSink())
            elif kind == "psql":
                # reference: indexer/sink/psql — SQL schema sink
                from ..state.sink_sql import SQLSink

                dsn = cfg.tx_index.psql_conn or (
                    "sqlite:"
                    + os.path.join(
                        cfg.base.path(cfg.base.db_dir), "tx_index.sqlite"
                    )
                )
                sinks.append(
                    SQLSink(dsn, chain_id=self.genesis.chain_id)
                )
            else:
                raise ValueError(f"unknown indexer {kind!r}")
        self.indexer = IndexerService(sinks or [NullSink()], self.event_bus)

        # node identity key (also the privval listener's transport key)
        self.node_key = NodeKey.load_or_generate(
            cfg.base.path(cfg.base.node_key_file)
        )

        # -- privval (reference: node/setup.go createPrivval) --
        self.privval = None
        self.privval_listener = None
        self.privval_pub_key = None
        if cfg.base.mode == MODE_VALIDATOR:
            if cfg.priv_validator.listen_addr.startswith("grpc://"):
                # node dials a gRPC signer (reference: node/setup.go:586
                # "grpc" scheme -> DialRemoteSigner); started (and its
                # lifecycle owned) via privval_listener like the socket
                # variant
                from ..privval.grpc import GRPCSignerClient
                from ..privval.signer import RetrySignerClient

                client = GRPCSignerClient(cfg.priv_validator.listen_addr)
                self.privval_listener = client
                # same retry envelope as the socket path: a signer that
                # is not up yet (or blips) must not abort node.start();
                # refusals (double-sign) still propagate immediately
                self.privval = RetrySignerClient(client)
            elif cfg.priv_validator.listen_addr:
                # remote signer dials in (reference:
                # privval/signer_listener_endpoint.go via
                # createAndStartPrivValidatorSocketClient)
                from ..privval.signer import (
                    RetrySignerClient,
                    SignerListenerEndpoint,
                )

                self.privval_listener = SignerListenerEndpoint(
                    cfg.priv_validator.listen_addr,
                    self.node_key.priv_key,
                )
                self.privval = RetrySignerClient(self.privval_listener)
            else:
                self.privval = FilePV.load_or_generate(
                    cfg.base.path(cfg.priv_validator.key_file),
                    cfg.base.path(cfg.priv_validator.state_file),
                )

        # -- state --
        state = self.state_store.load()
        if state is None:
            state = state_from_genesis(genesis)
            self.state_store.save(state)
        self.initial_state = state

        # -- p2p (reference: node/setup.go createPeerManager/createRouter) --
        listen = cfg.p2p.laddr.replace("tcp://", "")
        advertise = (
            cfg.p2p.external_address.replace("tcp://", "")
            if cfg.p2p.external_address
            else listen
        )
        self.node_info = NodeInfo(
            node_id=self.node_key.node_id,
            listen_addr=advertise,
            network=genesis.chain_id,
            moniker=cfg.base.moniker,
        )
        persistent = [
            p.strip()
            for p in cfg.p2p.persistent_peers.split(",")
            if p.strip()
        ]
        p2p_metrics = P2PMetrics(self.metrics_registry)
        self.peer_manager = PeerManager(
            self.node_key.node_id,
            PeerManagerOptions(
                persistent_peers=persistent,
                max_connected=cfg.p2p.max_connections,
                min_retry_time=cfg.p2p.min_retry_time,
                max_retry_time=cfg.p2p.max_retry_time,
                max_retry_time_persistent=(
                    cfg.p2p.max_retry_time_persistent
                ),
            ),
            store=_db("peerstore"),
            metrics=p2p_metrics,
        )
        for addr in (
            a.strip() for a in cfg.p2p.bootstrap_peers.split(",")
        ):
            if addr:
                self.peer_manager.add(addr)
        self.transport = transport if transport is not None else TCPTransport()
        self.router = Router(
            self.node_info,
            self.node_key.priv_key,
            self.peer_manager,
            self.transport,
            listen_addr=listen,
            options=RouterOptions(
                handshake_timeout=cfg.p2p.handshake_timeout,
                dial_timeout=cfg.p2p.dial_timeout,
                send_rate=cfg.p2p.send_rate,
                recv_rate=cfg.p2p.recv_rate,
                ping_interval=cfg.p2p.ping_interval,
                pong_timeout=cfg.p2p.pong_timeout,
                max_incoming_per_ip=(
                    cfg.p2p.max_incoming_connection_attempts
                ),
                slow_peer_drop_threshold=(
                    cfg.p2p.slow_peer_drop_threshold
                ),
                slow_peer_window_s=cfg.p2p.slow_peer_window,
                slow_peer_ban_s=cfg.p2p.slow_peer_ban,
            ),
            metrics=p2p_metrics,
        )

        # reactors are built in on_start, after the ABCI handshake
        self.mempool: Optional[TxMempool] = None
        self.evidence_pool: Optional[EvidencePool] = None
        self.block_exec: Optional[BlockExecutor] = None
        self.consensus: Optional[ConsensusState] = None
        self.consensus_reactor: Optional[ConsensusReactor] = None
        self.mempool_reactor: Optional[MempoolReactor] = None
        self.evidence_reactor: Optional[EvidenceReactor] = None
        self.blocksync_reactor = None
        self.statesync_reactor = None
        self.pex_reactor = None
        self.rpc_server = None
        self.rpc_env = None
        self.genesis_state_synced = False

    # ------------------------------------------------------------------

    async def on_start(self) -> None:
        """reference: node/node.go OnStart :415-470. A failure partway
        through tears down whatever already started — Service.stop()
        won't call on_stop after a failed start."""
        self._acquire_data_lock()
        # bind this loop (from its own thread — we are on it) so
        # profiler samples of the loop thread sub-attribute to the
        # running task's labeled origin
        from ..libs import profiler

        profiler.register_loop()
        try:
            await self._start_impl()
        except BaseException:
            await self._teardown()
            raise

    @staticmethod
    def _device_mesh(devices: int):
        """The batch-sharding mesh from `[tpu] devices` (reference
        seam: the backend choice is config, not code —
        crypto/crypto.go:53-61). 1 -> None (single chip); 0 -> every
        visible device; n -> the first n (erroring if absent, since a
        silently smaller mesh would change bucket padding semantics)."""
        if devices == 1:
            return None
        if devices < 0:
            raise RuntimeError(f"[tpu] devices = {devices}: must be >= 0")
        import jax

        from ..parallel import make_mesh

        avail = jax.devices()
        if devices == 0:
            devices = len(avail)
        if devices == 1:
            return None
        if len(avail) < devices:
            raise RuntimeError(
                f"[tpu] devices = {devices} but only {len(avail)} "
                f"jax device(s) are visible"
            )
        return make_mesh(avail[:devices])

    def _acquire_data_lock(self) -> None:
        """Advisory data-dir lock: offline commands (reindex-event,
        rollback, reset) refuse to touch the DBs of a RUNNING node, and
        a second node process on the same home fails fast instead of
        corrupting stores. Same-pid locks are treated as stale so an
        in-process crash-restart (the replay tests' crash simulation)
        can reacquire."""
        data_dir = self.cfg.base.path(self.cfg.base.db_dir)
        self._lock_path = os.path.join(data_dir, "LOCK")
        self._lock_fd = acquire_pid_lock(
            self._lock_path, what=f"data dir {data_dir}"
        )

    def _release_data_lock(self) -> None:
        fd = getattr(self, "_lock_fd", None)
        if fd is not None:
            release_pid_lock(self._lock_path, fd)
            self._lock_fd = None

    async def _start_impl(self) -> None:
        cfg = self.cfg
        if cfg.base.mode == MODE_SEED:
            # seed nodes run ONLY peer exchange (reference: node/seed.go)
            await self._start_seed()
            return
        await self.proxy.start()
        await self.event_bus.start()
        await self.indexer.start()
        if self.privval_listener is not None:
            await self.privval_listener.start()
        # resolve the validator identity once: with a remote signer this
        # blocks until the signer dials in (reference: node/setup.go
        # createAndStartPrivValidatorSocketClient + GetPubKey)
        self.privval_pub_key = None
        if self.privval is not None:
            self.privval_pub_key = await self.privval.get_pub_key()

        # ABCI handshake: replay stored blocks into the app until app,
        # store, and state agree (reference: replay.go:240)
        handshaker = Handshaker(
            self.state_store,
            self.initial_state,
            self.block_store,
            self.genesis,
            event_bus=self.event_bus,
        )
        await handshaker.handshake(self.proxy.consensus)
        state = self.state_store.load()
        assert state is not None

        # -- build reactors against the post-handshake state --
        self.mempool = TxMempool(
            self.proxy.mempool,
            cfg.mempool,
            height=state.last_block_height,
            metrics=MempoolMetrics(self.metrics_registry),
        )
        self.evidence_pool = EvidencePool(
            self._evidence_db,
            self.state_store,
            self.block_store,
            metrics=EvidenceMetrics(self.metrics_registry),
        )
        self.block_exec = BlockExecutor(
            self.state_store,
            self.proxy.consensus,
            self.mempool,
            evidence_pool=self.evidence_pool,
            block_store=self.block_store,
            event_bus=self.event_bus,
            metrics=StateMetrics(self.metrics_registry),
        )
        wal = WAL(cfg.base.path(cfg.consensus.wal_file))
        cs_metrics = ConsensusMetrics(self.metrics_registry)
        # per-node flight recorder (consensus/timeline.py): the ring
        # the consensus_timeline RPC route and debug bundle serve,
        # feeding the quorum-latency/rounds/stall metrics above
        from ..consensus.timeline import TimelineRecorder

        timeline = TimelineRecorder(
            capacity=cfg.instrumentation.consensus_timeline_capacity,
            enabled=cfg.instrumentation.consensus_timeline,
            metrics=cs_metrics,
        )
        self.consensus = ConsensusState(
            cfg.consensus,
            state,
            self.block_exec,
            self.block_store,
            privval=self.privval,
            event_bus=self.event_bus,
            wal=wal,
            evidence_pool=self.evidence_pool,
            metrics=cs_metrics,
            timeline=timeline,
        )

        # sync orchestration flags (reference: node/node.go:230
        # onlyValidatorIsUs skips block sync entirely)
        state_sync = cfg.statesync.enable and state.last_block_height == 0
        block_sync = cfg.blocksync.enable and not self._only_validator_is_us(
            state
        )
        wait_sync = state_sync or block_sync

        cs_channels = {
            cid: self.router.open_channel(d)
            for cid, d in consensus_channel_descriptors().items()
        }
        self.consensus_reactor = ConsensusReactor(
            self.consensus,
            cs_channels,
            self.peer_manager.subscribe(),
            self.event_bus,
            cfg=cfg.consensus,
            wait_sync=wait_sync,
        )
        # byzantine adversary plane (consensus/byzantine.py): one
        # armed() check at assembly — a disarmed process (TM_TPU_BYZ
        # unset) installs nothing and pays nothing on any hot path
        from ..consensus import byzantine

        if byzantine.armed():
            byzantine.maybe_install(
                self.consensus, self.consensus_reactor, cfg.base.moniker
            )
        self.mempool_reactor = MempoolReactor(
            self.mempool,
            self.router.open_channel(mempool_channel_descriptor()),
            self.peer_manager.subscribe(),
        )
        self.evidence_reactor = EvidenceReactor(
            self.evidence_pool,
            self.router.open_channel(evidence_channel_descriptor()),
            self.peer_manager.subscribe(),
        )
        from ..blocksync import BlocksyncReactor, blocksync_channel_descriptor
        from ..blocksync.metrics import BlocksyncMetrics

        bs_metrics = BlocksyncMetrics(self.metrics_registry)
        self.blocksync_reactor = BlocksyncReactor(
            state,
            self.block_exec,
            self.block_store,
            self.router.open_channel(
                blocksync_channel_descriptor(bs_metrics)
            ),
            self.peer_manager.subscribe(),
            block_sync=block_sync and not state_sync,
            consensus_reactor=self.consensus_reactor,
            event_bus=self.event_bus,
            metrics=bs_metrics,
        )
        from ..statesync import StatesyncReactor, statesync_channel_descriptors

        self.statesync_reactor = StatesyncReactor(
            self.genesis.chain_id,
            state,
            self.proxy.snapshot,
            self.state_store,
            self.block_store,
            {
                cid: self.router.open_channel(d)
                for cid, d in statesync_channel_descriptors().items()
            },
            self.peer_manager.subscribe(),
            cfg=cfg.statesync,
        )

        if cfg.p2p.pex:
            from ..p2p.pex import PexReactor, pex_channel_descriptor

            self.pex_reactor = PexReactor(
                self.peer_manager,
                self.router.open_channel(pex_channel_descriptor()),
                self.peer_manager.subscribe(),
            )

        # -- start everything (channels are registered; safe to listen) --
        await self.router.start()
        await self.consensus_reactor.start()
        await self.mempool_reactor.start()
        await self.evidence_reactor.start()
        await self.blocksync_reactor.start()
        await self.statesync_reactor.start()
        if self.pex_reactor is not None:
            await self.pex_reactor.start()

        # -- RPC (reference: node/node.go:480-540 startRPC). The
        # Environment always exists — in-process consumers
        # (rpc.LocalClient) need it even when the network listener is
        # disabled; only the server is gated on rpc.laddr --
        from ..rpc import Environment, RPCServer
        from ..rpc.metrics import RPCMetrics

        self.rpc_env = Environment(
            chain_id=self.genesis.chain_id,
            block_store=self.block_store,
            state_store=self.state_store,
            mempool=self.mempool,
            event_bus=self.event_bus,
            consensus=self.consensus,
            consensus_reactor=self.consensus_reactor,
            peer_manager=self.peer_manager,
            proxy=self.proxy,
            genesis=self.genesis,
            evidence_pool=self.evidence_pool,
            event_sinks=self.indexer.sinks,
            node_info=self.node_info,
            privval_pub_key=self.privval_pub_key,
            cfg=cfg,
            metrics=RPCMetrics(self.metrics_registry),
        )
        if cfg.rpc.laddr:
            self.rpc_server = RPCServer(
                self.rpc_env,
                laddr=cfg.rpc.laddr,
                max_body_bytes=cfg.rpc.max_body_bytes,
            )
            await self.rpc_server.start()

        # -- Prometheus exposition (reference: node/node.go:606) --
        if cfg.instrumentation.prometheus:
            await self._start_metrics_server(
                cfg.instrumentation.prometheus_listen_addr
            )

        if state_sync:
            self.spawn(self._state_sync_then_follow(), "state-sync")

        self.logger.info(
            "node started",
            node_id=self.node_key.node_id,
            chain_id=self.genesis.chain_id,
            mode=cfg.base.mode,
            tpu="installed" if cfg.tpu.enable else "disabled",
        )

    def _render_metrics(self) -> str:
        """Per-node series first, then the process-global registry
        (device verifier, any subsystem constructed without a per-node
        registry) minus names the per-node registry already rendered —
        one exposition document with no duplicate series."""
        from ..libs.metrics import DEFAULT_REGISTRY

        text = self.metrics_registry.render()
        return text + DEFAULT_REGISTRY.render(
            exclude=self.metrics_registry.names()
        )

    def _health_payload(self) -> dict:
        """/healthz: node height + sync status (block height from the
        store; syncing while the consensus reactor still waits on
        state/block sync)."""
        syncing = False
        if self.consensus_reactor is not None:
            syncing = bool(self.consensus_reactor.wait_sync)
        return {
            "node_id": self.node_key.node_id,
            "height": self.block_store.height(),
            "syncing": syncing,
        }

    async def _start_metrics_server(self, addr: str) -> None:
        """Plain-text Prometheus exposition on /metrics, JSON liveness
        on /healthz (reference: node/node.go:606)."""
        import json as _json

        host, _, port = addr.replace("tcp://", "").rpartition(":")

        async def handler(reader, writer):
            try:
                # bound the whole request (deadline + header cap): this
                # is an unauthenticated port, and a slow-loris client
                # feeding one header per few seconds must not pin a
                # task forever
                deadline = asyncio.get_event_loop().time() + 10.0

                async def _line():
                    budget = deadline - asyncio.get_event_loop().time()
                    if budget <= 0:
                        raise asyncio.TimeoutError
                    return await asyncio.wait_for(reader.readline(), budget)

                line = await _line()
                for _ in range(100):  # header cap
                    h = await _line()
                    if h in (b"\r\n", b"\n", b""):
                        break
                else:
                    raise asyncio.TimeoutError
                # parse the request line properly: an arbitrary request
                # merely CONTAINING "/metrics" (a query param, a longer
                # path) must not scrape
                try:
                    method, target, _version = (
                        line.decode("latin-1").strip().split(" ", 2)
                    )
                except (ValueError, UnicodeDecodeError):
                    method, target = "", ""
                path = target.split("?", 1)[0]
                ctype = b"text/plain; version=0.0.4"
                if method not in ("GET", "HEAD"):
                    status, body = b"405 Method Not Allowed", b"GET only\n"
                elif path == "/metrics":
                    status = b"200 OK"
                    body = self._render_metrics().encode()
                elif path == "/healthz":
                    status = b"200 OK"
                    ctype = b"application/json"
                    body = _json.dumps(self._health_payload()).encode()
                else:
                    status = b"404 Not Found"
                    body = b"see /metrics or /healthz\n"
                writer.write(
                    b"HTTP/1.1 " + status + b"\r\n"
                    b"Content-Type: " + ctype + b"\r\n"
                    b"Content-Length: " + str(len(body)).encode() + b"\r\n"
                    b"Connection: close\r\n\r\n"
                    + (b"" if method == "HEAD" else body)
                )
                await writer.drain()
            except (
                ConnectionError,
                asyncio.IncompleteReadError,
                asyncio.TimeoutError,
                ValueError,  # readline: line longer than the 64K limit
            ):
                pass
            finally:
                writer.close()

        self._metrics_server = await asyncio.start_server(
            handler, host or "0.0.0.0", int(port)
        )
        self.metrics_port = self._metrics_server.sockets[0].getsockname()[1]
        self.logger.info("prometheus metrics", addr=f"{host}:{self.metrics_port}")

    async def _start_seed(self) -> None:
        """Seed-mode boot: router + PEX only (reference: node/seed.go)."""
        from ..p2p.pex import PexReactor, pex_channel_descriptor

        self.pex_reactor = PexReactor(
            self.peer_manager,
            self.router.open_channel(pex_channel_descriptor()),
            self.peer_manager.subscribe(),
        )
        await self.router.start()
        await self.pex_reactor.start()
        self.logger.info(
            "seed node started", node_id=self.node_key.node_id
        )

    async def _state_sync_then_follow(self) -> None:
        """statesync → blocksync → consensus (reference:
        node/node.go:592 startStateSync → SwitchToBlockSync)."""
        try:
            state = await self.statesync_reactor.sync()
            await self.statesync_reactor.backfill(state)
            self.genesis_state_synced = True
            await self.blocksync_reactor.start_sync(state)
        except Exception as e:
            self.logger.error("state sync failed", err=str(e))
            raise

    def _only_validator_is_us(self, state) -> bool:
        """reference: node/node.go:230 onlyValidatorIsUs."""
        if self.privval_pub_key is None:
            return False
        if state.validators.size() != 1:
            return False
        addr = state.validators.validators[0].address
        return addr == self.privval_pub_key.address()

    async def on_stop(self) -> None:
        """reference: node/node.go OnStop — reverse start order."""
        await self._teardown()

    async def _teardown(self) -> None:
        # stop-and-join the sampler FIRST if this node enabled it: no
        # profiler thread may survive a node stop, and no sample may
        # land after (tests/test_teardown.py pins both)
        if getattr(self, "_profiler_owner", False):
            from ..libs import profiler

            profiler.disable()
            self._profiler_owner = False
        ms = getattr(self, "_metrics_server", None)
        if ms is not None:
            ms.close()
            try:
                await asyncio.wait_for(ms.wait_closed(), timeout=1.0)
            except asyncio.TimeoutError:
                pass  # straggling scrape connections die with the loop
            self._metrics_server = None
        for svc in (
            self.rpc_server,
            self.pex_reactor,
            self.statesync_reactor,
            self.blocksync_reactor,
            self.evidence_reactor,
            self.mempool_reactor,
            self.consensus_reactor,
            self.router,
            self.privval_listener,
            self.indexer,
            self.event_bus,
            self.proxy,
        ):
            if svc is not None and svc.is_running:
                try:
                    await svc.stop()
                except Exception as e:
                    self.logger.error(
                        "error stopping service", svc=svc.name, err=str(e)
                    )
        self.peer_manager.flush()
        for sink in getattr(self.indexer, "sinks", ()):
            close = getattr(sink, "close", None)
            if close is not None:
                try:
                    close()
                except Exception as e:
                    self.logger.error("error closing sink", err=str(e))
        for db in self._dbs:
            try:
                db.close()
            except Exception as e:
                self.logger.error("error closing db", err=str(e))
        self._dbs = []
        self._release_data_lock()


def _read_lock_pid(path: str) -> int:
    try:
        with open(path) as f:
            return int(f.read().strip() or 0)
    except (OSError, ValueError):
        return 0


def _pid_alive(pid: int) -> bool:
    try:
        os.kill(pid, 0)
    except OSError:
        return False
    return True


def acquire_pid_lock(path: str, what: str = "") -> int:
    """Atomically claim the advisory lockfile at `path`; returns an fd
    that must be kept open while held and passed to release_pid_lock().

    flock() on a held fd is the atomic claim step — two processes
    starting simultaneously cannot both succeed (a read-check-then-write
    pidfile guard fails exactly in the race it exists to prevent), the
    kernel releases the lock if the holder dies mid-hold, and pid-reuse
    cannot fake liveness. The file's pid content is secondary: it names
    the holder for error messages, and a live *foreign* pid written
    without the flock (a holder on another fs view, or tests simulating
    a running node) still refuses. Our own pid in the file is fine — an
    in-process crash-restart (the replay tests' crash simulation)
    reacquires after its dead fd's flock lapsed.
    """
    import fcntl

    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    fd = os.open(path, os.O_CREAT | os.O_RDWR, 0o644)
    try:
        fcntl.flock(fd, fcntl.LOCK_EX | fcntl.LOCK_NB)
    except OSError:
        pid = _read_lock_pid(path)
        os.close(fd)
        holder = f"process {pid}" if pid else "another process"
        raise RuntimeError(
            f"{what or path} is locked by running {holder}"
        ) from None
    pid = _read_lock_pid(path)
    if pid and pid != os.getpid() and _pid_alive(pid):
        fcntl.flock(fd, fcntl.LOCK_UN)
        os.close(fd)
        raise RuntimeError(
            f"{what or path} is locked by running process {pid}"
        )
    os.ftruncate(fd, 0)
    os.write(fd, str(os.getpid()).encode())
    return fd


def release_pid_lock(path: str, fd: int) -> None:
    """Empty the pidfile and drop the flock. The file itself stays
    (unlinking a flock-ed path lets a third process lock a fresh inode
    while a second still holds the old one)."""
    import fcntl

    try:
        os.ftruncate(fd, 0)
        fcntl.flock(fd, fcntl.LOCK_UN)
    except OSError:
        pass
    finally:
        try:
            os.close(fd)
        except OSError:
            pass


def make_node(
    cfg: Config,
    app=None,
    genesis: Optional[GenesisDoc] = None,
    transport: Optional[Transport] = None,
) -> Node:
    """Build a Node from config files on disk (reference:
    node/node.go:116 makeNode + node/public.go New).

    `app` overrides the builtin application (defaults to kvstore);
    `genesis`/`transport` overrides support tests and in-process
    harnesses.
    """
    cfg.ensure_dirs()
    if genesis is None:
        genesis = GenesisDoc.from_file(cfg.base.path(cfg.base.genesis_file))
    return Node(cfg, genesis, app=app, transport=transport)
