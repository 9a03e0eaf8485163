"""Real-process e2e testnet runner.

The in-process runner (runner.py) hosts every node in one asyncio loop
— fast and deterministic, but its "kill" is a polite stop: WAL replay
after a hard kill mid-fsync, torn tails from a genuinely dead process,
and ABCI handshake replay against a surviving app server are never
exercised. This runner closes that gap the way the reference's e2e
harness does with docker (test/e2e/runner/perturb.go:43-77): every
node is a SEPARATE OS PROCESS (`python -m tendermint_tpu.cmd start`)
talking TCP p2p, each with its own out-of-process kvstore app over
socket ABCI, and perturbations are REAL signals:

    kill        SIGKILL the node process, restart it (perturb.go:46
                docker kill + up). The app process survives, so the
                restarted node must WAL-replay and ABCI-handshake
                against an app that is ahead of/behind its stores.
    restart     SIGTERM, wait for exit, start again (graceful).
    pause       SIGSTOP ... SIGCONT after a few seconds — the process
                is alive but silent, like a frozen VM.
    disconnect  approximated as a longer SIGSTOP: without container
                network namespaces a Python process can't have its
                sockets severed externally. Honest limitation.

Invariants run over LIVE RPC (test/e2e/tests/ queries its nodes the
same way): height convergence via /status, hash agreement via /block,
tx inclusion under load via /abci_query against the kvstore app. The
block-interval benchmark covers the reference's 100-block window
(benchmark.go:14-34) when asked for.

`state_sync` nodes work across processes: when any node wants state
sync, every app process serves snapshots (`abci kvstore
--snapshot-interval`), and the late joiner's trust root is seeded the
way an operator would — block-1 hash fetched over a live node's RPC
and written into its config before its process starts. The end-of-run
invariant proves a real restore: the node must be at the tip yet
answer "no block at height 1" — a restored node never holds the FULL
genesis block (backfill fetches headers+commits only), while a node
that silently blocksynced from genesis does.

Process-mode limitations (documented, not silent): `misbehaviors`
(the double-prevote hook monkeypatches consensus internals) are
in-process-runner-only; manifests using them are rejected here.
Databases are forced to sqlite — a killed process must find its
stores on disk when it comes back.
"""

from __future__ import annotations

import asyncio
import base64
import os
import signal
import socket
import subprocess
import sys
import time
from typing import Dict, List, Optional

from ..config import Config, write_config
from ..crypto.ed25519 import PrivKeyEd25519
from ..node import NodeKey
from ..privval import FilePV
from ..rpc.client import HTTPClient, RPCClientError
from ..types.genesis import GenesisDoc, GenesisValidator
from .manifest import Manifest
from .runner import RunReport

__all__ = ["ProcessRunner", "run_manifest_processes"]


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _child_env() -> dict:
    """Child node processes run CPU-only jax: a chip belongs to one
    process at a time, and a child must never take it from (or hang
    behind) a parent that holds it."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    # the repo root so `-m tendermint_tpu.cmd` resolves in children
    root = os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )
    inherited = env.get("PYTHONPATH", "")
    env["PYTHONPATH"] = root + (os.pathsep + inherited if inherited else "")
    return env


class _ProcHandle:
    def __init__(self, name: str, cfg: Config):
        self.name = name
        self.cfg = cfg
        self.node_proc: Optional[subprocess.Popen] = None
        self.app_proc: Optional[subprocess.Popen] = None
        self.paused = False
        self.rpc = HTTPClient(cfg.rpc.laddr, timeout=5.0)

    @property
    def live(self) -> bool:
        return (
            self.node_proc is not None
            and self.node_proc.poll() is None
            and not self.paused
        )


class ProcessRunner:
    """Phases mirror runner.Runner; see module docstring."""

    def __init__(
        self, manifest: Manifest, home: str, timeout: float = 300.0
    ):
        for name, spec in manifest.nodes.items():
            if spec.misbehaviors:
                raise ValueError(
                    f"{name}: misbehaviors are only supported by the "
                    "in-process runner (they monkeypatch consensus "
                    "internals)"
                )
        self.m = manifest
        self.home = home
        self.timeout = timeout
        self.handles: Dict[str, _ProcHandle] = {}
        self.report = RunReport()
        self._tx_seq = 0
        self._sent_keys: List[bytes] = []
        self._resume_tasks: List[asyncio.Task] = []
        # runtime-mutable partition shared with every child via
        # TM_TPU_PARTITION_FILE (crypto/faults.py polls it): partition
        # and heal perturbations rewrite this file mid-run. Tracked as
        # a SET of isolated nodes so partitioning a second node
        # composes with (instead of silently healing) the first.
        self._partition_file = os.path.join(home, "partition.spec")
        self._partitioned: set = set()

    # -- setup (reference: setup.go; same genesis/keys as cmd testnet) --

    def setup(self) -> None:
        m = self.m
        privs = {
            name: PrivKeyEd25519.from_seed(
                name.encode().ljust(32, b"\x9e")[:32]
            )
            for name in m.validators
        }
        genesis = GenesisDoc(
            chain_id=m.chain_id,
            genesis_time_ns=time.time_ns(),
            initial_height=m.initial_height,
            validators=[
                GenesisValidator(pub_key=privs[n].pub_key(), power=p)
                for n, p in sorted(m.validators.items())
            ],
        )
        os.makedirs(self.home, exist_ok=True)
        with open(self._partition_file, "w") as f:
            f.write("")  # no partition at boot
        node_ids: Dict[str, str] = {}
        p2p_port: Dict[str, int] = {}
        for name, spec in self.m.sorted_nodes():
            cfg = Config()
            cfg.base.home = os.path.join(self.home, name)
            cfg.base.chain_id = m.chain_id
            cfg.base.mode = spec.mode
            # the moniker is the node's net-fault-plane label — what a
            # partition.spec member names (TCP hosts are all 127.0.0.1
            # here, so only the moniker/node-ID labels can tell the
            # children apart)
            cfg.base.moniker = name
            # stores must survive SIGKILL: force the on-disk backend
            cfg.base.db_backend = "sqlite"
            cfg.base.abci = "socket"
            cfg.base.proxy_app = f"tcp://127.0.0.1:{_free_port()}"
            cfg.consensus.timeout_propose = 2.0
            cfg.consensus.timeout_prevote = 1.0
            cfg.consensus.timeout_precommit = 1.0
            cfg.consensus.timeout_commit = 0.2
            if spec.state_sync:
                cfg.statesync.enable = True
                cfg.statesync.discovery_time = 2.0
                cfg.statesync.chunk_request_timeout = 10.0
                # trust root seeded over live RPC at spawn time
            cfg.rpc.laddr = f"tcp://127.0.0.1:{_free_port()}"
            p2p_port[name] = _free_port()
            cfg.p2p.laddr = f"tcp://127.0.0.1:{p2p_port[name]}"
            cfg.ensure_dirs()
            genesis.save_as(cfg.base.path(cfg.base.genesis_file))
            priv = privs.get(name)
            if priv is not None:
                FilePV.from_priv_key(
                    priv,
                    cfg.base.path(cfg.priv_validator.key_file),
                    cfg.base.path(cfg.priv_validator.state_file),
                ).save()
            node_ids[name] = NodeKey.load_or_generate(
                cfg.base.path(cfg.base.node_key_file)
            ).node_id
            self.handles[name] = _ProcHandle(name, cfg)
        for name, h in self.handles.items():
            h.cfg.p2p.persistent_peers = ",".join(
                f"{node_ids[o]}@127.0.0.1:{p2p_port[o]}"
                for o in self.handles
                if o != name
            )
            write_config(
                h.cfg, os.path.join(h.cfg.base.home, "config", "config.toml")
            )

    # -- start (reference: start.go) --

    # snapshots are advertised by every app when anyone will state
    # sync (the reference e2e app's snapshot_interval manifest knob)
    SNAPSHOT_INTERVAL = 2

    def _spawn_app(self, h: _ProcHandle) -> None:
        cmd = [
            sys.executable, "-m", "tendermint_tpu.cmd",
            "abci", "kvstore", "--addr", h.cfg.base.proxy_app,
        ]
        if any(s.state_sync for s in self.m.nodes.values()):
            cmd += ["--snapshot-interval", str(self.SNAPSHOT_INTERVAL)]
        log = open(os.path.join(h.cfg.base.home, "app.log"), "ab")
        h.app_proc = subprocess.Popen(
            cmd, stdout=log, stderr=subprocess.STDOUT, env=_child_env(),
        )
        log.close()

    def _spawn_node(self, h: _ProcHandle) -> None:
        log = open(os.path.join(h.cfg.base.home, "node.log"), "ab")
        env = _child_env()
        # arm the (initially empty) runtime-mutable partition plane in
        # every node child — partition/heal perturbations mutate the
        # shared file and the children re-read it on change
        env["TM_TPU_PARTITION_FILE"] = self._partition_file
        h.node_proc = subprocess.Popen(
            [
                sys.executable, "-m", "tendermint_tpu.cmd",
                "--home", h.cfg.base.home, "start",
            ],
            stdout=log, stderr=subprocess.STDOUT, env=env,
        )
        log.close()
        h.paused = False

    async def _start_node(self, name: str) -> None:
        h = self.handles[name]
        spec = self.m.nodes[name]
        if spec.state_sync and not h.cfg.statesync.trust_hash:
            await self._seed_state_sync_trust(h)
        if h.app_proc is None or h.app_proc.poll() is not None:
            self._spawn_app(h)
        self._spawn_node(h)

    async def _seed_state_sync_trust(self, h: _ProcHandle) -> None:
        """Anchor the late joiner's trust to the live chain the way an
        operator does: block-1 hash over a running node's RPC, written
        into the joiner's config before its process boots (reference:
        the runner passes trust hashes into statesync configs,
        setup.go)."""
        for other in self.handles.values():
            if other is h or not other.live:
                continue
            try:
                res = await other.rpc.call("block", height=1)
                h.cfg.statesync.trust_height = 1
                h.cfg.statesync.trust_hash = res["block_id"]["hash"]
                write_config(
                    h.cfg,
                    os.path.join(
                        h.cfg.base.home, "config", "config.toml"
                    ),
                )
                return
            except Exception:
                continue
        raise RuntimeError(
            f"{h.name}: no live node answered for the state-sync "
            "trust root"
        )

    # -- load over live RPC (reference: load.go) --

    async def _load_loop(self) -> None:
        rate = self.m.load.tx_rate
        if rate <= 0:
            return
        period = 1.0 / rate
        i = 0
        while True:
            await asyncio.sleep(period)
            live = [h for h in self.handles.values() if h.live]
            if not live:
                continue
            h = live[i % len(live)]
            i += 1
            self._tx_seq += 1
            key = f"load-{self._tx_seq}".encode()
            val = os.urandom(
                max(1, self.m.load.tx_size // 2)
            ).hex().encode()
            tx = (key + b"=" + val)[: self.m.load.tx_size]
            try:
                # short cap: a busy/restarting node must not stall the
                # whole load loop for the full client timeout
                await asyncio.wait_for(
                    h.rpc.call(
                        "broadcast_tx_async",
                        tx=base64.b64encode(tx).decode(),
                    ),
                    timeout=1.0,
                )
                self.report.txs_submitted += 1
                self._sent_keys.append(tx.split(b"=", 1)[0])
            except asyncio.TimeoutError:
                # the cancelled call may have left a half-written
                # request on the kept-alive socket; drop it so the
                # next call reconnects cleanly
                try:
                    await h.rpc.close()
                except Exception:
                    pass
            except Exception:
                pass  # node down / restarting: load is best-effort

    # -- perturb with REAL signals (reference: perturb.go:43-77) --

    async def _apply_perturbation(self, name: str, action: str) -> None:
        h = self.handles[name]
        if h.node_proc is None:
            return
        if action == "kill":
            if h.node_proc.poll() is None:
                h.node_proc.send_signal(signal.SIGKILL)
                h.node_proc.wait()
            # immediate restart, like docker kill + up: the node must
            # repair its WAL tail and handshake-replay against the
            # still-running app process
            await self._start_node(name)
        elif action == "restart":
            if h.node_proc.poll() is None:
                h.node_proc.send_signal(signal.SIGTERM)
                try:
                    await asyncio.get_running_loop().run_in_executor(
                        None, h.node_proc.wait, 30
                    )
                except subprocess.TimeoutExpired:
                    # a shutdown wedged past the grace period becomes
                    # a hard kill, like _teardown — never a raw
                    # exception that aborts the whole run
                    h.node_proc.kill()
                    h.node_proc.wait()
            await self._start_node(name)
        elif action == "partition":
            # cut the node from everyone else at the p2p fault plane:
            # its process keeps running and answering RPC, its links
            # drop every frame (unlike `disconnect`'s SIGSTOP
            # approximation, which also freezes RPC)
            self._partitioned.add(name)
            self._write_partition_spec()
        elif action == "heal":
            self._partitioned.discard(name)
            self._write_partition_spec()
        elif action in ("pause", "disconnect"):
            if h.node_proc.poll() is None:
                h.node_proc.send_signal(signal.SIGSTOP)
                h.paused = True

                async def resume(hold: float) -> None:
                    await asyncio.sleep(hold)
                    if h.node_proc and h.node_proc.poll() is None:
                        h.node_proc.send_signal(signal.SIGCONT)
                    h.paused = False

                self._resume_tasks.append(
                    asyncio.get_running_loop().create_task(
                        resume(3.0 if action == "pause" else 8.0)
                    )
                )

    def _write_partition_spec(self) -> None:
        """Render the isolated-node set as partition groups: each
        isolated node is its OWN group (cut from each other too), the
        remainder one connected group. Empty set = healed net."""
        isolated = sorted(self._partitioned)
        rest = [n for n in self.handles if n not in self._partitioned]
        groups = [[n] for n in isolated]
        if isolated and rest:
            groups.append(rest)
        spec = "|".join(",".join(g) for g in groups) if isolated else ""
        with open(self._partition_file, "w") as f:
            f.write(spec)

    # -- orchestration --

    async def _height_of(self, h: _ProcHandle) -> int:
        try:
            res = await h.rpc.call("status")
            return int(res["sync_info"]["latest_block_height"])
        except Exception:
            return -1

    async def _network_height(self) -> int:
        hs = [
            await self._height_of(h)
            for h in self.handles.values()
            if h.live
        ]
        return max((x for x in hs if x >= 0), default=0)

    async def run(self) -> RunReport:
        self.setup()
        try:
            return await self._run_inner()
        finally:
            await self._teardown()

    async def _run_inner(self) -> RunReport:
        for name, spec in self.m.sorted_nodes():
            if spec.start_at == 0:
                await self._start_node(name)
        load_task = asyncio.get_running_loop().create_task(
            self._load_loop()
        )
        pending_starts = {
            name: s.start_at
            for name, s in self.m.sorted_nodes()
            if s.start_at > 0
        }
        schedule: List[tuple] = []
        for name, h in self.handles.items():
            for p in self.m.nodes[name].perturb:
                schedule.append((p.height, name, p.action))
        schedule.sort()

        deadline = time.monotonic() + self.timeout
        try:
            while True:
                if time.monotonic() > deadline:
                    self.report.failures.append(
                        f"timeout before height {self.m.target_height} "
                        f"(at {await self._network_height()})"
                    )
                    break
                await asyncio.sleep(0.5)
                height = await self._network_height()
                for name, at in list(pending_starts.items()):
                    if height >= at:
                        del pending_starts[name]
                        await self._start_node(name)
                while schedule and schedule[0][0] <= height:
                    _, name, action = schedule.pop(0)
                    await self._apply_perturbation(name, action)
                if (
                    height >= self.m.target_height
                    and not pending_starts
                    and not schedule
                ):
                    # a node whose process is alive but mute — RPC not
                    # answering (-1) or SIGSTOP'd (paused) — IS a
                    # laggard: a process that never recovers must hold
                    # the run open until the timeout records it, not
                    # be silently excluded from convergence
                    laggard = False
                    for h in self.handles.values():
                        alive = (
                            h.node_proc is not None
                            and h.node_proc.poll() is None
                        )
                        if alive and (
                            h.paused
                            or await self._height_of(h)
                            < self.m.target_height
                        ):
                            laggard = True
                    if not laggard:
                        break
        finally:
            load_task.cancel()
            # resume tasks are AWAITED, not cancelled: a cancelled
            # resume leaves its node SIGSTOP'd and invisible to the
            # invariant checks below (their holds are bounded <=8 s)
            await asyncio.gather(
                load_task, *self._resume_tasks, return_exceptions=True
            )

        await self._check_invariants()
        await self._benchmark()
        return self.report

    async def _teardown(self) -> None:
        for h in self.handles.values():
            try:
                await h.rpc.close()
            except Exception:
                pass
            for proc, grace in ((h.node_proc, True), (h.app_proc, False)):
                if proc is None or proc.poll() is not None:
                    continue
                proc.send_signal(signal.SIGCONT)  # un-pause if stopped
                proc.send_signal(
                    signal.SIGTERM if grace else signal.SIGKILL
                )
            for proc in (h.node_proc, h.app_proc):
                if proc is not None:
                    try:
                        await asyncio.get_running_loop().run_in_executor(
                            None, proc.wait, 15
                        )
                    except subprocess.TimeoutExpired:
                        proc.kill()
                        proc.wait()

    # -- test over live RPC (reference: test/e2e/tests/) --

    async def _check_invariants(self) -> None:
        rep = self.report
        live = [h for h in self.handles.values() if h.live]
        if not live:
            rep.failures.append("no live nodes at end of run")
            return
        heights = {}
        for h in live:
            hh = await self._height_of(h)
            if hh >= 0:
                heights[h.name] = hh
        if not heights:
            rep.failures.append("no node answered /status at end of run")
            return
        rep.reached_height = min(heights.values())
        for h in live:
            if h.name not in heights:
                # alive but mute: a restarted process that never
                # recovered must fail the run, not vanish from it
                rep.failures.append(
                    f"{h.name} RPC unreachable at end of run"
                )
        if rep.reached_height < self.m.target_height:
            rep.failures.append(
                f"converged height {rep.reached_height} < target "
                f"{self.m.target_height}"
            )
        # one sweep over the reference node's blocks: hash agreement
        # across nodes + committed-tx count under load. The reference
        # must hold full history, so state-sync nodes (no early
        # blocks by design) are never the baseline.
        full_history = [
            h for h in live if not self.m.nodes[h.name].state_sync
        ]
        ref = (full_history or live)[0]
        committed = 0
        for height in range(1, rep.reached_height + 1):
            try:
                want = await ref.rpc.call("block", height=height)
            except Exception:
                continue
            committed += len(want["block"]["txs"] or [])
            for h in live:
                if h is ref:
                    continue
                try:
                    got = await h.rpc.call("block", height=height)
                except Exception:
                    continue
                if got["block_id"]["hash"] != want["block_id"]["hash"]:
                    rep.failures.append(
                        f"fork at height {height}: {h.name} disagrees "
                        f"with {ref.name}"
                    )
        # state-sync nodes must have RESTORED, not blocksynced from
        # genesis: a restored node never holds the FULL genesis block
        # (backfill fetches headers+commits only), while a node that
        # silently blocksynced from height 1 does.
        for name, spec in self.m.nodes.items():
            if not spec.state_sync:
                continue
            h = self.handles[name]
            synced = False
            try:
                res = await h.rpc.call("status")
                if int(res["sync_info"]["latest_block_height"]) >= 1:
                    try:
                        await h.rpc.call("block", height=1)
                        synced = False  # full genesis block on hand
                    except RPCClientError as e:
                        # only a JSON-RPC-level answer ("no block at
                        # height 1", negative error code) proves the
                        # restore; a transport failure proves nothing
                        synced = e.code is not None and e.code < 0
            except Exception:
                pass
            rep.state_synced[name] = synced
            if not synced:
                rep.failures.append(
                    f"{name} was configured for state sync but holds "
                    "the full genesis block (blocksynced instead?) or "
                    "did not answer RPC"
                )
        if self.m.load.tx_rate > 0:
            rep.txs_committed = committed
            if rep.txs_submitted > 0 and committed == 0:
                rep.failures.append("load ran but no txs were committed")
            # the app STATE must contain committed keys, not just the
            # blocks (kvstore semantics over live abci_query) — a
            # state-corrupting app would otherwise pass
            found = 0
            for key in self._sent_keys[:10]:
                try:
                    res = await ref.rpc.call(
                        "abci_query", path="/store", data=key.hex()
                    )
                    if res["response"].get("log") == "exists":
                        found += 1
                except Exception:
                    pass
            if committed > 0 and self._sent_keys and found == 0:
                rep.failures.append(
                    "no submitted kvstore key is queryable in app state"
                )

    # -- benchmark (reference: benchmark.go:14-34, 100-block window) --

    async def _benchmark(self) -> None:
        live = [h for h in self.handles.values() if h.live]
        if not live:
            return
        ref = live[0]
        times: List[int] = []
        for height in range(1, self.report.reached_height + 1):
            try:
                res = await ref.rpc.call("header", height=height)
                times.append(int(res["header"]["time_ns"]))
            except Exception:
                pass
        if len(times) < 2:
            return
        deltas = [(b - a) / 1e9 for a, b in zip(times, times[1:])]
        # the reference benchmark samples a window past startup
        # (benchmark.go:24 skips to an offset); the first couple of
        # intervals here measure process boot + peer dialing, not
        # steady-state consensus. rep.blocks reports what's included.
        if len(deltas) > 10:
            deltas = deltas[2:]
        rep = self.report
        rep.blocks = len(deltas)
        rep.interval_avg = sum(deltas) / len(deltas)
        mean = rep.interval_avg
        rep.interval_stddev = (
            sum((d - mean) ** 2 for d in deltas) / len(deltas)
        ) ** 0.5
        rep.interval_min = min(deltas)
        rep.interval_max = max(deltas)


def run_manifest_processes(
    manifest: Manifest, home: str, timeout: float = 300.0
) -> RunReport:
    """Convenience sync wrapper (the `e2e run --processes` CLI path)."""
    return asyncio.run(
        ProcessRunner(manifest, home, timeout=timeout).run()
    )
