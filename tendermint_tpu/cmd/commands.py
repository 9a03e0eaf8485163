"""Operator CLI: `python -m tendermint_tpu.cmd <command>`.

reference: cmd/tendermint/commands/ (init, run_node/start, light,
rollback, testnet, gen_validator, gen_node_key, show_validator,
show_node_id, reset, inspect, replay, version). argparse instead of
cobra; every command operates on a --home directory laid out exactly
like make_node expects (config/config.toml, config/genesis.json,
config/node_key.json, config/priv_validator_key.json, data/).
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import shutil
import signal
import sys
import time
from typing import List, Optional

from .. import version as _version
from ..config import Config, load_config, write_config
from ..crypto.ed25519 import PrivKeyEd25519


def _config_path(home: str) -> str:
    return os.path.join(os.path.expanduser(home), "config", "config.toml")


def _load_home(home: str) -> Config:
    path = _config_path(home)
    if os.path.exists(path):
        cfg = load_config(path)
    else:
        cfg = Config()
    cfg.base.home = home
    return cfg


# -- init (reference: commands/init.go) -------------------------------------


def cmd_init(args) -> int:
    from ..node.key import NodeKey
    from ..privval import FilePV
    from ..types.genesis import GenesisDoc, GenesisValidator

    cfg = Config()
    cfg.base.home = args.home
    cfg.base.mode = args.mode
    cfg.base.moniker = args.moniker
    cfg.ensure_dirs()

    genesis_path = cfg.base.path(cfg.base.genesis_file)
    pv = None
    if args.mode == "validator":
        pv = FilePV.load_or_generate(
            cfg.base.path(cfg.priv_validator.key_file),
            cfg.base.path(cfg.priv_validator.state_file),
            key_type=getattr(args, "key", None) or "ed25519",
        )
    if os.path.exists(genesis_path):
        print(f"found genesis file {genesis_path}")
        genesis = GenesisDoc.from_file(genesis_path)
    else:
        chain_id = args.chain_id or f"test-chain-{os.urandom(3).hex()}"
        validators = []
        if pv is not None:
            validators.append(
                GenesisValidator(pub_key=pv.key.pub_key, power=10)
            )
        genesis = GenesisDoc(
            chain_id=chain_id,
            genesis_time_ns=time.time_ns(),
            validators=validators,
        )
        genesis.save_as(genesis_path)
        print(f"generated genesis file {genesis_path}")
    cfg.base.chain_id = genesis.chain_id
    NodeKey.load_or_generate(cfg.base.path(cfg.base.node_key_file))
    write_config(cfg, _config_path(args.home))
    print(f"initialized {args.mode} node in {cfg.base.root()}")
    return 0


# -- start (reference: commands/run_node.go) --------------------------------


def cmd_signer(args) -> int:
    """Run the external signing process against a node's
    [priv_validator] listen_addr: loads this home's FilePV (key +
    last-sign double-sign protection state) and serves signing
    requests over SecretConnection — or gRPC with --grpc (reference:
    the tmkms/SignerServer deployment shape; privval/signer.py
    SignerServer, signer_server.go)."""
    from ..libs.log import configure
    from ..privval import FilePV

    cfg = _load_home(args.home)
    configure(
        level=cfg.base.log_level,
        json_format=cfg.base.log_format == "json",
    )
    pv = FilePV.load(
        cfg.base.path(cfg.priv_validator.key_file),
        cfg.base.path(cfg.priv_validator.state_file),
    )
    print(
        f"signer for validator {pv.key.address.hex()} -> {args.addr}",
        flush=True,
    )

    async def run() -> None:
        if args.grpc:
            if args.node_id:
                print(
                    "--node-id applies to the socket transport only "
                    "(no identity check exists on grpc); refusing to "
                    "silently ignore it",
                    file=sys.stderr,
                )
                raise SystemExit(2)
            from ..privval.grpc import GRPCSignerServer

            srv = GRPCSignerServer(args.addr, cfg.base.chain_id, pv)
        else:
            from ..privval.signer import SignerServer

            srv = SignerServer(
                args.addr,
                pv,
                expected_node_id=args.node_id,
                chain_id=cfg.base.chain_id,
            )
        loop = asyncio.get_running_loop()
        stop = asyncio.Event()
        for sig in (signal.SIGINT, signal.SIGTERM):
            loop.add_signal_handler(sig, stop.set)
        await srv.start()
        try:
            await stop.wait()
        finally:
            await srv.stop()

    asyncio.run(run())
    return 0


def cmd_start(args) -> int:
    from ..libs.log import configure
    from ..node import make_node

    cfg = _load_home(args.home)
    if args.moniker:
        cfg.base.moniker = args.moniker
    # without this, a started node emits nothing below WARNING —
    # unusable for operators and for e2e post-mortems
    configure(
        level=cfg.base.log_level,
        json_format=cfg.base.log_format == "json",
    )

    async def run() -> None:
        node = make_node(cfg)
        loop = asyncio.get_running_loop()
        stop = asyncio.Event()
        for sig in (signal.SIGINT, signal.SIGTERM):
            loop.add_signal_handler(sig, stop.set)
        # a failed start tears itself down (Node.on_start wraps
        # _start_impl in its own teardown), so only a SUCCESSFUL start
        # owes a stop() here
        await node.start()
        try:
            await stop.wait()
        finally:
            await node.stop()

    asyncio.run(run())
    return 0


# -- key / identity commands ------------------------------------------------


def cmd_gen_validator(args) -> int:
    """reference: commands/gen_validator.go — prints a fresh key
    (--key ed25519|secp256k1, matching GenFilePV's switch)."""
    from ..crypto.keys import generate_priv_key

    key_type = getattr(args, "key", None) or "ed25519"
    try:
        priv = generate_priv_key(key_type)
    except ValueError as e:
        print(str(e), file=sys.stderr)
        return 1
    out = {
        "address": priv.pub_key().address().hex().upper(),
        "pub_key": {"type": key_type, "value": priv.pub_key().bytes().hex()},
        "priv_key": {"type": key_type, "value": priv.bytes().hex()},
    }
    # tmct: ct-ok — gen_validator's documented contract IS emitting the
    # fresh private key JSON on stdout for the operator to install
    # (reference: commands/gen_validator.go prints priv_validator JSON)
    print(json.dumps(out, indent=2))
    return 0


def cmd_gen_node_key(args) -> int:
    """Write a fresh node key into --home and print its ID; refuses to
    overwrite (reference: commands/gen_node_key.go)."""
    from ..node.key import NodeKey

    cfg = _load_home(args.home)
    cfg.ensure_dirs()
    path = cfg.base.path(cfg.base.node_key_file)
    if os.path.exists(path):
        print(f"node key file already exists at {path}", file=sys.stderr)
        return 1
    nk = NodeKey(priv_key=PrivKeyEd25519.generate())
    nk.save_as(path)
    print(nk.node_id)
    return 0


def cmd_show_node_id(args) -> int:
    from ..node.key import NodeKey

    cfg = _load_home(args.home)
    nk = NodeKey.load_or_generate(cfg.base.path(cfg.base.node_key_file))
    print(nk.node_id)
    return 0


def cmd_show_validator(args) -> int:
    from ..privval import FilePV

    cfg = _load_home(args.home)
    pv = FilePV.load_or_generate(
        cfg.base.path(cfg.priv_validator.key_file),
        cfg.base.path(cfg.priv_validator.state_file),
    )
    print(
        json.dumps(
            {
                "type": pv.key.pub_key.type(),
                "value": pv.key.pub_key.bytes().hex(),
            }
        )
    )
    return 0


# -- rollback / reset (reference: commands/rollback.go, reset.go) ----------


def cmd_rollback(args) -> int:
    from ..state import StateStore
    from ..store.block_store import BlockStore
    from ..store.kv import open_db

    cfg = _load_home(args.home)
    try:
        with _ensure_node_stopped(cfg):
            db_dir = cfg.base.path(cfg.base.db_dir)
            state_db = open_db("state", cfg.base.db_backend, db_dir)
            block_db = open_db("blockstore", cfg.base.db_backend, db_dir)
            try:
                state_store = StateStore(state_db)
                block_store = BlockStore(block_db)
                new_state = state_store.rollback(block_store)
                print(
                    "rolled back state to height "
                    f"{new_state.last_block_height} "
                    f"app_hash {new_state.app_hash.hex()}"
                )
            finally:
                state_db.close()
                block_db.close()
    except RuntimeError as e:
        print(str(e), file=sys.stderr)
        return 1
    return 0


def cmd_reset_unsafe(args) -> int:
    """Remove all data, keep config + keys; reset privval state
    (reference: commands/reset.go UnsafeResetAll)."""
    cfg = _load_home(args.home)
    try:
        with _ensure_node_stopped(cfg):
            data = cfg.base.path("data")
            if os.path.isdir(data):
                shutil.rmtree(data)
            os.makedirs(data, exist_ok=True)
            os.makedirs(
                os.path.dirname(cfg.base.path(cfg.consensus.wal_file)),
                exist_ok=True,
            )
    except RuntimeError as e:
        print(str(e), file=sys.stderr)
        return 1
    print(f"removed all data in {data} (config and keys kept)")
    return 0


# -- testnet (reference: commands/testnet.go) -------------------------------


def cmd_testnet(args) -> int:
    from ..node.key import NodeKey
    from ..privval import FilePV
    from ..types.genesis import GenesisDoc, GenesisValidator

    n = args.validators
    out = os.path.expanduser(args.output_dir)
    privs = [PrivKeyEd25519.generate() for _ in range(n)]
    genesis = GenesisDoc(
        chain_id=args.chain_id or f"testnet-{os.urandom(3).hex()}",
        genesis_time_ns=time.time_ns(),
        validators=[
            GenesisValidator(pub_key=p.pub_key(), power=10) for p in privs
        ],
    )
    cfgs: List[Config] = []
    node_ids: List[str] = []
    for i in range(n):
        cfg = Config()
        cfg.base.home = os.path.join(out, f"node{i}")
        cfg.base.chain_id = genesis.chain_id
        cfg.base.moniker = f"node{i}"
        cfg.p2p.laddr = f"tcp://127.0.0.1:{args.starting_port + 2 * i}"
        cfg.rpc.laddr = f"tcp://127.0.0.1:{args.starting_port + 2 * i + 1}"
        cfg.ensure_dirs()
        genesis.save_as(cfg.base.path(cfg.base.genesis_file))
        FilePV.from_priv_key(
            privs[i],
            cfg.base.path(cfg.priv_validator.key_file),
            cfg.base.path(cfg.priv_validator.state_file),
        ).save()
        nk = NodeKey.load_or_generate(cfg.base.path(cfg.base.node_key_file))
        node_ids.append(nk.node_id)
        cfgs.append(cfg)
    for i, cfg in enumerate(cfgs):
        cfg.p2p.persistent_peers = ",".join(
            f"{node_ids[j]}@127.0.0.1:{args.starting_port + 2 * j}"
            for j in range(n)
            if j != i
        )
        write_config(cfg, _config_path(cfg.base.home))
    print(
        f"wrote {n}-validator testnet for chain {genesis.chain_id} "
        f"under {out}"
    )
    return 0


# -- light (reference: commands/light.go — verifying proxy) -----------------


def cmd_light(args) -> int:
    from ..light import Client, LightStore, TrustOptions
    from ..light.provider import HTTPProvider
    from ..rpc.jsonrpc import (
        INVALID_PARAMS,
        JSONRPCServer,
        RPCError,
    )
    from ..store.kv import open_db

    home = os.path.expanduser(args.home)
    os.makedirs(os.path.join(home, "light"), exist_ok=True)
    db = open_db("light", "sqlite", os.path.join(home, "light"))

    async def run() -> None:
        primary = HTTPProvider(args.primary)
        witnesses = [HTTPProvider(w) for w in args.witness or []]
        client = Client(
            args.chain_id,
            TrustOptions(
                period_ns=int(args.trust_period * 1e9),
                height=args.trust_height,
                hash=bytes.fromhex(args.trust_hash),
            ),
            primary,
            witnesses,
            LightStore(db),
            sequential=args.sequential,
        )

        from ..rpc.core import encode

        async def _verified(height: int):
            return await client.verify_light_block_at_height(
                height, time.time_ns()
            )

        async def route_header(req):
            h = int(req.params.get("height", 0))
            if h <= 0:
                raise RPCError(INVALID_PARAMS, "height required")
            lb = await _verified(h)
            return {"header": encode(lb.signed_header.header)}

        async def route_commit(req):
            h = int(req.params.get("height", 0))
            if h <= 0:
                raise RPCError(INVALID_PARAMS, "height required")
            lb = await _verified(h)
            return {
                "signed_header": encode(lb.signed_header),
                "canonical": True,
            }

        async def route_light_block(req):
            h = int(req.params.get("height", 0))
            if h <= 0:
                raise RPCError(INVALID_PARAMS, "height required")
            lb = await _verified(h)
            return {"height": h, "light_block": lb.to_proto().hex()}

        async def route_status(req):
            lb = client.store.latest_light_block()
            latest = lb.height if lb is not None else 0
            return {
                "chain_id": args.chain_id,
                "trusted_height": latest,
                "primary": args.primary,
                "witnesses": [w.id() for w in witnesses],
            }

        server = JSONRPCServer(
            {
                "header": route_header,
                "commit": route_commit,
                "light_block": route_light_block,
                "status": route_status,
            }
        )
        host, _, port = args.laddr.replace("tcp://", "").rpartition(":")
        await server.start(host or "127.0.0.1", int(port))
        print(
            f"light client proxy for {args.chain_id} on "
            f"{host}:{server.bound_port} (primary {args.primary})",
            flush=True,
        )
        stop = asyncio.Event()
        loop = asyncio.get_running_loop()
        for sig in (signal.SIGINT, signal.SIGTERM):
            loop.add_signal_handler(sig, stop.set)
        await stop.wait()
        await server.stop()

    try:
        asyncio.run(run())
    finally:
        db.close()
    return 0


# -- inspect (reference: internal/inspect) ----------------------------------


def cmd_inspect(args) -> int:
    """Read-only RPC over a STOPPED node's data directories."""
    cfg = _load_home(args.home)
    # hold the advisory lock for inspect's whole lifetime: a node
    # (or reset/rollback) starting mid-serve must fail fast, not
    # mutate the stores underneath us. Only the lock acquisition maps
    # to the one-line refusal; serve-time errors propagate with their
    # tracebacks.
    guard = _ensure_node_stopped(cfg)
    try:
        guard.__enter__()
    except RuntimeError as e:
        print(str(e), file=sys.stderr)
        return 1
    try:
        return _inspect_serve(cfg, args)
    finally:
        guard.__exit__(None, None, None)


def _inspect_serve(cfg: Config, args) -> int:
    from ..rpc.core import Environment
    from ..rpc.jsonrpc import JSONRPCServer
    from ..state import StateStore
    from ..state.indexer import KVSink
    from ..store.block_store import BlockStore
    from ..store.kv import open_db
    from ..types.genesis import GenesisDoc

    db_dir = cfg.base.path(cfg.base.db_dir)
    dbs = [open_db(n, cfg.base.db_backend, db_dir)
           for n in ("blockstore", "state", "tx_index")]
    genesis = None
    gpath = cfg.base.path(cfg.base.genesis_file)
    if os.path.exists(gpath):
        genesis = GenesisDoc.from_file(gpath)
    env = Environment(
        chain_id=genesis.chain_id if genesis else "",
        block_store=BlockStore(dbs[0]),
        state_store=StateStore(dbs[1]),
        genesis=genesis,
        event_sinks=[KVSink(dbs[2])],
        cfg=cfg,
    )
    read_only = {
        k: v
        for k, v in env.routes().items()
        if k
        in (
            "health", "status", "genesis", "genesis_chunked", "blockchain",
            "header", "header_by_hash", "block", "block_by_hash",
            "block_results", "commit", "validators", "consensus_params",
            "tx", "tx_search", "block_search", "light_block",
        )
    }

    async def run() -> None:
        server = JSONRPCServer(read_only)
        host, _, port = (
            args.laddr.replace("tcp://", "").rpartition(":")
        )
        await server.start(host or "127.0.0.1", int(port))
        print(
            f"inspect server on {host}:{server.bound_port} "
            f"(read-only routes: {', '.join(sorted(read_only))})",
            flush=True,
        )
        stop = asyncio.Event()
        loop = asyncio.get_running_loop()
        for sig in (signal.SIGINT, signal.SIGTERM):
            loop.add_signal_handler(sig, stop.set)
        await stop.wait()
        await server.stop()

    try:
        asyncio.run(run())
    finally:
        for db in dbs:
            db.close()
    return 0


# -- replay (reference: commands/replay.go) ---------------------------------


def cmd_replay(args) -> int:
    """Re-execute stored blocks through a fresh builtin app (sanity /
    debugging tool; reference: consensus/replay_file.go). With
    --console, drop into the interactive WAL playback console after
    block replay (reference: replay_file.go:54,188-193)."""
    from ..abci.client import local_creator
    from ..abci.kvstore import KVStoreApplication
    from ..abci.proxy import AppConns
    from ..consensus.replay import Handshaker
    from ..state import StateStore, state_from_genesis
    from ..store.block_store import BlockStore
    from ..store.kv import open_db
    from ..types.genesis import GenesisDoc

    cfg = _load_home(args.home)
    db_dir = cfg.base.path(cfg.base.db_dir)
    block_db = open_db("blockstore", cfg.base.db_backend, db_dir)
    state_db = open_db("state", cfg.base.db_backend, db_dir)
    genesis = GenesisDoc.from_file(cfg.base.path(cfg.base.genesis_file))

    async def run() -> None:
        block_store = BlockStore(block_db)
        # the node's REAL state store (the reference's
        # newConsensusStateForReplay does the same, replay_file.go:295):
        # the handshake decision table assumes state tracks the store,
        # and replays every stored block into the fresh app
        state_store = StateStore(state_db)
        state = state_store.load()
        if state is None:
            state = state_from_genesis(genesis)
            state_store.save(state)
        proxy = AppConns(local_creator(KVStoreApplication()))
        await proxy.start()
        try:
            handshaker = Handshaker(
                state_store, state, block_store, genesis
            )
            await handshaker.handshake(proxy.consensus)
            final = state_store.load()
            print(
                f"replayed {block_store.height()} blocks; final height "
                f"{final.last_block_height} app_hash "
                f"{final.app_hash.hex()}"
            )
            if getattr(args, "console", False):
                await _replay_console(cfg, final, proxy, block_store)
        finally:
            await proxy.stop()

    try:
        asyncio.run(run())
    finally:
        block_db.close()
        state_db.close()
    return 0


async def _build_replay_cs(cfg, state, proxy, block_store):
    """A ConsensusState in replay mode over the handshaken state — no
    privval, no live WAL, ticker started so scheduled timeouts are
    tracked (their firings go nowhere: the receive loop never runs;
    the console feeds recorded TimeoutInfo records instead)."""
    from ..config import MempoolConfig
    from ..consensus import ConsensusState
    from ..mempool import TxMempool
    from ..state import StateStore
    from ..state.execution import BlockExecutor
    from ..store.kv import MemKV

    state_store = StateStore(MemKV())
    state_store.save(state)
    mempool = TxMempool(proxy.mempool, MempoolConfig())
    block_exec = BlockExecutor(
        state_store, proxy.consensus, mempool, block_store=block_store
    )
    cs = ConsensusState(
        cfg.consensus, state, block_exec, block_store, privval=None,
        replay_mode=True,
    )
    await cs.ticker.start()
    return cs


def _stdin_reader_queue(loop, prompt: str = "") -> "asyncio.Queue":
    """Feed stdin lines into an asyncio.Queue from a daemon thread —
    the one sanctioned way a console coroutine reads the operator.
    Reading inline would park the event loop it shares with the
    proxy/ABCI clients (tmlive: live-block-in-main-loop); a
    default-executor hop would make asyncio.run's teardown join a
    thread still parked in input(), hanging Ctrl-C until the operator
    pressed Enter. A daemon thread is joined by nobody. EOF (or a
    loop that closed while the thread was parked) ends the stream
    with a None sentinel."""
    import threading

    lines: asyncio.Queue = asyncio.Queue()

    def _post(item) -> None:
        try:
            loop.call_soon_threadsafe(lines.put_nowait, item)
        except RuntimeError:
            pass  # loop already closed; the console is gone

    def _reader() -> None:
        while True:
            try:
                # tmlive: block-ok — dedicated stdin reader: waiting
                # for the operator is this daemon thread's whole job;
                # parking HERE is what keeps the event loop free
                raw = input(prompt)
            except Exception:  # EOFError / closed or broken stdin
                _post(None)
                return
            _post(raw)

    threading.Thread(target=_reader, daemon=True).start()
    return lines


def _console_rs(cs, field: str) -> str:
    """One rs-console view (reference: replay_file.go:259-287)."""
    rs = cs.rs
    if field == "short" or field == "":
        return f"{rs.height}/{rs.round}/{rs.step}"
    if field == "locked_round":
        return str(rs.locked_round)
    if field == "locked_block":
        return (
            rs.locked_block.hash().hex()
            if rs.locked_block is not None
            else "nil"
        )
    if field == "proposal":
        return repr(rs.proposal)
    if field == "validators":
        return "\n".join(
            f"{v.address.hex()} power={v.voting_power}"
            for v in rs.validators.validators
        )
    if field == "votes":
        out = []
        for r in range(rs.round + 1):
            pv = rs.votes.prevotes(r)
            pc = rs.votes.precommits(r)
            out.append(
                f"round {r}: prevotes={pv.bit_array() if pv else None} "
                f"precommits={pc.bit_array() if pc else None}"
            )
        return "\n".join(out)
    return f"unknown rs field {field!r}"


async def _replay_console(cfg, state, proxy, block_store) -> None:
    """Interactive WAL playback (reference: replay_file.go console:
    next [N], back [N], rs [field], n, quit). Steps the current
    height's recorded inputs one message at a time through a
    replay-mode ConsensusState; `back` rebuilds the state machine and
    replays up to count-N (the reference does the same — the state
    machine cannot run backwards)."""
    from ..consensus.wal import WAL

    wal = WAL(cfg.base.path(cfg.consensus.wal_file))
    end_height = state.last_block_height
    msgs = wal.search_for_end_height(end_height)
    if msgs is None:
        # distinct from an empty tail: the marker is absent (missing,
        # truncated, or corrupt WAL — search refuses gapped histories)
        print(
            f"cannot replay: WAL has no EndHeight({end_height}) marker "
            "(missing or corrupt WAL)"
        )
        return
    print(
        f"console: {len(msgs)} WAL records after EndHeight({end_height}); "
        "commands: next [N] | back [N] | rs [short|locked_round|"
        "locked_block|proposal|validators|votes] | n | quit"
    )
    cs = await _build_replay_cs(cfg, state, proxy, block_store)
    pos = 0

    async def apply_one() -> bool:
        nonlocal pos
        if pos >= len(msgs):
            print("end of WAL")
            return False
        m = msgs[pos]
        pos += 1
        try:
            await cs.replay_one(m)
        except RuntimeError as e:
            # e.g. an EndHeight mid-tail: store/WAL inconsistency —
            # surface it, exactly like crash catchup would
            print(f"replay error at #{pos}: {e}")
            return False
        print(f"#{pos}: {type(m).__name__} -> {_console_rs(cs, 'short')}")
        return True

    lines = _stdin_reader_queue(asyncio.get_running_loop(), prompt="> ")
    while True:
        line = await lines.get()
        if line is None:  # EOF
            break
        tokens = line.split()
        if not tokens:
            continue
        cmd, rest = tokens[0], tokens[1:]
        if cmd == "quit" or cmd == "q":
            break
        elif cmd == "next":
            count = int(rest[0]) if rest and rest[0].isdigit() else 1
            for _ in range(count):
                if not await apply_one():
                    break
        elif cmd == "back":
            count = int(rest[0]) if rest and rest[0].isdigit() else 1
            target = max(0, pos - count)
            await cs.ticker.stop()
            cs = await _build_replay_cs(cfg, state, proxy, block_store)
            pos = 0
            for _ in range(target):
                await apply_one()
            print(f"rewound to #{pos}")
        elif cmd == "rs":
            print(_console_rs(cs, rest[0] if rest else ""))
        elif cmd == "n":
            print(pos)
        else:
            print(f"unknown command {cmd!r}")
    await cs.ticker.stop()


def cmd_debug_dump(args) -> int:
    """Collect a diagnostic bundle from a node's home into a tarball:
    config, genesis, store heights + state summary, a WAL copy, and a
    live /metrics scrape when reachable (reference:
    cmd/tendermint/commands/debug/{dump,io}.go)."""
    import io
    import tarfile
    import urllib.request

    from ..state import StateStore
    from ..store.block_store import BlockStore
    from ..store.kv import open_db

    cfg = _load_home(args.home)
    out_path = os.path.expanduser(args.output)

    def add_bytes(tar, name, data: bytes):
        info = tarfile.TarInfo(name)
        info.size = len(data)
        info.mtime = int(time.time())
        tar.addfile(info, io.BytesIO(data))

    with tarfile.open(out_path, "w:gz") as tar:
        for rel in (
            "config/config.toml",
            "config/genesis.json",
        ):
            path = cfg.base.path(rel)
            if os.path.exists(path):
                tar.add(path, arcname=os.path.basename(path))
        wal_path = cfg.base.path(cfg.consensus.wal_file)
        if os.path.exists(wal_path):
            tar.add(wal_path, arcname="cs.wal")
        # rotated WAL chunks (autofile-group analog) ride along too; a
        # live node may prune a chunk between the listing and the add
        from ..consensus.wal import wal_group_files

        for chunk in wal_group_files(wal_path):
            if chunk != wal_path:
                try:
                    tar.add(
                        chunk,
                        arcname="cs.wal." + chunk.rsplit(".", 1)[-1],
                    )
                except OSError:
                    pass  # pruned mid-collection
        # store summary (opens read-only copies of the DBs)
        summary = {"collected_at": time.time()}
        try:
            db_dir = cfg.base.path(cfg.base.db_dir)
            bdb = open_db("blockstore", cfg.base.db_backend, db_dir)
            sdb = open_db("state", cfg.base.db_backend, db_dir)
            try:
                bs = BlockStore(bdb)
                st = StateStore(sdb).load()
                summary["block_store"] = {
                    "base": bs.base(),
                    "height": bs.height(),
                }
                if st is not None:
                    summary["state"] = {
                        "height": st.last_block_height,
                        "app_hash": st.app_hash.hex(),
                        "validators": st.validators.size(),
                        "chain_id": st.chain_id,
                    }
            finally:
                bdb.close()
                sdb.close()
        except Exception as e:
            summary["store_error"] = repr(e)
        # XLA profiler trace of a representative device batch
        # (SURVEY §5: the debug bundle carries device traces the way
        # the reference's carries pprof profiles)
        if getattr(args, "device_profile", False):
            try:
                summary["device_profile"] = _capture_device_profile(tar)
            except Exception as e:
                add_bytes(
                    tar, "device_profile_error.txt", repr(e).encode()
                )
        add_bytes(
            tar, "summary.json", json.dumps(summary, indent=2).encode()
        )
        # this process's span-trace ring as Chrome-trace JSON (empty
        # traceEvents when tracing was never enabled): in-process
        # embedders and the --device-profile capture above leave spans
        # here the way the reference's bundle carries pprof profiles
        from ..libs import trace as _trace

        add_bytes(tar, "trace.json", _trace.to_chrome_trace().encode())
        # SLO-breach exemplars: each slow request's span tree (empty
        # list when exemplar capture was never enabled) — the flame
        # decomposition behind a p99 outlier, see docs/load.md
        add_bytes(
            tar,
            "slow_requests.json",
            _trace.exemplars_to_json().encode(),
        )
        # consensus flight-recorder timeline (docs/observability.md):
        # the live ring over RPC when the node answers, else the WAL
        # reconstruction — a wedged/dead node still explains itself
        timeline_doc = None
        if getattr(args, "rpc_url", ""):
            try:
                # follow the seq cursor: one page is at most
                # TIMELINE_PAGE_CAP events, the resident ring holds up
                # to consensus_timeline_capacity — the bundle wants
                # all of it (page count bounded by capacity/cap + 1)
                base = args.rpc_url.rstrip("/")
                doc, cursor = None, 0
                for _ in range(64):
                    with urllib.request.urlopen(
                        f"{base}/consensus_timeline?after_seq={cursor}",
                        timeout=5,
                    ) as resp:
                        page = json.loads(resp.read())["result"]
                    if doc is None:
                        doc = page
                    else:
                        doc["events"].extend(page["events"])
                        doc["next_seq"] = page["next_seq"]
                    if not page["events"]:
                        break
                    cursor = page["next_seq"]
                if doc is not None and doc.get("events"):
                    # a disabled or just-reset ring answers with zero
                    # events — the WAL reconstruction below still has
                    # the story, so only a non-empty ring wins
                    doc["source"] = "rpc_ring"
                    timeline_doc = json.dumps(doc).encode()
            except Exception:
                timeline_doc = None  # fall through to the WAL
        if timeline_doc is None:
            try:
                from ..consensus.timeline import (
                    events_from_wal,
                    summarize_heights,
                )

                events = events_from_wal(wal_path)
                timeline_doc = json.dumps(
                    {
                        "source": "wal_reconstruction",
                        "events": events,
                        "heights": summarize_heights(events),
                    }
                ).encode()
            except Exception as e:
                timeline_doc = json.dumps(
                    {"timeline_error": repr(e)}
                ).encode()
        add_bytes(tar, "timeline.json", timeline_doc)
        # profiling plane (libs/profiler.py): the live node's
        # aggregated wall-clock samples over RPC when reachable (paged
        # under PROFILE_PAGE_CAP), else this process's own profiler
        # state — in-process embedders that profiled leave their table
        # here next to trace.json
        profile_doc = None
        if getattr(args, "rpc_url", ""):
            try:
                base = args.rpc_url.rstrip("/")
                with urllib.request.urlopen(
                    f"{base}/profile?action=status", timeout=5
                ) as resp:
                    status = json.loads(resp.read())["result"]
                stacks, cursor = [], 0
                for _ in range(64):
                    with urllib.request.urlopen(
                        f"{base}/profile?action=snapshot&after={cursor}",
                        timeout=5,
                    ) as resp:
                        page = json.loads(resp.read())["result"]
                    stacks.extend(page["stacks"])
                    if not page["stacks"]:
                        break
                    cursor = page["next"]
                if status["stats"].get("samples_total"):
                    # a never-enabled profiler answers with zero
                    # samples — the in-process fallback below may
                    # still have a table
                    profile_doc = json.dumps(
                        {
                            "source": "rpc",
                            "stats": status["stats"],
                            "subsystem_shares": status.get(
                                "subsystem_shares", {}
                            ),
                            "stacks": stacks,
                        }
                    ).encode()
            except Exception:
                profile_doc = None  # fall through to in-process
        if profile_doc is None:
            from ..libs import profiler as _profiler

            profile_doc = _profiler.to_profile_json().encode()
        add_bytes(tar, "profile.json", profile_doc)
        # live metrics scrape, best effort
        if args.metrics_url:
            try:
                with urllib.request.urlopen(
                    args.metrics_url, timeout=5
                ) as resp:
                    add_bytes(tar, "metrics.txt", resp.read())
            except Exception as e:
                add_bytes(
                    tar, "metrics_error.txt", repr(e).encode()
                )
        # live RPC scrapes (reference debug/dump.go dumpDebugData):
        # status, consensus state, net_info
        if getattr(args, "rpc_url", ""):
            for route in ("status", "consensus_state", "net_info"):
                try:
                    with urllib.request.urlopen(
                        args.rpc_url.rstrip("/") + "/" + route, timeout=5
                    ) as resp:
                        add_bytes(tar, f"{route}.json", resp.read())
                except Exception as e:
                    add_bytes(
                        tar, f"{route}_error.txt", repr(e).encode()
                    )
    print(f"wrote debug bundle to {out_path}")
    # kill variant (reference: cmd/tendermint/commands/debug/kill.go —
    # collect the bundle, THEN abort the running node so its final
    # state is captured alongside the crash)
    pid = getattr(args, "kill", 0)
    if pid:
        import signal as _signal

        os.kill(int(pid), _signal.SIGABRT)
        print(f"sent SIGABRT to pid {pid}")
    return 0


def _capture_device_profile(tar, n: int = 256) -> dict:
    """Run one warmed batch through the device verifier under the XLA
    profiler and pack the trace into the bundle (TensorBoard-loadable).
    Span tracing is on for the length of the capture, mirrored into the
    profiler's trace, so the device's events sit beside the program's
    own `pack_rows` / `device_launch` spans and not bare runtime names;
    the same spans land in the bundle's trace.json."""
    import tempfile

    import jax

    from ..crypto.ed25519 import PrivKeyEd25519
    from ..libs import trace
    from ..ops.ed25519_kernel import Ed25519Verifier

    pks, msgs, sigs = [], [], []
    for i in range(n):
        priv = PrivKeyEd25519.from_seed(i.to_bytes(4, "big") + b"\x51" * 28)
        msg = b"debug-profile-%d" % i
        pks.append(priv.pub_key().bytes())
        msgs.append(msg)
        sigs.append(priv.sign(msg))
    verifier = Ed25519Verifier()
    t0 = time.perf_counter()
    ok = verifier.verify(pks, msgs, sigs)  # warm-up compiles
    compile_s = time.perf_counter() - t0
    if not bool(ok.all()):
        raise RuntimeError("profile batch failed to verify")
    with tempfile.TemporaryDirectory(prefix="tt-device-profile-") as prof_dir:
        was_on = trace.is_enabled()
        held = trace.set_mirror(jax.profiler.TraceAnnotation)
        trace.enable()
        try:
            with jax.profiler.trace(prof_dir):
                t0 = time.perf_counter()
                with trace.span("debug_profile_batch", batch=n):
                    verifier.verify(pks, msgs, sigs)
                run_s = time.perf_counter() - t0
        finally:
            trace.set_mirror(held)
            if not was_on:
                trace.disable()
        for root, _dirs, files in os.walk(prof_dir):
            for f in files:
                full = os.path.join(root, f)
                rel = os.path.relpath(full, prof_dir)
                tar.add(
                    full, arcname=os.path.join("device_profile", rel)
                )
    return {
        "backend": jax.default_backend(),
        "batch": n,
        "warmup_s": round(compile_s, 3),
        "profiled_run_s": round(run_s, 4),
    }


def cmd_e2e(args) -> int:
    """Manifest-driven e2e testnets from the command line (reference:
    the test/e2e runner + generator binaries)."""
    from ..e2e import Manifest, generate, run_manifest

    if args.e2e_cmd == "generate":
        if args.manifest:
            print(
                "e2e generate takes no manifest argument",
                file=sys.stderr,
            )
            return 1
        out = os.path.expanduser(args.output_dir)
        os.makedirs(out, exist_ok=True)
        for i, m in enumerate(generate(seed=args.seed, count=args.count)):
            path = os.path.join(out, f"gen-{args.seed}-{i}.toml")
            with open(path, "w") as f:
                f.write(m.to_toml())
            print(path)
        return 0
    # run
    if not args.manifest:
        print("e2e run requires a manifest path", file=sys.stderr)
        return 1
    m = Manifest.from_toml(os.path.expanduser(args.manifest))
    import tempfile

    home = args.home_dir or tempfile.mkdtemp(prefix="tt-e2e-")
    mode = "processes" if args.processes else "in-process"
    print(f"running {m.chain_id}: {len(m.nodes)} nodes ({mode}) -> {home}")
    if args.processes:
        from ..e2e.process_runner import run_manifest_processes

        rep = run_manifest_processes(m, home, timeout=args.timeout)
    else:
        rep = run_manifest(m, home, timeout=args.timeout)
    print(
        json.dumps(
            {
                "ok": rep.ok,
                "reached_height": rep.reached_height,
                "blocks": rep.blocks,
                "block_interval_avg_s": round(rep.interval_avg, 3),
                "block_interval_stddev_s": round(rep.interval_stddev, 3),
                "txs_submitted": rep.txs_submitted,
                "txs_committed": rep.txs_committed,
                "evidence_heights": rep.evidence_heights,
                "state_synced": rep.state_synced,
                "failures": rep.failures,
            },
            indent=2,
        )
    )
    return 0 if rep.ok else 1


def cmd_key_migrate(args) -> int:
    """Translate legacy string-prefixed database keys to the current
    binary layout (reference: cmd/tendermint/commands/key_migrate.go +
    scripts/keymigrate/migrate.go). Resumable: already-migrated keys
    are skipped."""
    from ..store.keymigrate import CONTEXTS, migrate_db
    from ..store.kv import open_db

    cfg = _load_home(args.home)
    try:
        with _ensure_node_stopped(cfg):
            db_dir = cfg.base.path(cfg.base.db_dir)
            total = 0
            # iterate the migrator's own dispatch table so the command
            # cannot drift from it (contexts born in the current layout
            # have no entry and are not opened — open_db would create
            # stray empty database files)
            for i, ctx in enumerate(CONTEXTS):
                db = open_db(ctx, cfg.base.db_backend, db_dir)
                try:
                    n = migrate_db(db, ctx)
                finally:
                    db.close()
                print(
                    f"[{i + 1}/{len(CONTEXTS)}] {ctx}: "
                    f"{n} key(s) migrated"
                )
                total += n
            print(f"completed database migration: {total} key(s)")
    except RuntimeError as e:
        print(str(e), file=sys.stderr)
        return 1
    return 0


def cmd_version(args) -> int:
    print(_version.__version__)
    return 0


class _ensure_node_stopped:
    """Context manager for offline data-dir commands: refuse when a
    RUNNING node holds the advisory LOCK, and hold the lock ourselves
    for the command's duration so a node started mid-command fails
    fast instead of racing the same databases
    (counterpart of node.Node._acquire_data_lock)."""

    def __init__(self, cfg: Config) -> None:
        self.cfg = cfg
        self.lock = os.path.join(
            cfg.base.path(cfg.base.db_dir), "LOCK"
        )
        self._fd: int | None = None

    def __enter__(self) -> "_ensure_node_stopped":
        from ..node.node import acquire_pid_lock

        try:
            self._fd = acquire_pid_lock(self.lock)
        except RuntimeError as e:
            raise RuntimeError(
                f"node appears to be running ({e}); stop it first"
            ) from None
        return self

    def __exit__(self, *exc) -> None:
        if self._fd is not None:
            from ..node.node import release_pid_lock

            release_pid_lock(self.lock, self._fd)
            self._fd = None


def cmd_reindex_event(args) -> int:
    """Rebuild the tx/block event indexes from stored blocks and saved
    ABCI responses — recovery after index corruption or a sink config
    change (reference: cmd/tendermint/commands/reindex_event.go)."""
    cfg = _load_home(args.home)
    try:
        with _ensure_node_stopped(cfg):
            return _reindex_events(cfg, args)
    except RuntimeError as e:
        print(str(e), file=sys.stderr)
        return 1


def _reindex_events(cfg: Config, args) -> int:
    from ..state import StateStore
    from ..state.indexer import KVSink, TxResult
    from ..store.block_store import BlockStore
    from ..store.kv import open_db

    db_dir = cfg.base.path(cfg.base.db_dir)
    bdb = open_db("blockstore", cfg.base.db_backend, db_dir)
    sdb = open_db("state", cfg.base.db_backend, db_dir)
    idb = open_db("tx_index", cfg.base.db_backend, db_dir)
    try:
        bs = BlockStore(bdb)
        st = StateStore(sdb)
        sink = KVSink(idb)
        base, tip = bs.base(), bs.height()
        start = args.start_height or base
        end = args.end_height or tip
        if start < base or end > tip or start > end:
            print(
                f"invalid range [{start}, {end}]: stored blocks span "
                f"[{base}, {tip}]",
                file=sys.stderr,
            )
            return 1
        done = skipped = 0
        for height in range(start, end + 1):
            block = bs.load_block(height)
            resp = st.load_abci_responses(height)
            if block is None or resp is None:
                skipped += 1
                continue
            if len(resp.deliver_txs) != len(block.txs):
                # partial/corrupt responses: indexing a truncated zip
                # would silently drop txs while claiming success
                print(
                    f"height {height}: {len(block.txs)} txs but "
                    f"{len(resp.deliver_txs)} saved results; skipped",
                    file=sys.stderr,
                )
                skipped += 1
                continue
            events = list(
                getattr(resp.begin_block_obj, "events", ()) or ()
            )
            events += list(
                getattr(resp.end_block_obj, "events", ()) or ()
            )
            sink.index_block_events(height, events)
            results = [
                TxResult(height=height, index=i, tx=tx, result=r)
                for i, (tx, r) in enumerate(
                    zip(block.txs, resp.deliver_tx_objs)
                )
            ]
            if results:
                sink.index_tx_events(results)
            done += 1
        if done == 0:
            print(
                f"no heights reindexed in [{start}, {end}]: stored "
                "blocks or ABCI responses are missing (pruned?)",
                file=sys.stderr,
            )
            return 1
        print(
            f"reindexed {done} heights in [{start}, {end}]"
            + (f" ({skipped} skipped: missing data)" if skipped else "")
        )
        return 0
    finally:
        bdb.close()
        sdb.close()
        idb.close()


def _parse_tx(s: str) -> bytes:
    """0x-prefixed hex, else the raw string bytes (reference:
    abci/cmd/abci-cli stringOrHexToBytes)."""
    if s.startswith("0x") or s.startswith("0X"):
        return bytes.fromhex(s[2:])
    return s.encode()


async def _abci_exec(client, cmd: str, operand: str, path: str) -> None:
    """One abci-cli style request/response (reference: abci/cmd/
    abci-cli — echo/info/deliver_tx/check_tx/commit/query)."""
    from ..abci import types as T

    def show(code=None, data=None, log="", info=""):
        if code is not None:
            status = "OK" if code == 0 else f"{code}"
            print(f"-> code: {status}")
        if log:
            print(f"-> log: {log}")
        if info:
            print(f"-> info: {info}")
        if data:
            try:
                print(f"-> data: {data.decode()}")
            except UnicodeDecodeError:
                pass
            print(f"-> data.hex: 0x{data.hex().upper()}")

    if cmd == "echo":
        resp = await client.echo(operand)
        print(f"-> data: {resp.message}")
    elif cmd == "info":
        resp = await client.info(T.RequestInfo())
        print(f"-> data: {resp.data}")
        print(f"-> version: {resp.version}")
        print(f"-> last_block_height: {resp.last_block_height}")
        print(f"-> last_block_app_hash: 0x{resp.last_block_app_hash.hex()}")
    elif cmd == "deliver-tx":
        resp = await client.deliver_tx(
            T.RequestDeliverTx(tx=_parse_tx(operand))
        )
        show(resp.code, resp.data, resp.log, resp.info)
    elif cmd == "check-tx":
        resp = await client.check_tx(
            T.RequestCheckTx(tx=_parse_tx(operand))
        )
        show(resp.code, resp.data, resp.log, resp.info)
    elif cmd == "commit":
        resp = await client.commit()
        show(0, resp.data)
    elif cmd == "query":
        resp = await client.query(
            T.RequestQuery(data=_parse_tx(operand), path=path)
        )
        show(resp.code, None, resp.log, resp.info)
        print(f"-> key: {resp.key.decode(errors='replace')}")
        print(f"-> value: {resp.value.decode(errors='replace')}")
    else:
        raise ValueError(f"unknown abci command {cmd!r}")


def cmd_abci(args) -> int:
    """Drive an out-of-process ABCI application over its socket, or
    serve the builtin kvstore app (reference: abci/cmd/ — the abci-cli
    tool with its console and example-app server)."""
    from ..abci.client import SocketClient
    from ..abci.kvstore import KVStoreApplication
    from ..abci.server import SocketServer

    if args.grpc:
        from ..abci.grpc_transport import GRPCClient, GRPCServer

        make_server = GRPCServer
        make_client = GRPCClient
    else:
        make_server = SocketServer
        make_client = SocketClient

    async def serve_kvstore():
        srv = make_server(
            args.addr,
            KVStoreApplication(
                snapshot_interval=args.snapshot_interval
            ),
        )
        await srv.start()
        print(f"kvstore app listening on {args.addr}", flush=True)
        try:
            await asyncio.Event().wait()
        except (KeyboardInterrupt, asyncio.CancelledError):
            pass
        finally:
            await srv.stop()
        return 0

    async def drive():
        client = make_client(args.addr, must_connect=True)
        await client.start()
        try:
            if args.abci_cmd == "console":
                print(
                    "abci console: echo|info|deliver-tx|check-tx|"
                    "commit|query <operand>  (ctrl-d to exit)",
                    flush=True,
                )
                lines = _stdin_reader_queue(asyncio.get_running_loop())
                while True:
                    line = await lines.get()
                    if line is None:
                        break
                    parts = line.strip().split(None, 1)
                    if not parts:
                        continue
                    try:
                        await _abci_exec(
                            client,
                            parts[0],
                            parts[1] if len(parts) > 1 else "",
                            args.path,
                        )
                    except Exception as e:
                        print(f"-> error: {e}", flush=True)
            else:
                try:
                    await _abci_exec(
                        client, args.abci_cmd, args.operand, args.path
                    )
                except ValueError as e:
                    print(f"-> error: {e}", file=sys.stderr)
                    return 1
            return 0
        finally:
            await client.stop()

    if args.abci_cmd == "kvstore":
        return asyncio.run(serve_kvstore())
    return asyncio.run(drive())


# -- parser -----------------------------------------------------------------


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="tendermint_tpu",
        description="TPU-native BFT consensus node (tendermint-compatible)",
    )
    p.add_argument(
        "--home",
        default=os.environ.get("TMHOME", "~/.tendermint_tpu"),
        help="node home directory",
    )
    sub = p.add_subparsers(dest="command", required=True)

    sp = sub.add_parser("init", help="initialize a node home directory")
    sp.add_argument(
        "mode",
        nargs="?",
        default="validator",
        choices=["validator", "full", "seed"],
    )
    sp.add_argument("--chain-id", default="")
    sp.add_argument("--moniker", default="anonymous")
    sp.add_argument(
        "--key",
        default="ed25519",
        choices=["ed25519", "secp256k1"],
        help="validator key type (reference: commands/init.go --key)",
    )
    sp.set_defaults(fn=cmd_init)

    sp = sub.add_parser("start", help="run the node")
    sp.add_argument("--moniker", default="")
    sp.set_defaults(fn=cmd_start)

    sp = sub.add_parser(
        "signer",
        help="run an external signing process (dials a node's "
        "[priv_validator] listen_addr, serves this home's FilePV)",
    )
    sp.add_argument(
        "--addr",
        default="tcp://127.0.0.1:26659",
        help="socket mode: the node's priv_validator listen address "
        "to DIAL; --grpc mode: the local address this signer LISTENS "
        "on (the node dials grpc://<this>)",
    )
    sp.add_argument(
        "--node-id",
        default="",
        help="socket mode only: expected node identity for the "
        "SecretConnection (empty = accept any)",
    )
    sp.add_argument(
        "--grpc",
        action="store_true",
        help="use the gRPC privval transport instead of the socket one",
    )
    sp.set_defaults(fn=cmd_signer)

    sp = sub.add_parser("gen-validator", help="print a fresh validator key")
    sp.add_argument(
        "--key",
        default="ed25519",
        choices=["ed25519", "secp256k1"],
        help="key type (reference: commands/gen_validator.go --key)",
    )
    sp.set_defaults(fn=cmd_gen_validator)

    sp = sub.add_parser("gen-node-key", help="generate a node key")
    sp.set_defaults(fn=cmd_gen_node_key)

    sp = sub.add_parser("show-node-id", help="print this node's p2p ID")
    sp.set_defaults(fn=cmd_show_node_id)

    sp = sub.add_parser(
        "show-validator", help="print this node's validator pubkey"
    )
    sp.set_defaults(fn=cmd_show_validator)

    sp = sub.add_parser(
        "rollback", help="rewind state one height (after an app hash panic)"
    )
    sp.set_defaults(fn=cmd_rollback)

    sp = sub.add_parser(
        "unsafe-reset-all", help="wipe data, keep config and keys"
    )
    sp.set_defaults(fn=cmd_reset_unsafe)

    sp = sub.add_parser("testnet", help="write N-validator testnet homes")
    sp.add_argument("--validators", "-v", type=int, default=4)
    sp.add_argument("--output-dir", "-o", default="./testnet")
    sp.add_argument("--chain-id", default="")
    sp.add_argument("--starting-port", type=int, default=26656)
    sp.set_defaults(fn=cmd_testnet)

    sp = sub.add_parser(
        "reindex-event",
        help="rebuild tx/block event indexes from stored blocks",
    )
    sp.add_argument("--start-height", type=int, default=0)
    sp.add_argument("--end-height", type=int, default=0)
    sp.set_defaults(fn=cmd_reindex_event)

    sp = sub.add_parser(
        "abci",
        help="abci-cli: drive an ABCI app socket or serve the kvstore",
    )
    sp.add_argument(
        "abci_cmd",
        choices=[
            "kvstore",
            "console",
            "echo",
            "info",
            "deliver-tx",
            "check-tx",
            "commit",
            "query",
        ],
    )
    sp.add_argument("operand", nargs="?", default="")
    sp.add_argument("--addr", default="tcp://127.0.0.1:26658")
    sp.add_argument("--path", default="/store", help="query path")
    sp.add_argument(
        "--snapshot-interval",
        type=int,
        default=0,
        help="kvstore server: take a state snapshot every N heights "
        "(0 disables; needed for state-sync providers)",
    )
    sp.add_argument(
        "--grpc",
        action="store_true",
        help="use the gRPC ABCI transport instead of the socket one",
    )
    sp.set_defaults(fn=cmd_abci)

    sp = sub.add_parser(
        "light", help="run a verifying light-client RPC proxy"
    )
    sp.add_argument("chain_id")
    sp.add_argument("--primary", required=True, help="full node RPC addr")
    sp.add_argument(
        "--witness", action="append", help="witness RPC addr (repeatable)"
    )
    sp.add_argument("--trust-height", type=int, required=True)
    sp.add_argument("--trust-hash", required=True)
    sp.add_argument(
        "--trust-period", type=float, default=168 * 3600.0, help="seconds"
    )
    sp.add_argument("--sequential", action="store_true")
    sp.add_argument("--laddr", default="tcp://127.0.0.1:8888")
    sp.set_defaults(fn=cmd_light)

    sp = sub.add_parser(
        "inspect", help="read-only RPC over a stopped node's data"
    )
    sp.add_argument("--laddr", default="tcp://127.0.0.1:26657")
    sp.set_defaults(fn=cmd_inspect)

    sp = sub.add_parser(
        "replay", help="re-execute stored blocks through a fresh app"
    )
    sp.add_argument(
        "--console",
        action="store_true",
        help="interactive WAL playback after block replay "
        "(next/back/rs/n/quit)",
    )
    sp.set_defaults(fn=cmd_replay)

    sp = sub.add_parser(
        "debug", help="collect a diagnostic bundle into a tarball"
    )
    sp.add_argument("--output", "-o", default="./debug_bundle.tar.gz")
    sp.add_argument(
        "--device-profile",
        action="store_true",
        dest="device_profile",
        help="include an XLA profiler trace of a device verify batch",
    )
    sp.add_argument(
        "--metrics-url",
        default="",
        help="live /metrics endpoint to scrape into the bundle",
    )
    sp.add_argument(
        "--rpc-url",
        default="",
        dest="rpc_url",
        help="live RPC endpoint: status/consensus_state/net_info "
        "scraped into the bundle",
    )
    sp.add_argument(
        "--kill",
        type=int,
        default=0,
        help="after collecting the bundle, SIGABRT this node pid "
        "(the reference's `debug kill`)",
    )
    sp.set_defaults(fn=cmd_debug_dump)

    sp = sub.add_parser(
        "e2e", help="run or generate manifest-driven e2e testnets"
    )
    sp.add_argument("e2e_cmd", choices=["run", "generate"])
    sp.add_argument("manifest", nargs="?", default="")
    sp.add_argument("--home-dir", default="")
    sp.add_argument("--timeout", type=float, default=240.0)
    sp.add_argument("--seed", type=int, default=1)
    sp.add_argument("--count", type=int, default=4)
    sp.add_argument("--output-dir", "-o", default="./e2e-manifests")
    sp.add_argument(
        "--processes",
        action="store_true",
        help="run each node as a separate OS process over TCP with a "
        "socket ABCI app; perturbations use real signals "
        "(SIGKILL/SIGSTOP)",
    )
    sp.set_defaults(fn=cmd_e2e)

    sp = sub.add_parser(
        "key-migrate",
        help="migrate legacy database key formats to the current layout",
    )
    sp.set_defaults(fn=cmd_key_migrate)

    sp = sub.add_parser("version", help="print the version")
    sp.set_defaults(fn=cmd_version)

    return p


def main(argv: Optional[List[str]] = None) -> int:
    args = build_parser().parse_args(argv)
    return args.fn(args)
