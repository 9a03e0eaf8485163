"""Operator CLI tests (reference model: cmd/tendermint/commands/*_test.go).

Drives the argparse surface exactly as an operator would: init a home,
start a node briefly, roll back, build a testnet, and run the verifying
light proxy against a live node.
"""

import asyncio
import json
import os
import signal
import subprocess
import sys
import time

import pytest

from tendermint_tpu.cmd import main as cli_main
from tendermint_tpu.config import load_config

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_cli(*argv) -> int:
    return cli_main(list(argv))


def test_init_writes_home(tmp_path, capsys):
    home = str(tmp_path / "home")
    assert run_cli("--home", home, "init", "validator",
                   "--chain-id", "cli-chain") == 0
    for rel in (
        "config/config.toml",
        "config/genesis.json",
        "config/node_key.json",
        "config/priv_validator_key.json",
    ):
        assert os.path.exists(os.path.join(home, rel)), rel
    cfg = load_config(os.path.join(home, "config", "config.toml"))
    assert cfg.base.chain_id == "cli-chain"
    assert cfg.base.mode == "validator"
    # idempotent: a second init keeps the genesis
    assert run_cli("--home", home, "init", "validator") == 0
    cfg2 = load_config(os.path.join(home, "config", "config.toml"))
    assert cfg2.base.chain_id == "cli-chain"


def test_key_commands(tmp_path, capsys):
    home = str(tmp_path / "home")
    assert run_cli("--home", home, "init", "validator") == 0
    capsys.readouterr()
    assert run_cli("--home", home, "show-node-id") == 0
    node_id = capsys.readouterr().out.strip()
    assert len(node_id) == 40
    assert run_cli("--home", home, "show-validator") == 0
    val = json.loads(capsys.readouterr().out)
    assert val["type"] == "ed25519" and len(val["value"]) == 64
    assert run_cli("gen-validator") == 0
    gv = json.loads(capsys.readouterr().out)
    assert len(gv["priv_key"]["value"]) in (64, 128)
    assert run_cli("version") == 0
    assert capsys.readouterr().out.strip()


def test_gen_validator_secp256k1(capsys):
    """reference: commands/gen_validator.go --key — secp256k1 is
    first-class through the native backend (the PR-1 shim raised
    here), and the emitted key actually signs/verifies."""
    from tendermint_tpu.crypto.keys import (
        privkey_from_type_and_bytes,
        pubkey_from_type_and_bytes,
    )

    assert run_cli("gen-validator", "--key", "secp256k1") == 0
    gv = json.loads(capsys.readouterr().out)
    assert gv["priv_key"]["type"] == "secp256k1"
    assert len(gv["pub_key"]["value"]) == 66  # 33-byte compressed point
    assert len(gv["priv_key"]["value"]) == 64
    priv = privkey_from_type_and_bytes(
        "secp256k1", bytes.fromhex(gv["priv_key"]["value"])
    )
    pub = pubkey_from_type_and_bytes(
        "secp256k1", bytes.fromhex(gv["pub_key"]["value"])
    )
    assert priv.pub_key() == pub
    assert pub.address().hex().upper() == gv["address"]
    sig = priv.sign(b"cli keygen smoke")
    assert pub.verify_signature(b"cli keygen smoke", sig)
    # unknown types exit 1 through the argparse choices/ValueError path
    assert run_cli("gen-validator", "--key", "ed25519") == 0
    capsys.readouterr()


def test_testnet_layout(tmp_path, capsys):
    out = str(tmp_path / "net")
    assert run_cli("testnet", "-v", "3", "-o", out,
                   "--chain-id", "net-chain", "--starting-port", "30000") == 0
    genesis_hashes = set()
    for i in range(3):
        home = os.path.join(out, f"node{i}")
        cfg = load_config(os.path.join(home, "config", "config.toml"))
        assert cfg.base.chain_id == "net-chain"
        # fully meshed persistent peers
        assert cfg.p2p.persistent_peers.count("@") == 2
        with open(os.path.join(home, "config", "genesis.json")) as f:
            genesis_hashes.add(f.read())
    assert len(genesis_hashes) == 1  # identical genesis across homes


def test_start_runs_and_produces_blocks(tmp_path):
    """`start` in a subprocess: SIGTERM stops it cleanly; a restart plus
    `rollback` exercises the recovery surface."""
    home = str(tmp_path / "home")
    assert run_cli("--home", home, "init", "validator",
                   "--chain-id", "start-chain") == 0
    # speed up consensus + free RPC port
    cfg_path = os.path.join(home, "config", "config.toml")
    cfg = load_config(cfg_path)
    cfg.consensus.timeout_commit = 0.2
    cfg.rpc.laddr = "tcp://127.0.0.1:0"
    cfg.p2p.laddr = "tcp://127.0.0.1:0"  # a free port, not 26656
    from tendermint_tpu.config import write_config

    write_config(cfg, cfg_path)

    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.Popen(
        [sys.executable, "-m", "tendermint_tpu.cmd",
         "--home", home, "start"],
        env=env,
        stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT,
        cwd=REPO,
    )
    try:
        deadline = time.time() + 90
        from tendermint_tpu.state import StateStore
        from tendermint_tpu.store.kv import open_db

        height = 0
        while time.time() < deadline and height < 2:
            time.sleep(2.0)
            try:
                db = open_db("state", "sqlite", os.path.join(home, "data"))
                st = StateStore(db).load()
                height = st.last_block_height if st else 0
                db.close()
            except Exception:
                pass
        assert height >= 2, "node produced no blocks"
    finally:
        proc.send_signal(signal.SIGTERM)
        try:
            proc.wait(timeout=30)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait()
    assert proc.returncode == 0

    # rollback rewinds one height
    import io
    from contextlib import redirect_stdout

    buf = io.StringIO()
    with redirect_stdout(buf):
        assert run_cli("--home", home, "rollback") == 0
    assert "rolled back state to height" in buf.getvalue()

    # unsafe-reset-all clears data but keeps keys
    with redirect_stdout(buf):
        assert run_cli("--home", home, "unsafe-reset-all") == 0
    assert os.path.exists(
        os.path.join(home, "config", "priv_validator_key.json")
    )
    assert not os.path.exists(
        os.path.join(home, "data", "state.sqlite")
    )


def test_debug_bundle(tmp_path, capsys):
    """`debug` collects config/genesis/WAL/store summary after a run
    (reference: commands/debug/dump.go)."""
    import asyncio as aio
    import tarfile

    home = str(tmp_path / "dbg")
    assert run_cli("--home", home, "init", "validator",
                   "--chain-id", "dbg-chain") == 0
    # produce a little history in-process
    from tendermint_tpu.node import make_node
    from tendermint_tpu.config import load_config, write_config

    cfg_path = os.path.join(home, "config", "config.toml")
    cfg = load_config(cfg_path)
    cfg.consensus.timeout_commit = 0.2
    cfg.rpc.laddr = "tcp://127.0.0.1:0"
    cfg.p2p.laddr = "tcp://127.0.0.1:0"  # a free port, not 26656
    write_config(cfg, cfg_path)

    async def produce():
        cfg2 = load_config(cfg_path)
        cfg2.base.home = home
        node = make_node(cfg2)
        await node.start()
        try:
            await node.consensus.wait_for_height(3, timeout=60.0)
        finally:
            await node.stop()

    aio.run(produce())
    out = str(tmp_path / "bundle.tar.gz")
    assert run_cli("--home", home, "debug", "-o", out) == 0
    with tarfile.open(out) as tar:
        names = tar.getnames()
        assert "config.toml" in names
        assert "genesis.json" in names
        assert "summary.json" in names
        assert "cs.wal" in names
        # span-trace ring rides along as valid Chrome-trace JSON
        assert "trace.json" in names
        chrome = json.loads(tar.extractfile("trace.json").read())
        assert "traceEvents" in chrome
        summary = json.loads(
            tar.extractfile("summary.json").read()
        )
        assert summary["block_store"]["height"] >= 2
        assert summary["state"]["chain_id"] == "dbg-chain"


def test_replay_console(tmp_path, monkeypatch, capsys):
    """`replay --console` steps the current height's WAL records one
    at a time with next/back/rs/n (reference: replay_file.go console,
    :54,188-193)."""
    import asyncio as aio

    home = str(tmp_path / "rc")
    assert run_cli("--home", home, "init", "validator",
                   "--chain-id", "rc-chain") == 0
    from tendermint_tpu.config import load_config, write_config
    from tendermint_tpu.node import make_node

    cfg_path = os.path.join(home, "config", "config.toml")
    cfg = load_config(cfg_path)
    cfg.consensus.timeout_commit = 0.2
    cfg.rpc.laddr = "tcp://127.0.0.1:0"
    cfg.p2p.laddr = "tcp://127.0.0.1:0"  # a free port, not 26656
    write_config(cfg, cfg_path)

    async def produce():
        cfg2 = load_config(cfg_path)
        cfg2.base.home = home
        node = make_node(cfg2)
        await node.start()
        try:
            await node.consensus.wait_for_height(3, timeout=60.0)
        finally:
            await node.stop()

    aio.run(produce())

    script = iter(
        ["n", "next 3", "rs", "rs locked_round", "back 1", "n", "quit"]
    )
    monkeypatch.setattr(
        "builtins.input", lambda prompt="": next(script)
    )
    assert run_cli("--home", home, "replay", "--console") == 0
    out = capsys.readouterr().out
    assert "console:" in out
    assert "WAL records after EndHeight" in out
    # rs short prints height/round/step
    import re

    assert re.search(r"^\d+/\d+/\d+$", out, re.M), out
    assert "rewound to" in out


def test_debug_kill(tmp_path):
    """`debug --kill PID` collects the bundle then SIGABRTs the target
    (reference: cmd/tendermint/commands/debug/kill.go)."""
    import signal as sig
    import subprocess as sp
    import tarfile

    home = str(tmp_path / "dk")
    assert run_cli("--home", home, "init", "validator",
                   "--chain-id", "dk-chain") == 0
    victim = sp.Popen(
        [sys.executable, "-c", "import time; time.sleep(60)"]
    )
    try:
        out = str(tmp_path / "kill_bundle.tar.gz")
        assert run_cli(
            "--home", home, "debug", "-o", out, "--kill", str(victim.pid)
        ) == 0
        victim.wait(timeout=10)
        assert victim.returncode == -sig.SIGABRT
        with tarfile.open(out) as tar:
            assert "config.toml" in tar.getnames()
    finally:
        if victim.poll() is None:
            victim.terminate()
            victim.wait()


def test_debug_bundle_device_profile(tmp_path):
    """`debug --device-profile` packs an XLA profiler trace of a
    verify batch into the bundle (SURVEY §5 device-trace analog of the
    reference's pprof collection)."""
    import tarfile

    home = str(tmp_path / "dbgp")
    assert run_cli("--home", home, "init", "validator",
                   "--chain-id", "dbgp-chain") == 0
    out = str(tmp_path / "bundle_prof.tar.gz")
    assert run_cli(
        "--home", home, "debug", "-o", out, "--device-profile"
    ) == 0
    with tarfile.open(out) as tar:
        names = tar.getnames()
        assert "summary.json" in names
        summary = json.loads(tar.extractfile("summary.json").read())
        assert "device_profile_error.txt" not in names, names
        prof = summary["device_profile"]
        assert prof["batch"] == 256 and prof["profiled_run_s"] > 0
        assert any(n.startswith("device_profile/") for n in names), (
            names
        )


def test_device_profile_carries_the_programs_spans(tmp_path):
    """The capture turns span tracing on for its own length, mirrored
    into the profiler's trace: the xplane file names the program's
    phases around the runtime's events, the same spans are in the ring
    the bundle exports as trace.json, and the recorder is left as it
    was found."""
    import tarfile

    from jax.profiler import ProfileData

    from tendermint_tpu.cmd import commands
    from tendermint_tpu.libs import trace

    trace.disable()
    trace.reset()
    trace.set_mirror(None)
    out = str(tmp_path / "profile_only.tar")
    with tarfile.open(out, "w") as tar:
        summary = commands._capture_device_profile(tar, n=8)
    assert summary["batch"] == 8
    assert not trace.is_enabled() and trace.set_mirror(None) is None
    names = [s.name for s in trace.snapshot()]
    for phase in ("pack_rows", "device_launch", "debug_profile_batch"):
        assert phase in names, names
    trace.reset()
    with tarfile.open(out) as tar:
        (member,) = [
            m for m in tar.getmembers() if m.name.endswith(".xplane.pb")
        ]
        assert member.name.startswith("device_profile/")
        data = tar.extractfile(member).read()
    host = next(
        p for p in ProfileData.from_serialized_xspace(data).planes
        if p.name == "/host:CPU"
    )
    seen = {e.name for line in host.lines for e in line.events}
    assert {"debug_profile_batch", "pack_rows", "device_launch"} <= seen


def test_light_proxy_serves_verified_headers(tmp_path):
    """Boot a full node in-process, run the light proxy logic against
    its RPC, and fetch a verified header through the proxy surface
    (reference: commands/light.go)."""
    from tendermint_tpu.config import Config
    from tendermint_tpu.crypto.ed25519 import PrivKeyEd25519
    from tendermint_tpu.light import Client, LightStore, TrustOptions
    from tendermint_tpu.light.provider import HTTPProvider
    from tendermint_tpu.node import make_node
    from tendermint_tpu.privval import FilePV
    from tendermint_tpu.rpc import HTTPClient
    from tendermint_tpu.store.kv import MemKV
    from tendermint_tpu.types.genesis import GenesisDoc, GenesisValidator

    async def go():
        priv = PrivKeyEd25519.from_seed(b"\x31" * 32)
        genesis = GenesisDoc(
            chain_id="light-cli",
            genesis_time_ns=time.time_ns(),
            validators=[GenesisValidator(pub_key=priv.pub_key(), power=5)],
        )
        cfg = Config()
        cfg.base.home = str(tmp_path / "full")
        cfg.base.chain_id = "light-cli"
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
        node = make_node(cfg)
        await node.start()
        try:
            await node.consensus.wait_for_height(4, timeout=60.0)
            addr = f"127.0.0.1:{node.rpc_server.bound_port}"
            # trust root = block 1 via the HTTP provider
            provider = HTTPProvider(addr)
            lb1 = await provider.light_block(1)
            client = Client(
                "light-cli",
                TrustOptions(
                    period_ns=10**18,
                    height=1,
                    hash=lb1.signed_header.hash(),
                ),
                provider,
                [],
                LightStore(MemKV()),
            )
            lb3 = await client.verify_light_block_at_height(
                3, time.time_ns()
            )
            want = node.block_store.load_block(3).hash()
            assert lb3.signed_header.header.hash() == want
        finally:
            await node.stop()

    asyncio.run(go())


def test_abci_cli_against_kvstore_socket(tmp_path, capsys):
    """abci-cli parity: serve the kvstore over a socket (one process),
    drive echo/deliver-tx/commit/query through the `abci` subcommands
    (reference: abci/cmd/ abci-cli + example kvstore server)."""
    import socket
    import subprocess
    import sys as _sys
    import time as _time

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    addr = f"tcp://127.0.0.1:{port}"
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.dirname(os.path.dirname(__file__))
    srv = subprocess.Popen(
        [_sys.executable, "-m", "tendermint_tpu.cmd", "abci",
         "kvstore", "--addr", addr],
        stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT,
        env=env,
        text=True,
    )
    try:
        line = srv.stdout.readline()
        assert "listening" in line, line
        assert run_cli("abci", "echo", "ping", "--addr", addr) == 0
        assert "-> data: ping" in capsys.readouterr().out
        assert run_cli(
            "abci", "deliver-tx", "name=satoshi", "--addr", addr
        ) == 0
        assert "-> code: OK" in capsys.readouterr().out
        assert run_cli("abci", "commit", "--addr", addr) == 0
        capsys.readouterr()
        assert run_cli("abci", "query", "name", "--addr", addr) == 0
        out = capsys.readouterr().out
        assert "-> value: satoshi" in out
        assert run_cli("abci", "info", "--addr", addr) == 0
        assert "last_block_height" in capsys.readouterr().out
    finally:
        srv.terminate()
        try:
            srv.wait(timeout=10)
        except subprocess.TimeoutExpired:
            srv.kill()


def test_reindex_event_rebuilds_tx_index(tmp_path, capsys):
    """`reindex-event` repopulates a wiped tx/block index from stored
    blocks + ABCI responses (reference: commands/reindex_event.go)."""
    import asyncio as aio

    home = str(tmp_path / "reidx")
    assert run_cli("--home", home, "init", "validator",
                   "--chain-id", "reidx-chain") == 0
    from tendermint_tpu.config import load_config, write_config
    from tendermint_tpu.node import make_node

    cfg_path = os.path.join(home, "config", "config.toml")
    cfg = load_config(cfg_path)
    cfg.consensus.timeout_commit = 0.2
    cfg.rpc.laddr = "tcp://127.0.0.1:0"
    cfg.p2p.laddr = "tcp://127.0.0.1:0"  # a free port, not 26656
    cfg.base.db_backend = "sqlite"
    write_config(cfg, cfg_path)

    tx = b"reindex=me"

    async def produce():
        cfg2 = load_config(cfg_path)
        cfg2.base.home = home
        node = make_node(cfg2)
        await node.start()
        try:
            await node.consensus.wait_for_height(2, timeout=60.0)
            await node.mempool.check_tx(tx)
            tip = node.block_store.height()
            await node.consensus.wait_for_height(tip + 2, timeout=60.0)
        finally:
            await node.stop()

    aio.run(produce())

    # wipe the index, then rebuild it
    import glob

    for f in glob.glob(os.path.join(home, "data", "tx_index*")):
        os.remove(f)
    assert run_cli("--home", home, "reindex-event") == 0
    out = capsys.readouterr().out
    assert "reindexed" in out

    from tendermint_tpu.state.indexer import KVSink
    from tendermint_tpu.store.kv import open_db
    from tendermint_tpu.types.tx import tx_hash

    idb = open_db("tx_index", "sqlite", os.path.join(home, "data"))
    try:
        sink = KVSink(idb)
        got = sink.get_tx_by_hash(tx_hash(tx))
        assert got is not None and got.tx == tx
        assert sink.has_block(2)
    finally:
        idb.close()


def test_offline_commands_refuse_running_node(tmp_path, capsys):
    """reindex-event/rollback/unsafe-reset-all check the advisory data
    LOCK so they cannot race a live node's databases."""
    import subprocess
    import sys as _sys

    home = str(tmp_path / "locked")
    assert run_cli("--home", home, "init", "validator",
                   "--chain-id", "lock-chain") == 0
    lock_dir = os.path.join(home, "data")
    os.makedirs(lock_dir, exist_ok=True)
    lock = os.path.join(lock_dir, "LOCK")

    # a live foreign pid holds the lock -> refused
    other = subprocess.Popen([_sys.executable, "-c", "import time; time.sleep(30)"])
    try:
        with open(lock, "w") as f:
            f.write(str(other.pid))
        assert run_cli("--home", home, "reindex-event") == 1
        assert run_cli("--home", home, "rollback") == 1
        assert run_cli("--home", home, "unsafe-reset-all") == 1
    finally:
        other.kill()
        other.wait()

    # dead pid -> stale lock, command proceeds past the guard
    with open(lock, "w") as f:
        f.write(str(other.pid))  # now dead
    assert run_cli("--home", home, "unsafe-reset-all") == 0


def test_e2e_cli_generate_and_run(tmp_path, capsys):
    """`e2e generate` writes TOML manifests the parser accepts;
    `e2e run` executes one and reports the invariant results
    (reference: the standalone test/e2e runner + generator)."""
    out = str(tmp_path / "manifests")
    assert run_cli("e2e", "generate", "--seed", "2", "--count", "2",
                   "-o", out) == 0
    paths = sorted(
        os.path.join(out, f) for f in os.listdir(out)
    )
    assert len(paths) == 2
    # round-trip: generated TOML parses back into a valid manifest
    from tendermint_tpu.e2e import Manifest

    manifests = [Manifest.from_toml(p) for p in paths]
    for m in manifests:
        m.validate()
    # pick a small one to actually run
    small = min(
        zip(paths, manifests),
        key=lambda pm: (len(pm[1].nodes), pm[1].target_height),
    )[0]
    capsys.readouterr()
    rc = run_cli("e2e", "run", small,
                 "--home-dir", str(tmp_path / "net"),
                 "--timeout", "180")
    out_text = capsys.readouterr().out
    assert rc == 0, out_text
    report = json.loads(out_text[out_text.index("{"):])
    assert report["ok"] and report["reached_height"] >= 3


def test_key_migrate_translates_legacy_layout(tmp_path, capsys):
    """`key-migrate` rewrites the reference's v0.34-style ASCII keys
    (H:/P:/C:/SC:/BH:, stateKey/validatorsKey:…) into the current
    binary-prefix layout, after which BlockStore/StateStore read the
    data (reference: scripts/keymigrate/migrate.go). Re-running is a
    no-op (resumable contract)."""
    import struct

    from tendermint_tpu.state.store import StateStore
    from tendermint_tpu.store.block_store import BlockStore
    from tendermint_tpu.store.kv import open_db

    from tests.test_store import make_chain_block

    home = str(tmp_path / "legacy")
    assert run_cli("--home", home, "init", "validator",
                   "--chain-id", "mig-chain") == 0
    cfg = load_config(os.path.join(home, "config", "config.toml"))
    cfg_db_dir = cfg.base.path(cfg.base.db_dir)

    # build canonical encodings with the CURRENT store, then rewrite
    # the db into the legacy key layout
    block = make_chain_block(3)
    parts = block.make_part_set()
    from tendermint_tpu.types import BlockID, Commit, CommitSig
    from tendermint_tpu.types.block_id import PartSetHeader
    from tendermint_tpu.types.block_meta import BlockMeta

    meta = BlockMeta.from_block(block, len(block.to_proto()))
    seen = Commit(
        height=3,
        round=0,
        block_id=BlockID(hash=block.hash(),
                         part_set_header=parts.header()),
        signatures=[CommitSig.absent()],
    )
    db = open_db("blockstore", "sqlite", cfg_db_dir)
    db.set(b"H:3", meta.to_proto())
    for i in range(parts.header().total):
        db.set(b"P:3:%d" % i, parts.get_part(i).to_proto())
    db.set(b"C:2", block.last_commit.to_proto())
    db.set(b"SC:2", seen.to_proto())  # superseded by SC:3
    db.set(b"SC:3", seen.to_proto())
    db.set(b"BH:" + block.hash().hex().encode(), b"3")
    db.close()

    assert run_cli("--home", home, "key-migrate") == 0
    out = capsys.readouterr().out
    assert "blockstore" in out and "completed database migration" in out

    db = open_db("blockstore", "sqlite", cfg_db_dir)
    try:
        bs = BlockStore(db)
        assert bs.height() == 3
        got = bs.load_block(3)
        assert got is not None and got.hash() == block.hash()
        assert bs.load_block_meta_by_hash(block.hash()).header.height == 3
        assert bs.load_seen_commit().height == 3
        # legacy keys are gone
        assert db.get(b"H:3") is None and db.get(b"SC:2") is None
    finally:
        db.close()

    # second run: nothing legacy left
    assert run_cli("--home", home, "key-migrate") == 0
    assert "completed database migration: 0 key(s)" in capsys.readouterr().out
