"""Real-process e2e: separate OS processes, TCP p2p, socket ABCI,
real signals (reference: test/e2e/runner/perturb.go:43-77).

These spawn actual `python -m tendermint_tpu.cmd start` subprocesses —
minutes, not seconds — so they carry the slow marker. They are the
only tests where SIGKILL'd-for-real WAL recovery and ABCI handshake
replay against a surviving app process are exercised end-to-end.
"""

import asyncio
import os
import signal

import pytest

from tendermint_tpu.e2e.manifest import Manifest
from tendermint_tpu.e2e.process_runner import ProcessRunner


def run(coro):
    return asyncio.run(coro)


@pytest.mark.slow
def test_process_net_converges(tmp_path):
    """A 2-validator process net reaches its target height; invariants
    (hash agreement over RPC) and the block-interval benchmark hold."""
    m = Manifest(
        chain_id="proc-ci",
        validators={"v0": 10, "v1": 10},
        target_height=4,
    )
    m.validate()
    rep = run(ProcessRunner(m, str(tmp_path), timeout=240.0).run())
    assert rep.ok, rep.failures
    assert rep.reached_height >= 4
    assert rep.blocks >= 3


@pytest.mark.slow
def test_process_net_sigkill_recovery(tmp_path):
    """SIGKILL one of four validators mid-run: the dead process's WAL
    and sqlite stores are reopened by a fresh process, the ABCI
    handshake replays against the still-running app, and the network
    converges with no fork (the crash path the in-process runner
    cannot exercise).

    History: this test stalled on the seed (the restarted validator
    wedged at its boot height while the net ran ~270 heights ahead).
    Root cause — diagnosed with tmlive's thread-root/reachability
    substrate and debug-level process logs — was NOT a blocking site
    but catchup-vote loss: the reborn node announces its height while
    its consensus reactor is still in wait_sync (blocksync grace), the
    peers stream the stored-commit precommits into the void and mark
    them delivered, and nothing ever resends. Fixed by the gossip-votes
    stall-reset in consensus/reactor.py (`vote_catchup_stall`); the
    deterministic regression lives at tests/test_reactors.py::
    test_catchup_votes_dropped_during_wait_sync_are_resent."""
    m = Manifest.parse(
        {
            "chain_id": "proc-kill-ci",
            "target_height": 5,
            "validators": {"v0": 10, "v1": 10, "v2": 10, "v3": 10},
            "node": {"v1": {"perturb": ["kill:2"]}},
            "load": {"tx_rate": 1, "tx_size": 48},
        }
    )
    m.validate()
    runner = ProcessRunner(m, str(tmp_path), timeout=340.0)
    rep = run(runner.run())
    assert rep.ok, rep.failures
    assert rep.reached_height >= 5
    # the kill really happened: the first node process is dead and a
    # different pid carried the node to the end
    log = open(
        os.path.join(str(tmp_path), "v1", "node.log"), "rb"
    ).read()
    # "completed ABCI handshake" appears exactly once per successful
    # boot (replay.py) — two completions prove the post-SIGKILL
    # process really re-handshook ("ABCI handshake" alone would match
    # twice in a single boot)
    assert log.count(b"completed ABCI handshake") >= 2, (
        "expected a second completed handshake from the post-SIGKILL "
        "process"
    )
    assert rep.txs_submitted > 0 and rep.txs_committed > 0


@pytest.mark.slow
def test_process_net_partition_heal_during_catchup(tmp_path):
    """ISSUE 13: the PR-9 wedge class under REAL faults — SIGKILL one
    of four validators, then cut the reborn process off mid-catchup
    with a genuine p2p-level partition (TM_TPU_PARTITION_FILE: every
    child polls the shared spec file; its links drop every frame while
    the process keeps running and serving RPC), then heal. The
    surviving 3/4 majority must keep committing through the partition,
    and after heal the victim must converge to the target with no fork
    — which exercises both the catchup stall-reset (PR 9) and the
    live-height gossip stall-reset (this PR) against marks that lied
    because frames died on a surviving connection."""
    m = Manifest.parse(
        {
            "chain_id": "proc-part-ci",
            "target_height": 10,
            "validators": {"v0": 10, "v1": 10, "v2": 10, "v3": 10},
            "node": {
                "v1": {"perturb": ["kill:2", "partition:4", "heal:8"]}
            },
            "load": {"tx_rate": 1, "tx_size": 48},
        }
    )
    m.validate()
    runner = ProcessRunner(m, str(tmp_path), timeout=340.0)
    rep = run(runner.run())
    assert rep.ok, rep.failures
    assert rep.reached_height >= 10
    # the kill really happened (two completed ABCI handshakes = two
    # real boots), and the partition file really mutated
    log = open(
        os.path.join(str(tmp_path), "v1", "node.log"), "rb"
    ).read()
    assert log.count(b"completed ABCI handshake") >= 2
    spec = open(os.path.join(str(tmp_path), "partition.spec")).read()
    assert spec == ""  # healed at the end
    assert rep.txs_submitted > 0 and rep.txs_committed > 0


def test_process_runner_rejects_inprocess_only_features(tmp_path):
    m = Manifest.parse(
        {
            "chain_id": "p",
            "validators": {"v0": 10},
            "node": {"v0": {"misbehaviors": {"double-prevote": 3}}},
        }
    )
    with pytest.raises(ValueError, match="in-process"):
        ProcessRunner(m, str(tmp_path))


def test_child_env_pins_cpu_and_repo_root(monkeypatch):
    """Child node processes must never take the chip from a parent
    that holds it: JAX_PLATFORMS is pinned to cpu whatever the parent
    runs on, and the repo root leads PYTHONPATH (ahead of whatever was
    inherited) so `-m tendermint_tpu.cmd` resolves in the child."""
    from tendermint_tpu.e2e.process_runner import _child_env

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    monkeypatch.setenv("PYTHONPATH", "/somewhere/else")
    env = _child_env()
    assert env["JAX_PLATFORMS"] == "cpu"
    assert env["PYTHONPATH"].split(os.pathsep) == [root, "/somewhere/else"]
    monkeypatch.delenv("PYTHONPATH")
    assert _child_env()["PYTHONPATH"] == root


def test_partition_perturbation_parses_and_maps():
    """partition/heal are first-class manifest perturbations: they
    parse, round-trip validation, and the process runner maps them to
    partition-file writes (TM_TPU_PARTITION_FILE plumbing)."""
    import inspect

    from tendermint_tpu.e2e import process_runner as pr
    from tendermint_tpu.e2e.manifest import Perturbation

    p = Perturbation.parse("partition:4")
    assert (p.action, p.height) == ("partition", 4)
    assert Perturbation.parse("heal:8").action == "heal"
    src = inspect.getsource(pr.ProcessRunner._apply_perturbation)
    assert "partition" in src and "heal" in src
    spawn = inspect.getsource(pr.ProcessRunner._spawn_node)
    assert "TM_TPU_PARTITION_FILE" in spawn


def test_perturbation_signals_map():
    """kill/restart/pause/disconnect all map to real signals in the
    process runner (SIGKILL / SIGTERM / SIGSTOP+SIGCONT)."""
    import inspect

    from tendermint_tpu.e2e import process_runner as pr

    src = inspect.getsource(pr.ProcessRunner._apply_perturbation)
    assert "SIGKILL" in src and "SIGTERM" in src
    assert "SIGSTOP" in src and "SIGCONT" in src
    assert signal.SIGKILL  # the platform actually has them


@pytest.mark.slow
def test_process_net_state_sync(tmp_path):
    """A late-joining full node in its own OS process state-syncs from
    snapshot-serving app processes: trust root seeded over live RPC,
    chunks restored via socket ABCI, and the end state proves a real
    restore (earliest stored block above genesis)."""
    m = Manifest.parse(
        {
            "chain_id": "proc-ss-ci",
            "target_height": 8,
            "validators": {"v0": 10, "v1": 10, "v2": 10},
            "node": {
                "joiner": {
                    "mode": "full",
                    "state_sync": True,
                    "start_at": 5,
                }
            },
            "load": {"tx_rate": 1, "tx_size": 48},
        }
    )
    m.validate()
    rep = run(ProcessRunner(m, str(tmp_path), timeout=340.0).run())
    assert rep.ok, rep.failures
    assert rep.state_synced.get("joiner") is True


@pytest.mark.slow
def test_process_remote_signer_node(tmp_path):
    """A validator whose key lives in a SEPARATE signer process (the
    tmkms deployment shape): the node exposes [priv_validator]
    listen_addr, `cmd signer` dials it over SecretConnection, and the
    chain only advances once the signer is attached. SIGKILLing the
    signer stalls signing; a restarted signer (same last-sign state on
    disk) resumes it."""
    import subprocess
    import sys
    import time as _time

    from tendermint_tpu.e2e.process_runner import _child_env, _free_port

    home = str(tmp_path / "val")
    env = _child_env()
    subprocess.run(
        [sys.executable, "-m", "tendermint_tpu.cmd", "--home", home,
         "init", "validator", "--chain-id", "proc-signer-ci"],
        check=True, env=env, capture_output=True,
    )
    pv_port = _free_port()
    rpc_port = _free_port()
    # point the node at the remote signer + fast consensus timeouts
    from tendermint_tpu.cmd.commands import _load_home
    from tendermint_tpu.config import write_config

    cfg = _load_home(home)
    cfg.priv_validator.listen_addr = f"tcp://127.0.0.1:{pv_port}"
    cfg.rpc.laddr = f"tcp://127.0.0.1:{rpc_port}"
    cfg.consensus.timeout_commit = 0.2
    write_config(cfg, f"{home}/config/config.toml")

    node_log = open(tmp_path / "node.log", "wb")
    node = subprocess.Popen(
        [sys.executable, "-m", "tendermint_tpu.cmd", "--home", home,
         "start"],
        stdout=node_log, stderr=subprocess.STDOUT, env=env,
    )
    signer_log = open(tmp_path / "signer.log", "wb")
    signer = subprocess.Popen(
        [sys.executable, "-m", "tendermint_tpu.cmd", "--home", home,
         "signer", "--addr", f"tcp://127.0.0.1:{pv_port}"],
        stdout=signer_log, stderr=subprocess.STDOUT, env=env,
    )

    def height() -> int:
        import json
        import urllib.request

        req = urllib.request.Request(
            f"http://127.0.0.1:{rpc_port}/",
            data=json.dumps(
                {"jsonrpc": "2.0", "id": 1, "method": "status",
                 "params": {}}
            ).encode(),
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=3) as r:
            res = json.loads(r.read())
        return int(
            res["result"]["sync_info"]["latest_block_height"]
        )

    try:
        deadline = _time.monotonic() + 120
        h = -1
        while _time.monotonic() < deadline:
            try:
                h = height()
                if h >= 3:
                    break
            except Exception:
                pass
            _time.sleep(0.5)
        assert h >= 3, f"remote-signer chain stuck at {h}"

        # kill the signer: the chain must stall (no local key at all)
        signer.kill()
        signer.wait()
        _time.sleep(3.0)

        def height_retry(tries=8):
            last = None
            for _ in range(tries):
                try:
                    return height()
                except Exception as e:
                    last = e
                    _time.sleep(0.5)
            raise last

        stalled = height_retry()
        _time.sleep(4.0)
        assert height_retry() <= stalled + 1, (
            "chain advanced without signer"
        )

        # a fresh signer process resumes from the on-disk sign state
        signer = subprocess.Popen(
            [sys.executable, "-m", "tendermint_tpu.cmd", "--home", home,
             "signer", "--addr", f"tcp://127.0.0.1:{pv_port}"],
            stdout=signer_log, stderr=subprocess.STDOUT, env=env,
        )
        deadline = _time.monotonic() + 90
        resumed = False
        while _time.monotonic() < deadline:
            try:
                if height() >= stalled + 2:
                    resumed = True
                    break
            except Exception:
                pass
            _time.sleep(0.5)
        assert resumed, "chain did not resume after signer restart"
    finally:
        for p in (signer, node):
            if p.poll() is None:
                p.terminate()
        for p in (signer, node):
            try:
                p.wait(20)
            except subprocess.TimeoutExpired:
                p.kill()
                p.wait()
        node_log.close()
        signer_log.close()
