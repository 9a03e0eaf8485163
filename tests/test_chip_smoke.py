"""chip_smoke.py rehearsed on the CPU backend.

The script itself has no CPU arm (it exits non-zero without a TPU), but
its phases are functions of their sizes, so each one runs here at a
tiny size with the thresholds lowered through install()'s own
arguments: what can be wrong with a phase's paths, arguments, control
flow and device accounting is found here, not on the chip. The bypass
cases arm the program's own fault-containment routes and require that a
phase then FAILS although every verdict it checked was still right —
the failure mode the script exists to catch.
"""

import os
import shutil
import subprocess
import sys

import pytest

import chip_smoke as S
from tendermint_tpu.crypto import breaker, faults, sigcache, tpu_verifier
from tendermint_tpu.libs import trace
from tendermint_tpu.ops import merkle_kernel

SEED = 21
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def log():
    log = S.CompileLog()
    yield log
    log.close()


@pytest.fixture
def uninstalled():
    """Everything process-global phase_install sets, put back."""
    was_tracing = trace.is_enabled()
    yield
    tpu_verifier.uninstall()
    merkle_kernel.uninstall()
    breaker.reset_all()
    sigcache.reset()
    if not was_tracing:
        trace.disable()
    trace.reset()


@pytest.fixture
def install_row(uninstalled):
    """install with the thresholds lowered to the rehearsal's sizes."""
    return S.phase_install(min_batch=2, merkle_min_leaves=16)


def test_phase_install_is_the_nodes_install(install_row):
    assert tpu_verifier.installed() == 2
    assert merkle_kernel.installed() == 16
    assert install_row["native_libs"] == {
        "keccakf": True, "signbytes": True, "ed25519_batch": True,
    }
    assert install_row["cache_dir"].endswith(".jax_cache")
    # the defaults, untouched, are the node's own
    tpu_verifier.uninstall()
    row = S.phase_install()
    assert (row["min_batch"], row["merkle_min_leaves"]) == (8, 512)


def test_phase_commit(install_row, log):
    row = S.phase_commit(16, SEED, log)
    # 16 equal validators: the light tally stops after 11, the full
    # verification checks 16; first, warm and corrupted calls of each
    assert row["light_quorum_sigs"] == 11
    assert row["device"]["batches"] == 6
    assert row["device"]["sigs"] == 3 * (11 + 16)
    assert row["device"]["buckets"] == [32]
    assert 0 <= row["corrupted_index"] < 11


def test_phase_commit_mixed_with_merkle(install_row, log):
    row = S.phase_commit_mixed(16, 64, SEED, log)
    assert row["key_classes"] == {"ed25519": 8, "sr25519": 8}
    assert row["device"]["batches"] == 8 and row["device"]["sigs"] == 64
    assert len(row["corrupted_indexes"]) == 2
    assert row["merkle"]["through_device_hooks"] == {
        "roots": 3, "leaves": 192, "proofs": 128,
    }


def test_phase_commit_mixed_streamed_in_chunks(install_row, log, monkeypatch):
    """On an accelerator add() launches a dispatch per full
    STREAM_CHUNK; the accounting must count dispatches the way the
    program makes them (here: 8 signatures a class in chunks of 4)."""
    monkeypatch.setattr(tpu_verifier, "_STREAMING", True)
    monkeypatch.setattr(tpu_verifier._TpuBatchVerifier, "STREAM_CHUNK", 4)
    sent = S.Sent()
    sent.group(10)
    assert (sent.batches, sent.sigs) == (3, 10)
    row = S.phase_commit_mixed(16, 0, SEED, log)
    assert row["device"]["batches"] == 4 * 2 * 2
    assert row["device"]["sigs"] == 64
    assert "merkle" not in row


def test_phase_light(install_row, log):
    row = S.phase_light(4, 3, SEED, log)
    assert row["headers"] == 4 and row["light_quorum_sigs"] == 3
    # a CPU-backed install keeps the one-hop window (merged windows
    # only pay on an accelerator), so every hop is its own dispatch
    assert row["window_hops"] == 1
    bad_hop = row["corrupted_height"] - 1
    assert row["device"]["batches"] == 3 + 1 + bad_hop
    assert row["device"]["sigs"] == 3 * (3 + 3 + bad_hop)


def test_phase_light_merged_windows(install_row, log):
    """The accelerator's shape — merged 32-hop windows, streamed in
    chunks — with both knobs turned by hand: the accounting must follow
    the program through the forged chain, whose bad window is sent once
    and names its hop."""
    from tendermint_tpu.crypto import batch

    state = batch.group_affinity_state()
    batch.set_group_affinity(2)
    try:
        row = S.phase_light(4, 5, SEED, log)
    finally:
        batch.restore_group_affinity(state)
    assert row["window_hops"] == 2
    bad_hop = row["corrupted_height"] - 1
    before_bad = (bad_hop - 1) // 2 * 2
    # good sync: windows of 2, 2, 1; bulk: one batch; forged: the whole
    # windows before the bad one, then the bad window, merged, once
    assert row["device"]["batches"] == 3 + 1 + before_bad // 2 + 1
    bad_window = min(2, 5 - before_bad)
    assert row["device"]["sigs"] == 3 * (5 + 5 + before_bad + bad_window)


def test_phase_node(install_row, log):
    row = S.phase_node(600, 3, log)
    # Node.__init__ installs the defaults over the rehearsal's
    assert tpu_verifier.installed() == 8
    assert merkle_kernel.installed() == 512
    assert row["largest_block_txs"] >= 512
    assert row["merkle"]["roots"] >= 1
    assert row["device"]["batches"] == 0
    assert row["height"] >= 3


def test_mesh_path_on_four_virtual_devices(uninstalled, log):
    """`--chips 4` rehearsed: a mesh install, the mixed commit through
    the sharded verifiers, and every stage of a dispatch — placed rows,
    SHA-512 digests, the tile's bitmap — spread over all four devices
    (at the parent commit the first two sat on device 0)."""
    import jax

    from tendermint_tpu.parallel import make_mesh

    install = S.phase_install(min_batch=2, mesh=make_mesh(jax.devices()[:4]))
    assert install["mesh_devices"] == 4
    rows = [S.phase_commit_mixed(16, 0, SEED, log)]
    placement = S.phase_mesh_placement(8, SEED)
    for stage in ("input_rows", "sha512_digests", "tile_bitmap"):
        assert placement["stages"][stage]["devices"] == [0, 1, 2, 3]
    assert placement["stages"]["sha512_digests"]["shard_shape"] == [64, 2]
    S.finish(log, install, rows)


def test_finish_reports_cache_and_breakers(install_row, log):
    rows = [S.phase_commit(16, SEED, log)]
    row = S.finish(log, install_row, rows)
    assert set(row["breakers"].values()) == {"closed"}
    assert row["cache_dir"] == install_row["cache_dir"]
    assert row["tpu_verifier"]["faults"] == 0
    assert row["tpu_verifier"]["batches"] == 6
    # device work no phase accounts for fails the run
    with pytest.raises(S.SmokeFailure, match="the phases account for 0"):
        S.finish(log, install_row, [])


def test_cpu_factory_never_asks_the_device(install_row, monkeypatch):
    """The reference the smoke compares with — and the verifier a
    faulted device batch is re-verified through — is the CPU factory.
    Its sr25519 verifier re-checks a failed batch signature by
    signature; on an accelerator `verify_signature` routes singles to
    the device, so that re-check must use the host-only verify. (Found
    by the first chip run: 5,000 uncounted single dispatches.)"""
    from tendermint_tpu.crypto.batch import cpu_factory

    monkeypatch.setattr(tpu_verifier, "_STREAMING", True)
    tpu_verifier.sr_single_breaker().close_now()
    assert tpu_verifier.single_sr_verifier() is not None
    privs, vals = S.make_validators(8, SEED, ("sr25519",))
    commit = S.sign_commit(privs, vals, S._block_id(7), 1, S.BASE_TIME_NS)
    triples = S.commit_triples(vals, S.corrupted(commit, 3))
    before = tpu_verifier.stats()
    bv = cpu_factory("sr25519")()
    for t in triples:
        bv.add(*t)
    ok, bits = bv.verify()
    assert not ok and bits == [i != 3 for i in range(8)]
    assert tpu_verifier.stats() == before


@pytest.mark.parametrize("how", ["gather-hang", "open-breaker"])
def test_bypass_fails_the_phase_though_verdicts_are_right(
    how, install_row, log, monkeypatch
):
    """A device that hangs, or a route whose breaker is open, is
    answered from the CPU factory with the same verdicts and the same
    wrong-signature index — so the phase's verdict checks all pass, and
    only the accounting can tell. It must."""
    if how == "gather-hang":
        # warm the program: the compile blocks in dispatch(), not in
        # the gather the hang is injected into; the short deadline (read
        # at each gather) comes after it, or a loaded machine's warm-up
        # is the fault
        S.phase_commit(16, SEED, log)
        monkeypatch.setenv("TM_TPU_GATHER_DEADLINE_S", "0.2")
        with faults.inject("tpu.gather", mode="hang", hang_s=1.0, times=1):
            with pytest.raises(S.SmokeFailure, match=r"1 device fault"):
                S.phase_commit(16, SEED, log)
    else:
        breaker.breaker_for("ed25519").open_now(backoff_s=600.0)
        with pytest.raises(
            S.SmokeFailure, match=r"device dispatches: 0, sent 6"
        ):
            S.phase_commit(16, SEED, log)


@pytest.mark.parametrize("where", ["checkout", "alone"])
def test_no_cpu_arm(where, tmp_path):
    """Without a TPU the script exits non-zero and prints no result —
    from the checkout, and from a directory that holds nothing else of
    the repo (where it cannot even import the package)."""
    if where == "checkout":
        cwd = REPO
    else:
        cwd = str(tmp_path)
        shutil.copy(os.path.join(REPO, "chip_smoke.py"), cwd)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    proc = subprocess.run(
        [sys.executable, "chip_smoke.py"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    if where == "checkout":
        assert proc.stdout == ""
        assert "needs a TPU" in proc.stderr


@pytest.mark.parametrize(
    "platforms, backend, want",
    [
        ("cpu", None, False),  # answered from the string: no backend query
        ("", "tpu", True),  # the chip machine: whatever jax finds
        ("tpu", "tpu", True),
        ("", "cpu", False),  # jax found no chip
    ],
)
def test_on_accelerator_asks_jax_and_nothing_else(
    platforms, backend, want, monkeypatch
):
    """The streaming / merged-window / deadline decisions hang on one
    question, answered by the jax_platforms string for CPU-pinned
    processes and by jax's own backend otherwise — no sniffing of
    importable packages, which was written for a plug-in that is gone."""
    from unittest import mock

    import jax

    def default_backend():
        assert backend is not None, "a CPU-pinned process queried the backend"
        return backend

    monkeypatch.setattr(tpu_verifier, "_STREAMING", None)
    monkeypatch.setattr(jax, "default_backend", default_backend)
    with mock.patch.object(
        type(jax.config), "jax_platforms",
        new_callable=mock.PropertyMock, return_value=platforms,
    ):
        assert tpu_verifier.on_accelerator() is want
    assert not hasattr(tpu_verifier, "_has_tpu_runtime")
