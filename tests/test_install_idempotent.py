"""An install at the live install's settings changes nothing: the
second `Node` of a process (a restart in place, a localnet, the
benchmark's node cell under a harness that installed first) keeps the
verifiers, the breakers and the warm buckets and starts no probe. Any
other install is a new generation, as before."""

import pytest

from tendermint_tpu.crypto import batch as crypto_batch
from tendermint_tpu.crypto import breaker, sigcache, tpu_verifier
from tendermint_tpu.crypto.ed25519 import PrivKeyEd25519

N = 9


@pytest.fixture(autouse=True)
def _clean_install_state():
    tpu_verifier.uninstall()
    breaker.reset_all()
    yield
    tpu_verifier.uninstall()
    breaker.reset_all()
    sigcache.reset()


def dispatch_once() -> None:
    """One batch of N through the device seam (the CPU backend's)."""
    work = []
    for i in range(N):
        priv = PrivKeyEd25519.from_seed(bytes([i + 1]) * 32)
        msg = b"vote %d" % i
        work.append((priv.pub_key(), msg, priv.sign(msg)))
    bv = crypto_batch.create_batch_verifier(work[0][0], size_hint=N)
    assert isinstance(bv, tpu_verifier._TpuBatchVerifier)
    for triple in work:
        bv.add(*triple)
    assert bv.verify() == (True, [True] * N)


def live() -> tuple:
    """What a second install must leave as it was."""
    return (
        tpu_verifier.installed(),
        tuple(breaker.registered(name) for name in ("ed25519", "sr25519", "sr25519-single")),
        tuple(breaker.registered(name).state() for name in ("ed25519", "sr25519", "sr25519-single")),
        frozenset(tpu_verifier._WARM_BUCKETS),
        tpu_verifier._SHARED_VERIFIER,
    )


def test_an_install_at_the_live_settings_keeps_breakers_and_warm_buckets():
    tpu_verifier.install(min_batch=2)
    dispatch_once()
    misses = tpu_verifier.stats()["warm_misses"]
    was = live()
    assert was[0] == 2 and len(was[3]) == 1
    # a tripped route stays tripped: the second install is no reset
    for _ in range(10):
        breaker.breaker_for("sr25519").record_failure()
    tripped = live()
    assert tripped[2][1] == breaker.OPEN
    tpu_verifier.install(min_batch=2)
    assert live() == tripped
    dispatch_once()
    assert tpu_verifier.stats()["warm_misses"] == misses  # the bucket was still warm


@pytest.mark.parametrize("what", ["min_batch", "breakers dropped", "uninstalled"])
def test_any_other_install_is_a_new_generation(what):
    tpu_verifier.install(min_batch=2)
    dispatch_once()
    misses = tpu_verifier.stats()["warm_misses"]
    was = live()
    if what == "min_batch":
        tpu_verifier.install(min_batch=3)
    elif what == "breakers dropped":
        breaker.reset_all()  # what a test's teardown does without uninstalling
        tpu_verifier.install(min_batch=2)
    else:
        tpu_verifier.uninstall()
        assert tpu_verifier.installed() is None
        tpu_verifier.install(min_batch=2)
    now = live()
    assert now[0] == (3 if what == "min_batch" else 2)
    assert all(new is not None and new is not old for new, old in zip(now[1], was[1]))
    assert now[3] == frozenset()
    dispatch_once()
    assert tpu_verifier.stats()["warm_misses"] == misses + 1


def test_two_nodes_in_one_process_leave_the_install_as_it_was(tmp_path):
    from tendermint_tpu.node import make_node

    from .test_node import make_genesis, make_home

    priv = PrivKeyEd25519.from_seed(b"\x07" * 32)
    genesis = make_genesis([priv])
    first = make_node(make_home(tmp_path, 0, genesis, priv))
    assert first.cfg.tpu.enable and tpu_verifier.installed() == 2
    dispatch_once()
    misses = tpu_verifier.stats()["warm_misses"]
    was = live()
    second = make_node(make_home(tmp_path, 1, genesis, None))
    assert second.cfg.tpu.enable and live() == was
    dispatch_once()
    assert tpu_verifier.stats()["warm_misses"] == misses
    # a node that asks for another threshold still overrides process-wide
    cfg = make_home(tmp_path, 2, genesis, None)
    cfg.tpu.min_batch_size = 4
    make_node(cfg)
    assert tpu_verifier.installed() == 4 and live()[3] == frozenset()
