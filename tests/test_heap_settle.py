"""The heap settle at the device seam (libs/heap.py, crypto/tpu_verifier.py):
after the first touch of a device program the seam collects once and
freezes, so later collections never walk the traced program again; a
dispatch that compiled nothing settles nothing; uninstall() thaws; the
collector itself is left as the interpreter set it up.

Counts and verdicts on the CPU backend, never a speed. One parametrised
test over the cases. Each stands alone: the two real first touches drop
JAX's caches first (tracing a tile program is most of this file's time,
so they come first and the rest run over what they left warm), the
others warm the program they need and make their compile event with a
program of a few operations.
"""

from __future__ import annotations

import gc
import time
import weakref

import jax
import pytest

from tendermint_tpu.crypto import batch as crypto_batch
from tendermint_tpu.crypto import breaker, faults, tpu_verifier
from tendermint_tpu.crypto.ed25519 import PrivKeyEd25519
from tendermint_tpu.crypto.sr25519 import PrivKeySr25519
from tendermint_tpu.libs import heap

PRIV = {"ed25519": PrivKeyEd25519, "sr25519": PrivKeySr25519}
N = 8  # the smallest bucket: one cheap program a key class

# what a traced tile program alone leaves behind is ≈ 250 k tracked
# objects (ISSUE 29's count); the freeze takes the rest of the heap too
PROGRAM_OBJECTS = 100_000


@pytest.fixture(autouse=True)
def _seam():
    """Every case starts thawed and uninstalled, and leaves so."""
    tpu_verifier.uninstall()
    yield
    tpu_verifier.uninstall()
    faults.reset()
    breaker.reset_all()


def triples(key: str, flip=None) -> list:
    out = []
    for i in range(N):
        priv = PRIV[key].from_seed(bytes([60 + i]) * 32)
        msg = b"heap-settle-%d" % i
        sig = priv.sign(msg)
        if i == flip:
            sig = sig[:9] + bytes([sig[9] ^ 4]) + sig[10:]
        out.append((priv.pub_key(), msg, sig))
    return out


def verify(work: list):
    bv = crypto_batch.create_batch_verifier(work[0][0], size_hint=len(work))
    for pub_key, msg, sig in work:
        bv.add(pub_key, msg, sig)
    return bv, bv.verify()


def settles() -> int:
    return tpu_verifier.stats()["heap_settles"]


def first_dispatch(key: str) -> None:
    """The dispatch that traces the tile program settles once, the next
    one not at all, and both give the CPU seam's verdict on a flipped
    signature bit: (False, that lane alone)."""
    work = triples(key, flip=5)
    _cpu, want = verify(work)
    assert want == (False, [i != 5 for i in range(N)])
    jax.clear_caches()  # whatever an earlier test traced is cold again
    tpu_verifier.install(min_batch=2)
    before, frozen = settles(), gc.get_freeze_count()
    assert frozen == 0
    bv, got = verify(work)
    assert isinstance(bv, tpu_verifier._TpuBatchVerifier) and not bv.faulted
    assert got == want
    assert settles() == before + 1
    assert gc.get_freeze_count() >= frozen + PROGRAM_OBJECTS
    stats = tpu_verifier.stats()
    assert abs(stats["heap_frozen_objects"] - gc.get_freeze_count()) < 1_000
    # what the first touch left is out of the collector's reach: the
    # generations hold this test's few objects, not the program's
    assert len(gc.get_objects()) < PROGRAM_OBJECTS // 10
    frozen = gc.get_freeze_count()
    bv, got = verify(work)
    assert got == want and not bv.faulted
    # (frozen objects still die by reference count, so the count may fall)
    assert settles() == before + 1 and gc.get_freeze_count() <= frozen


def warm(key: str = "ed25519") -> list:
    """Installed, with `key`'s program traced and its settle, if it took
    one, behind us."""
    work = triples(key)
    tpu_verifier.install(min_batch=2)
    assert verify(work)[1][0]
    return work


def compile_something() -> None:
    """A program JAX has never seen, so a real compile event, at the
    cost of a few operations."""
    jax.jit(lambda x: x * 3 + 1)(jax.numpy.arange(7))


def second_dispatch_settles_nothing() -> None:
    work = warm()
    before, frozen = settles(), gc.get_freeze_count()
    assert verify(work)[1] == (True, [True] * N)
    assert settles() == before and gc.get_freeze_count() <= frozen


def reinstall_over_a_warm_trace_cache_settles_nothing() -> None:
    work = warm()
    before = settles()
    misses = tpu_verifier.stats()["warm_misses"]
    # other settings than the live install's, so a new generation (the
    # same again would keep the warm set): _WARM_BUCKETS cleared, jit's
    # cache not
    tpu_verifier.install(min_batch=3)
    frozen = gc.get_freeze_count()
    assert verify(work)[1][0]
    # the bucket is a first touch for the seam's telemetry and no
    # compile for JAX: the compile event, not the warm miss, is the signal
    assert tpu_verifier.stats()["warm_misses"] == misses + 1
    assert settles() == before and gc.get_freeze_count() <= frozen


def a_compile_outside_the_seam_waits_for_the_next_verify() -> None:
    """What the merkle hooks' first touch does: the mark is made where
    the program compiled, the settle where the seam is next quiet."""
    work = warm()
    before, frozen = settles(), gc.get_freeze_count()
    compile_something()
    assert settles() == before and gc.get_freeze_count() <= frozen
    assert verify(work)[1][0]
    assert settles() == before + 1
    assert gc.get_freeze_count() >= PROGRAM_OBJECTS


def uninstall_thaws() -> None:
    work = warm()
    compile_something()
    assert verify(work)[1][0]
    assert gc.get_freeze_count() >= PROGRAM_OBJECTS
    tpu_verifier.uninstall()
    assert gc.get_freeze_count() == 0
    assert tpu_verifier.stats()["heap_frozen_objects"] == 0
    # and stops listening: a compile on the CPU seam marks nothing
    compile_something()
    assert not heap.settle()
    assert gc.get_freeze_count() == 0


def the_collector_is_left_as_it_was() -> None:
    assert gc.isenabled() and gc.get_threshold() == (700, 10, 10)
    work = warm()
    compile_something()
    before = settles()
    assert verify(work)[1][0]
    assert settles() == before + 1
    assert gc.isenabled() and gc.get_threshold() == (700, 10, 10)

    class Node:
        pass

    a, b = Node(), Node()
    a.other, b.other = b, a
    dead = weakref.ref(a)
    del a, b
    # a cycle born after the freeze is garbage the collector still finds
    # (a young collection does: it never needed the frozen heap)
    gc.collect(0)
    assert dead() is None


def never_installed_never_freezes() -> None:
    """A node with `[tpu] enable = false`: no install, no listener, no
    settle, though the process compiles a program and verifies a batch."""
    import asyncio
    import pathlib
    import tempfile

    from tendermint_tpu.node import make_node

    from .test_node import make_genesis, make_home

    before, frozen = settles(), gc.get_freeze_count()

    async def go(home: pathlib.Path):
        priv = PrivKeyEd25519.from_seed(b"\x01" * 32)
        cfg = make_home(home, 0, make_genesis([priv]), priv)
        cfg.tpu.enable = False
        node = make_node(cfg)
        assert tpu_verifier.installed() is None
        await node.start()
        try:
            await node.consensus.wait_for_height(3, timeout=60.0)
        finally:
            await node.stop()

    with tempfile.TemporaryDirectory() as home:
        asyncio.run(go(pathlib.Path(home)))
    compile_something()
    bv, (ok, _bits) = verify(triples("ed25519"))
    assert ok and not isinstance(bv, tpu_verifier._TpuBatchVerifier)
    assert not heap.settle()
    assert settles() == before and gc.get_freeze_count() <= frozen


def the_installs_probe_settles_before_the_breaker_closes() -> None:
    """With singles worth the device (`min_batch` 1, as on an
    accelerator) the install's probe is the first touch of the smallest
    sr25519 bucket, on a thread of its own: its settle is done when the
    single-verify breaker closes, which is what a caller waits for."""
    tpu_verifier.install(min_batch=2)  # on the CPU its probe dispatches nothing
    compile_something()  # marked, whether or not the probe's program is warm
    before = settles()
    tpu_verifier.install(min_batch=1)
    single = tpu_verifier.sr_single_breaker()
    deadline = time.monotonic() + 300
    while single.state() != breaker.CLOSED and time.monotonic() < deadline:
        time.sleep(0.02)
    assert single.state() == breaker.CLOSED
    assert settles() == before + 1
    assert gc.get_freeze_count() >= PROGRAM_OBJECTS


def a_faulted_batch_leaves_the_mark_for_the_next() -> None:
    """A dispatch that compiled and then lost its gather drains on the
    CPU and settles nothing; the next batch the device completes does."""
    work = warm()
    compile_something()
    before, frozen = settles(), gc.get_freeze_count()
    with faults.inject("tpu.gather", "raise", times=1, key="ed25519"):
        bv, (ok, _bits) = verify(work)
    assert ok and bv.faulted
    assert settles() == before and gc.get_freeze_count() <= frozen
    breaker.breaker_for("ed25519").close_now()
    bv, (ok, _bits) = verify(work)
    assert ok and not bv.faulted
    assert settles() == before + 1
    assert gc.get_freeze_count() >= PROGRAM_OBJECTS


CASES = [
    pytest.param(first_dispatch, ("sr25519",), id="first_dispatch-sr25519"),
    pytest.param(
        the_installs_probe_settles_before_the_breaker_closes, (), id="probe_settles"
    ),
    pytest.param(first_dispatch, ("ed25519",), id="first_dispatch-ed25519"),
    pytest.param(second_dispatch_settles_nothing, (), id="second_dispatch"),
    pytest.param(
        reinstall_over_a_warm_trace_cache_settles_nothing, (), id="reinstall_warm"
    ),
    pytest.param(
        a_compile_outside_the_seam_waits_for_the_next_verify, (), id="outside_the_seam"
    ),
    pytest.param(
        a_faulted_batch_leaves_the_mark_for_the_next, (), id="faulted_batch_waits"
    ),
    pytest.param(uninstall_thaws, (), id="uninstall_thaws"),
    pytest.param(the_collector_is_left_as_it_was, (), id="collector_left_on"),
    pytest.param(never_installed_never_freezes, (), id="never_installed"),
]


@pytest.mark.parametrize("case, args", CASES)
def test_heap_settle(case, args):
    case(*args)
