"""The two-phase drain (crypto/batch.py `drain_classes`): every key
class's verifier is filled and launched before any class is gathered,
the class whose launches cost the host byte rows alone goes first, and
nothing of the verdict, the error index, the cache or the fault
containment changes for it.

The device is a recording backing with the dispatch()/gather() pair
behind the real seam verifiers (crypto/tpu_verifier.py), its verdicts
the CPU's, so the order of the seam's calls is what is under test.
Counts and verdicts on the CPU, never a speed.
"""

from __future__ import annotations

import contextlib

import pytest

from tendermint_tpu.crypto import batch as crypto_batch
from tendermint_tpu.crypto import breaker as B
from tendermint_tpu.crypto import faults, sigcache
from tendermint_tpu.crypto import tpu_verifier as T
from tendermint_tpu.crypto.ed25519 import PrivKeyEd25519, PubKeyEd25519
from tendermint_tpu.crypto.sr25519 import PrivKeySr25519, PubKeySr25519
from tendermint_tpu.libs import heap, trace
from tendermint_tpu.types import (
    Commit,
    InvalidCommitError,
    verify_commit,
)
from tendermint_tpu.types.commit import CommitSig
from tendermint_tpu.types.validation import verify_triples_grouped
from tendermint_tpu.types.validator import Validator, ValidatorSet

from .test_types import CHAIN_ID, make_block_id, signed_vote

ED, SR = "ed25519", "sr25519"
PRIV = {ED: PrivKeyEd25519, SR: PrivKeySr25519}
PUB = {ED: PubKeyEd25519, SR: PubKeySr25519}
SEAM = {ED: T.TpuEd25519BatchVerifier, SR: T.TpuSr25519BatchVerifier}
N = 10  # validators: five a key class


class Recording:
    """A backing device verifier that writes down what the seam asks of
    it and answers as the CPU would."""

    bucket_sizes = (8, 32, 128)

    def __init__(self, key: str, log: list, host_operand) -> None:
        self.key, self.log, self._host = key, log, host_operand

    def host_operand(self, n):
        """A fixed answer, or one a width (the sr25519 kernel's)."""
        return self._host(n) if callable(self._host) else self._host

    def dispatch(self, pks, msgs, sigs):
        self.log.append(("dispatch", self.key, len(pks)))
        oracle = getattr(PUB[self.key], "verify_signature_cpu", None)
        oracle = oracle or PUB[self.key].verify_signature
        return [
            oracle(PUB[self.key](pk), m, s) for pk, m, s in zip(pks, msgs, sigs)
        ]

    def gather(self, handle):
        self.log.append(("gather", self.key, len(handle)))
        return handle


@contextlib.contextmanager
def recording_seam(chunk=None, host_operand=None):
    """Both key classes routed through the real seam verifiers over
    recording backings; with `chunk`, add() streams full chunks of that
    size as it does on an accelerator. Yields (log, verifiers made)."""
    host_operand = host_operand or {ED: False, SR: True}
    log, made = [], []
    held = (T._TpuBatchVerifier.__dict__["_streaming"], T._TpuBatchVerifier.STREAM_CHUNK)
    if chunk is not None:
        T._TpuBatchVerifier._streaming = staticmethod(lambda: True)
        T._TpuBatchVerifier.STREAM_CHUNK = chunk

    def factory(key):
        backing = Recording(key, log, host_operand[key])

        def make(_hint):
            made.append(SEAM[key](backing))
            return made[-1]

        return make

    for key in (ED, SR):
        crypto_batch.register_device_factory(key, factory(key))
    try:
        yield log, made
    finally:
        for key in (ED, SR):
            crypto_batch.unregister_device_factory(key)
        T._TpuBatchVerifier._streaming, T._TpuBatchVerifier.STREAM_CHUNK = held


@pytest.fixture(autouse=True)
def _clean():
    T.uninstall()
    sigcache.reset()
    yield
    sigcache.reset()
    faults.reset()
    B.reset_all()
    trace.disable()
    trace.reset()
    heap.thaw()


def mixed_commit(first: str, kinds=(ED, SR)):
    """A fully signed commit over N validators whose key classes
    alternate, with a validator of class `first` at commit index 0 (the
    set sorts by address, so the seed decides: try seeds until it does).
    Returns (vals, block id, commit, privs in commit order)."""
    for base in range(1, 60):
        privs = [
            PRIV[kinds[i % len(kinds)]].from_seed(bytes([base + i]) * 32)
            for i in range(N)
        ]
        vals = ValidatorSet(
            [Validator(pub_key=p.pub_key(), voting_power=10) for p in privs]
        )
        if vals.validators[0].pub_key.type() == first:
            break
    else:
        raise AssertionError(f"no seed puts {first} at index 0")
    by_addr = {p.pub_key().address(): p for p in privs}
    privs = [by_addr[v.address] for v in vals.validators]
    bid = make_block_id(b"\x2a")
    sigs = []
    for i, priv in enumerate(privs):
        v = signed_vote(priv, vals, i, bid)
        sigs.append(CommitSig.for_block(v.signature, v.validator_address, v.timestamp_ns))
    return vals, bid, Commit(height=1, round=0, block_id=bid, signatures=sigs), privs


def indexes_of(vals, key: str) -> list:
    return [i for i, v in enumerate(vals.validators) if v.pub_key.type() == key]


def flip(commit, idx: int) -> None:
    sig = bytearray(commit.signatures[idx].signature)
    sig[7] ^= 0x10
    commit.signatures[idx].signature = bytes(sig)


def cache_key(vals, commit, idx: int) -> tuple:
    return (
        vals.validators[idx].pub_key.bytes(),
        commit.vote_sign_bytes(CHAIN_ID, idx),
        commit.signatures[idx].signature,
    )


def cached(vals, commit) -> list:
    """The commit indexes whose triple the verified-signature cache holds."""
    return [i for i in range(N) if sigcache.seen_key(cache_key(vals, commit, i))]


def calls(log, *names) -> list:
    return [(name, key) for name, key, _n in log if name in names]


# -- the order of the seam's calls --------------------------------------


@pytest.mark.parametrize("chunk", [None, 2], ids=["one-launch", "streamed"])
@pytest.mark.parametrize("first", [ED, SR])
def test_every_class_is_in_flight_before_the_first_gather(first, chunk):
    """Whichever class sits at commit index 0 (and so heads the dict),
    the class that packs byte rows alone launches first, every class's
    last launch precedes any gather, and the drain's span says so."""
    vals, bid, commit, _privs = mixed_commit(first)
    assert vals.validators[0].pub_key.type() == first
    trace.enable(capacity=4096)
    with recording_seam(chunk) as (log, made):
        verify_commit(CHAIN_ID, vals, bid, 1, commit)
    names = [name for name, _key, _n in log]
    first_gather = names.index("gather")
    assert "dispatch" not in names[first_gather:]
    launches = calls(log, "dispatch")
    per_class = 1 if chunk is None else 3  # 5 = 2 + 2 + 1
    assert launches == [("dispatch", ED)] * per_class + [("dispatch", SR)] * per_class
    assert calls(log, "gather") == [("gather", ED)] * per_class + [("gather", SR)] * per_class
    assert sum(n for name, _key, n in log if name == "dispatch") == N
    assert [type(bv) for bv in made] == (
        [SEAM[first], SEAM[SR if first == ED else ED]]
    )
    (drain,) = [s for s in trace.snapshot() if s.name == "batch_drain"]
    assert drain.attrs == {"classes": 2, "overlapped": 2}
    # every key is in the cache: both classes were proven
    assert cached(vals, commit) == list(range(N))


@pytest.mark.parametrize(
    "narrowest, sr_first", [(8, False), (4, True)], ids=["host-made", "device-made"]
)
def test_the_launch_order_follows_the_width_each_class_launches(narrowest, sr_first):
    """sr25519 at commit index 0 heads the dict. Where its five rows
    are narrower than the width its operand moves onto the device at,
    ed25519 launches first; where they are as wide, neither class makes
    an operand on the host and the dict's order stands."""
    vals, bid, commit, _privs = mixed_commit(SR)
    host = {ED: False, SR: lambda n: n < narrowest}
    with recording_seam(host_operand=host) as (log, _made):
        verify_commit(CHAIN_ID, vals, bid, 1, commit)
    order = [SR, ED] if sr_first else [ED, SR]
    assert calls(log, "dispatch", "gather") == [
        ("dispatch", order[0]), ("dispatch", order[1]),
        ("gather", order[0]), ("gather", order[1]),
    ]  # fmt: skip


def test_the_launch_order_follows_the_verifiers_property_not_its_name():
    """With the backings' `host_operand` the other way round, sr25519
    launches first: the order is read from the verifier object."""
    vals, bid, commit, _privs = mixed_commit(ED)
    with recording_seam(host_operand={ED: True, SR: False}) as (log, _made):
        verify_commit(CHAIN_ID, vals, bid, 1, commit)
    assert calls(log, "dispatch", "gather") == [
        ("dispatch", SR), ("dispatch", ED), ("gather", SR), ("gather", ED),
    ]  # fmt: skip


@pytest.mark.parametrize("chunk", [None, 4], ids=["one-launch", "streamed"])
def test_a_single_class_drain_issues_the_calls_it_always_did(chunk):
    """One key class: the seam's calls are those of a verifier driven by
    hand, add() after add() and then verify()."""
    vals, bid, commit, _privs = mixed_commit(ED, kinds=(ED,))
    with recording_seam(chunk) as (log, made):
        verify_commit(CHAIN_ID, vals, bid, 1, commit)
        drained, made_by_drain = list(log), len(made)
        del log[:]
        sigcache.reset()
        bv = crypto_batch.create_batch_verifier(vals.validators[0].pub_key, size_hint=N)
        for i, val in enumerate(vals.validators):
            bv.add(val.pub_key, commit.vote_sign_bytes(CHAIN_ID, i), commit.signatures[i].signature)
        assert bv.verify() == (True, [True] * N)
        assert drained == log
    assert made_by_drain == 1
    widths = [n for name, _key, n in drained if name == "dispatch"]
    assert widths == ([N] if chunk is None else [4, 4, 2])


def test_host_verifiers_have_nothing_to_launch():
    """No device factory: the CPU verifiers' launch() is the base
    class's no-op, the commit verifies, and the span counts no overlap."""
    vals, bid, commit, _privs = mixed_commit(SR)
    trace.enable(capacity=4096)
    verify_commit(CHAIN_ID, vals, bid, 1, commit)
    (drain,) = [s for s in trace.snapshot() if s.name == "batch_drain"]
    assert drain.attrs == {"classes": 2, "overlapped": 0}
    assert cached(vals, commit) == list(range(N))
    cpu = crypto_batch.create_batch_verifier(vals.validators[0].pub_key)
    assert cpu.launch() is False and cpu.host_operand(N) is False
    assert cpu.abandon() is None


def test_a_warm_verification_drains_nothing():
    """Every triple a cache hit: no verifier is made, and no drain span
    dilutes the count of overlapped classes."""
    vals, bid, commit, _privs = mixed_commit(ED)
    with recording_seam() as (log, made):
        verify_commit(CHAIN_ID, vals, bid, 1, commit)
        assert len(made) == 2
        del log[:]
        trace.enable(capacity=4096)
        with sigcache.commit_memo_disabled():
            verify_commit(CHAIN_ID, vals, bid, 1, commit)
    assert log == [] and len(made) == 2
    names = [s.name for s in trace.snapshot()]
    assert "sigcache_probe" in names and "batch_drain" not in names
    assert crypto_batch.drain_classes({}) == {}


# -- verdicts, the error's index, the cache --------------------------------


@pytest.mark.parametrize("first", [ED, SR])
@pytest.mark.parametrize(
    "bad", ["one-in-each-class", "second-launched-class-only", "first-launched-class-only"]
)
def test_the_error_names_the_lowest_bad_commit_index(first, bad):
    """Every class is verified whatever another answered, and the error
    is the reference's: the lowest wrong index across classes."""
    vals, bid, commit, _privs = mixed_commit(first)
    wrong = {
        "one-in-each-class": [indexes_of(vals, ED)[3], indexes_of(vals, SR)[1]],
        "second-launched-class-only": [indexes_of(vals, SR)[2]],
        "first-launched-class-only": [indexes_of(vals, ED)[4]],
    }[bad]
    for idx in wrong:
        flip(commit, idx)
    with recording_seam() as (log, _made):
        with pytest.raises(InvalidCommitError) as err:
            verify_commit(CHAIN_ID, vals, bid, 1, commit)
    lowest = min(wrong)
    assert str(err.value) == (
        f"wrong signature (#{lowest}): {commit.signatures[lowest].signature.hex()}"
    )
    # both classes went to the device and were gathered
    assert calls(log, "dispatch", "gather") == [
        ("dispatch", ED), ("dispatch", SR), ("gather", ED), ("gather", SR),
    ]  # fmt: skip
    # what was proven is cached, what was wrong is not
    assert cached(vals, commit) == [i for i in range(N) if i not in wrong]


def test_merged_triples_overlap_too_and_raise_without_an_index():
    """verify_triples_grouped (the light client's merged windows) drains
    through the same helper: sr25519 first in the triples, ed25519
    first on the device, one wrong signature fails the merged batch."""
    vals, _bid, commit, _privs = mixed_commit(SR)
    triples = [
        (v.pub_key, commit.vote_sign_bytes(CHAIN_ID, i), commit.signatures[i].signature)
        for i, v in enumerate(vals.validators)
    ]
    with recording_seam() as (log, _made):
        verify_triples_grouped(triples)
        assert calls(log, "dispatch", "gather") == [
            ("dispatch", ED), ("dispatch", SR), ("gather", ED), ("gather", SR),
        ]  # fmt: skip
        assert sigcache.entries() == N
        sigcache.reset()
        pk, sb, sig = triples[indexes_of(vals, ED)[0]]
        triples[indexes_of(vals, ED)[0]] = (pk, sb, sig[:3] + bytes([sig[3] ^ 1]) + sig[4:])
        with pytest.raises(InvalidCommitError, match="wrong signature in merged batch"):
            verify_triples_grouped(triples)
    assert sigcache.entries() == N - 1


# -- fault containment ------------------------------------------------------


@pytest.mark.parametrize("faulty", [ED, SR])
def test_a_class_whose_early_launch_raises_is_drained_on_the_cpu(faulty):
    """The launch must not raise: verify() finds the recorded fault,
    re-verifies that class on the CPU, marks it faulted and caches
    nothing of it; the other class never notices."""
    other = SR if faulty == ED else ED
    vals, bid, commit, _privs = mixed_commit(ED)
    flip(commit, indexes_of(vals, faulty)[1])
    faults0 = T.stats()["faults"]
    with recording_seam() as (log, made):
        with faults.inject("tpu.dispatch", mode="raise", key=faulty) as rule:
            with pytest.raises(InvalidCommitError) as err:
                verify_commit(CHAIN_ID, vals, bid, 1, commit)
        assert rule.fired == 1
    assert f"wrong signature (#{indexes_of(vals, faulty)[1]})" in str(err.value)
    by_class = {bv.KEY_TYPE: bv for bv in made}
    assert by_class[faulty].faulted and not by_class[other].faulted
    assert T.stats()["faults"] == faults0 + 1
    # the faulty class never reached the device, the other did as ever
    assert calls(log, "dispatch", "gather") == [("dispatch", other), ("gather", other)]
    assert cached(vals, commit) == indexes_of(vals, other)


def test_an_add_that_raises_in_the_second_class_abandons_the_first():
    """A malformed signature size in the class filled second raises out
    of add(), as it always did: by then the first class is in flight.
    It is abandoned: nothing is gathered, nothing cached, and no
    verifier still reports work."""
    vals, bid, commit, _privs = mixed_commit(ED)
    short = indexes_of(vals, SR)[2]
    commit.signatures[short].signature = commit.signatures[short].signature[:63]
    with recording_seam() as (log, made):
        with pytest.raises(ValueError, match="malformed signature size"):
            verify_commit(CHAIN_ID, vals, bid, 1, commit)
    assert calls(log, "dispatch", "gather") == [("dispatch", ED)]
    assert sigcache.entries() == 0
    assert len(made) == 2
    for bv in made:
        assert len(bv) == 0 and bv._handles == [] and bv._pks == []
        assert bv.verify() == (False, [])


# -- the settle ---------------------------------------------------------------


def test_the_heap_settles_once_after_the_last_gather():
    """A program "compiled" by the first class's launch marks the heap;
    the settle that verify() ends with waits while the second class is
    in flight, and lands once, after the last gather."""
    vals, bid, commit, _privs = mixed_commit(ED)
    settles_at = []

    class Compiling(Recording):
        def dispatch(self, pks, msgs, sigs):
            heap.mark_dirty()  # what the seam's compile listener does
            return super().dispatch(pks, msgs, sigs)

        def gather(self, handle):
            settles_at.append(heap.stats()["heap_settles"])
            return super().gather(handle)

    before = heap.stats()["heap_settles"]
    with recording_seam() as (log, _made):
        crypto_batch.register_device_factory(
            ED, lambda _hint: T.TpuEd25519BatchVerifier(Compiling(ED, log, False))
        )
        crypto_batch.register_device_factory(
            SR, lambda _hint: T.TpuSr25519BatchVerifier(Compiling(SR, log, True))
        )
        verify_commit(CHAIN_ID, vals, bid, 1, commit)
    assert settles_at == [before, before]
    assert heap.stats()["heap_settles"] == before + 1


def test_a_deferred_scope_holds_the_settle_back_and_nests():
    before = heap.stats()["heap_settles"]
    heap.mark_dirty()
    with heap.deferred():
        with heap.deferred():
            assert heap.settle() is False
        assert heap.settle() is False
        assert heap.stats()["heap_settles"] == before
    assert heap.stats()["heap_settles"] == before + 1
    assert heap.settle() is False  # nothing marked since
