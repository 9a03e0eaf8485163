"""The cache misses cross the batch seam as columns (crypto/batch.py
`Columns`, `BatchVerifier.add_many`): whatever builds them (the vector
plan's masked selects, the trusting replay's and the scalar loop's
appends, the merged window's transposition) and whichever verifier
takes them (one that loops over add(), or the device verifier's own
add_many), the error, its index and the cache are those of the
reference loop, and the device verifier streams as add() streamed.

Verdicts and counts on the CPU, never a speed.
"""

from __future__ import annotations

import functools
import hashlib
import os
import types

import pytest

from tendermint_tpu.crypto import batch as crypto_batch
from tendermint_tpu.crypto import breaker as B
from tendermint_tpu.crypto import faults, sigcache
from tendermint_tpu.crypto import tpu_verifier as T
from tendermint_tpu.crypto.ed25519 import Ed25519BatchVerifier, PubKeyEd25519
from tendermint_tpu.crypto.secp256k1 import (
    PrivKeySecp256k1,
    Secp256k1BatchVerifier,
)
from tendermint_tpu.crypto.sr25519 import Sr25519BatchVerifier
from tendermint_tpu.libs import heap, trace
from tendermint_tpu.types import Commit, InvalidCommitError
from tendermint_tpu.types import validation as V
from tendermint_tpu.types.commit import CommitSig
from tendermint_tpu.types.validator import Validator, ValidatorSet

from .test_drain_classes import ED, PRIV, SR, Recording, recording_seam
from .test_types import CHAIN_ID, make_block_id, signed_vote

SECP = "secp256k1"
N = 12  # validators, equal power: a light check processes 9 of them
SETS = {"ed": (ED,), "mixed": (ED, SR), "unbatched": (ED, SR, SECP)}
TRUST = V.Fraction(2, 3)


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


def priv_of(kind: str, seed: int):
    if kind == SECP:
        return PrivKeySecp256k1(bytes([seed]) * 32)
    return PRIV[kind].from_seed(bytes([seed]) * 32)


@functools.lru_cache(maxsize=None)
def signed_commit(kinds: tuple):
    """A fully signed commit over N validators whose key classes cycle
    through `kinds`, with a proposer that has a batch verifier in every
    set (a set whose proposer has none is verified vote by vote and
    never reaches the seam). Returns (vals, block id, commit)."""
    for base in range(1, 80):
        privs = [priv_of(kinds[i % len(kinds)], base + i) for i in range(N)]
        vals = ValidatorSet(
            [Validator(pub_key=p.pub_key(), voting_power=10) for p in privs]
        )
        if vals.get_proposer().pub_key.type() != SECP:
            break
    else:
        raise AssertionError("no seed gives a batchable proposer")
    by_addr = {p.pub_key().address(): p for p in privs}
    bid = make_block_id(b"\x33")
    sigs = []
    for i, val in enumerate(vals.validators):
        v = signed_vote(by_addr[val.address], vals, i, bid)
        sigs.append(
            CommitSig.for_block(v.signature, v.validator_address, v.timestamp_ns)
        )
    return vals, bid, Commit(height=1, round=0, block_id=bid, signatures=sigs)


def corrupted(commit: Commit, idx) -> Commit:
    """A copy of `commit` with one bit of vote `idx` flipped (None: a
    plain copy, so that no memo of an earlier case is met again)."""
    sigs = [
        CommitSig.for_block(s.signature, s.validator_address, s.timestamp_ns)
        for s in commit.signatures
    ]
    if idx is not None:
        raw = bytearray(sigs[idx].signature)
        raw[9] ^= 0x04
        sigs[idx].signature = bytes(raw)
    return Commit(
        height=commit.height, round=commit.round, block_id=commit.block_id,
        signatures=sigs,
    )


def key_of(vals, commit, idx: int) -> tuple:
    return (
        vals.validators[idx].pub_key.bytes(),
        commit.vote_sign_bytes(CHAIN_ID, idx),
        commit.signatures[idx].signature,
    )


def cached_triples() -> set:
    """The signature triples the cache holds (a commit memo's key is
    longer, and only the vector plans write one)."""
    return {k for k in sigcache._gen0 | sigcache._gen1 if len(k) == 3}


for_block = lambda c: not c.is_for_block()  # noqa: E731
always = lambda c: True  # noqa: E731

# entry -> (processed votes of a fully signed N-validator commit, the
# call under test, the same check through the reference scan loop)
ENTRIES = {
    "verify_commit": (
        N,
        lambda vals, bid, c: V.verify_commit(CHAIN_ID, vals, bid, 1, c),
        lambda vals, bid, c: V._verify_commit_batch_scalar(
            CHAIN_ID, vals, c, vals.total_voting_power() * 2 // 3,
            lambda s: s.is_absent(), lambda s: s.is_for_block(), True, True,
        ),
    ),
    "verify_commit_light": (
        9,
        lambda vals, bid, c: V.verify_commit_light(CHAIN_ID, vals, bid, 1, c),
        lambda vals, bid, c: V._verify_commit_batch_scalar(
            CHAIN_ID, vals, c, vals.total_voting_power() * 2 // 3,
            for_block, always, False, True,
        ),
    ),
    "verify_commit_light_trusting": (
        9,
        lambda vals, bid, c: V.verify_commit_light_trusting(
            CHAIN_ID, vals, c, TRUST
        ),
        lambda vals, bid, c: V._verify_commit_batch_scalar(
            CHAIN_ID, vals, c, vals.total_voting_power() * 2 // 3,
            for_block, always, False, False,
        ),
    ),
    "verify_commit_light_bulk": (
        9,
        lambda vals, bid, c: V.verify_commit_light_bulk(
            CHAIN_ID, [(vals, bid, 1, c)]
        ),
        lambda vals, bid, c: V._verify_commit_batch_scalar(
            CHAIN_ID, vals, c, vals.total_voting_power() * 2 // 3,
            for_block, always, False, True,
        ),
    ),
}

# the corrupted vote: none, or the first, a middle or the last processed
# vote of the set's k-th key class
CASES = [
    (name, None if where == "clean" else (k, where))
    for name, kinds in SETS.items()
    for k, where in [(0, "clean")]
    + [(k, w) for k in range(len(kinds)) for w in ("first", "middle", "last")]
]


def outcome(call, vals, bid, commit) -> tuple:
    """(error class, error text, cached triples) of one cold call."""
    sigcache.reset()
    try:
        call(vals, bid, commit)
        err = (None, None)
    except InvalidCommitError as e:
        err = (type(e), str(e))
    return err + (cached_triples(),)


@pytest.mark.parametrize("seam", ["host", "device"])
@pytest.mark.parametrize(
    "set_name,bad",
    CASES,
    ids=[f"{n}-{'clean' if b is None else f'class{b[0]}-{b[1]}'}" for n, b in CASES],
)
@pytest.mark.parametrize("entry", list(ENTRIES))
def test_columns_give_the_reference_error_and_cache(
    entry, set_name, bad, seam, monkeypatch
):
    """Each producer of columns against the reference scan loop and
    against what the protocol says: the error names the lowest bad vote,
    and the cache holds every other processed vote; a bad vote of a key
    type without a batch verifier stops the check where the loop stops,
    with the earlier votes of such types cached and no class drained."""
    kinds = SETS[set_name]
    vals, bid, signed = signed_commit(kinds)
    processed, call, reference = ENTRIES[entry]
    if SECP in kinds:
        monkeypatch.delitem(crypto_batch._CPU_FACTORIES, SECP)
    bad_idx = None
    if bad is not None:
        k, where = bad
        mine = [
            i for i in range(processed)
            if vals.validators[i].pub_key.type() == kinds[k]
        ]
        bad_idx = {"first": mine[0], "middle": mine[len(mine) // 2], "last": mine[-1]}[where]
    commit = corrupted(signed, bad_idx)

    # what the protocol says
    unplaced = False
    if bad_idx is None:
        want_err = (None, None)
        want_cache = {key_of(vals, commit, i) for i in range(processed)}
    else:
        hexed = commit.signatures[bad_idx].signature.hex()
        want_err = (InvalidCommitError, f"wrong signature (#{bad_idx}): {hexed}")
        if vals.validators[bad_idx].pub_key.type() == SECP:
            # the merged check cannot place an inline failure
            unplaced = entry == "verify_commit_light_bulk"
            if unplaced:
                want_err = (InvalidCommitError, "wrong signature in merged batch")
            want_cache = {
                key_of(vals, commit, i)
                for i in range(bad_idx)
                if vals.validators[i].pub_key.type() == SECP
            }
        else:
            want_cache = {
                key_of(vals, commit, i) for i in range(processed) if i != bad_idx
            }

    def both():
        return (
            outcome(call, vals, bid, commit),
            outcome(reference, vals, bid, commit),
        )

    if seam == "device":
        with recording_seam(chunk=2) as (_log, made):
            got, ref = both()
        assert {type(bv) for bv in made} <= {
            T.TpuEd25519BatchVerifier, T.TpuSr25519BatchVerifier
        }  # fmt: skip
    else:
        got, ref = both()
    assert got[:2] == want_err
    assert got[2] == want_cache
    assert ref[2] == got[2]
    if not unplaced:
        assert ref[:2] == got[:2]


def test_the_merged_error_carries_the_lowest_position_over_classes():
    """Two bad triples, one a class: the merged check names the lower
    place, read from the positions column."""
    vals, _bid, signed = signed_commit(SETS["mixed"])
    lows = {
        kind: min(i for i in range(N) if vals.validators[i].pub_key.type() == kind)
        for kind in (ED, SR)
    }
    commit = corrupted(corrupted(signed, lows[ED] + 2), lows[SR] + 2)
    triples = [
        (vals.validators[i].pub_key, commit.vote_sign_bytes(CHAIN_ID, i), commit.signatures[i].signature)
        for i in range(N)
    ]
    with pytest.raises(InvalidCommitError) as e:
        V.verify_triples_grouped(triples)
    assert e.value.position == min(lows.values()) + 2
    V.verify_triples_grouped([])  # nothing to verify, nothing raised


# -- add_many against add(), a verifier class at a time --------------------


def device_verifier(kind):
    return lambda: (
        T.TpuEd25519BatchVerifier if kind == ED else T.TpuSr25519BatchVerifier
    )(Recording(kind, [], kind == SR))


VERIFIERS = {
    "Ed25519BatchVerifier": (ED, Ed25519BatchVerifier),
    "Sr25519BatchVerifier": (SR, Sr25519BatchVerifier),
    "Secp256k1BatchVerifier": (SECP, Secp256k1BatchVerifier),
    "TpuEd25519BatchVerifier": (ED, device_verifier(ED)),
    "TpuSr25519BatchVerifier": (SR, device_verifier(SR)),
}


def columns_of(kind: str, n: int = 5):
    privs = [priv_of(kind, 90 + i) for i in range(n)]
    msgs = [b"row-%d" % i for i in range(n)]
    sigs = [p.sign(m) for p, m in zip(privs, msgs)]
    return [p.pub_key() for p in privs], msgs, sigs


def raised_by(fn):
    try:
        fn()
    except (TypeError, ValueError) as e:
        return type(e), str(e)
    return None


@pytest.mark.parametrize("fault", ["bad-bit", "short-signature", "foreign-key"])
@pytest.mark.parametrize("name", list(VERIFIERS))
@pytest.mark.parametrize("with_key_bytes", [False, True], ids=["keys", "keys+bytes"])
def test_add_many_is_add_over_columns(name, fault, with_key_bytes):
    """The bitmap of columns through add_many is that of the same
    triples through add(); a column with a wrong-length signature or a
    foreign key raises what add() raises for that triple, and a
    verifier that takes columns whole has then queued nothing."""
    kind, make = VERIFIERS[name]
    pks, msgs, sigs = columns_of(kind)
    if fault == "bad-bit":
        sigs[3] = sigs[3][:4] + bytes([sigs[3][4] ^ 1]) + sigs[3][5:]
    elif fault == "short-signature":
        sigs[3] = sigs[3][:63]
    else:
        pks[3] = priv_of(SR if kind == ED else ED, 77).pub_key()
    key_bytes = [pk.bytes() for pk in pks] if with_key_bytes else None

    one, many = make(), make()
    by_add = raised_by(lambda: [one.add(*row) for row in zip(pks, msgs, sigs)])
    by_add_many = raised_by(lambda: many.add_many(pks, msgs, sigs, key_bytes))
    assert by_add_many == by_add
    if fault == "bad-bit":
        assert by_add is None
        assert many.verify() == one.verify() == (False, [True, True, True, False, True])
    else:
        assert by_add is not None
        overrides = type(many).add_many is not crypto_batch.BatchVerifier.add_many
        assert len(many) == (0 if overrides else 3)
        assert len(one) == 3


def test_add_many_refuses_columns_of_unequal_length():
    pks, msgs, sigs = columns_of(ED)
    bv = device_verifier(ED)()
    with pytest.raises(ValueError):
        bv.add_many(pks, msgs, sigs[:4])
    with pytest.raises(ValueError):
        bv.add_many(pks, msgs, sigs, [pk.bytes() for pk in pks[:4]])
    assert len(bv) == 0


def test_the_batch_add_span_counts_what_entered_as_columns():
    """`bulk`: the rows a verifier's own add_many took; 0 for a class
    whose verifier inherits the loop over add()."""
    vals, bid, commit = signed_commit(SETS["mixed"])
    trace.enable(capacity=4096)
    V.verify_commit(CHAIN_ID, vals, bid, 1, corrupted(commit, None))
    host = [s.attrs for s in trace.snapshot() if s.name == "batch_add"]
    assert sorted((a["sigs"], a["bulk"]) for a in host) == [(6, 0), (6, 0)]
    trace.reset()
    sigcache.reset()
    with recording_seam():
        V.verify_commit(CHAIN_ID, vals, bid, 1, corrupted(commit, None))
    device = [s.attrs for s in trace.snapshot() if s.name == "batch_add"]
    assert sorted((a["key"], a["sigs"], a["bulk"]) for a in device) == [
        (ED, 6, 6), (SR, 6, 6),
    ]  # fmt: skip


# -- the streaming rule ------------------------------------------------------


class Chunks:
    """A backing that writes down each window it is handed."""

    bucket_sizes = (2048,)

    def __init__(self) -> None:
        self.windows: list = []

    def host_operand(self, n):
        return False

    def dispatch(self, pks, msgs, sigs):
        self.windows.append((list(pks), list(msgs), list(sigs)))
        return [True] * len(pks)

    def gather(self, handle):
        return handle


def synthetic(n: int):
    pks = [PubKeyEd25519(hashlib.sha256(b"k%d" % i).digest()) for i in range(n)]
    msgs = [b"m%d" % i for i in range(n)]
    sigs = [hashlib.sha512(b"s%d" % i).digest() for i in range(n)]
    return pks, msgs, sigs


@pytest.fixture
def streaming(monkeypatch):
    monkeypatch.setattr(
        T._TpuBatchVerifier, "_streaming", staticmethod(lambda: True)
    )


@pytest.mark.parametrize("ahead", [0, 100], ids=["columns-alone", "after-100-add"])
def test_add_many_streams_full_windows_in_add_order(streaming, ahead):
    """5,000 triples: two `tpu_stream_dispatch` spans of exactly 2,048,
    in add order whether or not add() queued some first, 904 left for
    launch(); the bitmap is aligned with the columns."""
    pks, msgs, sigs = synthetic(5000)
    backing = Chunks()
    bv = T.TpuEd25519BatchVerifier(backing)
    trace.enable(capacity=4096)
    for row in zip(pks[:ahead], msgs[:ahead], sigs[:ahead]):
        bv.add(*row)
    bv.add_many(pks[ahead:], msgs[ahead:], sigs[ahead:], [pk.bytes() for pk in pks[ahead:]])
    spans = [s for s in trace.snapshot() if s.name == "tpu_stream_dispatch"]
    assert [(s.attrs["n"], s.attrs["chunk"]) for s in spans] == [(2048, 0), (2048, 1)]
    assert len(bv._pks) == 904 and len(bv) == 5000
    assert [w[0] for w in backing.windows] == [
        [pk.bytes() for pk in pks[:2048]], [pk.bytes() for pk in pks[2048:4096]],
    ]  # fmt: skip
    assert backing.windows[1][1:] == (msgs[2048:4096], sigs[2048:4096])
    assert bv.launch() is True and backing.windows[2][1] == msgs[4096:]
    assert bv.verify() == (True, [True] * 5000)


def test_a_column_short_of_a_window_asks_nothing_of_the_backend(monkeypatch):
    """As add() asks `_streaming()` only at a full window, add_many asks
    it only of columns that can fill one."""
    def boom():
        raise AssertionError("asked")

    monkeypatch.setattr(T._TpuBatchVerifier, "_streaming", staticmethod(boom))
    pks, msgs, sigs = synthetic(2047)
    bv = T.TpuEd25519BatchVerifier(Chunks())
    bv.add_many(pks, msgs, sigs)
    assert len(bv._pks) == 2047


def test_a_stream_fault_stops_the_launches_and_the_cpu_drains_it_all(
    streaming, monkeypatch
):
    """The second window's dispatch raises: nothing further is launched,
    every triple stays queued in add order, and verify() re-verifies
    all 5,000 through the CPU factory with `faulted` set."""
    pks, msgs, sigs = synthetic(5000)

    class Failing(Chunks):
        def dispatch(self, pks, msgs, sigs):
            if self.windows:
                raise RuntimeError("device lost")
            return super().dispatch(pks, msgs, sigs)

    seen: list = []

    class Cpu(Ed25519BatchVerifier):
        def verify(self):
            seen.extend(self._items)
            return True, [True] * len(self._items)

    monkeypatch.setitem(crypto_batch._CPU_FACTORIES, ED, Cpu)
    backing = Failing()
    bv = T.TpuEd25519BatchVerifier(backing)
    bv.add_many(pks, msgs, sigs)
    assert len(backing.windows) == 1 and isinstance(bv._stream_fault, RuntimeError)
    assert len(bv._pks) == 5000 - 2048 and bv.launch() is False
    assert bv.verify() == (True, [True] * 5000)
    assert bv.faulted and seen == list(zip(pks, msgs, sigs))
    assert len(backing.windows) == 1


# -- the benchmark's reader of the span's `bulk` ------------------------------


def _span(sid, name, **attrs):
    return types.SimpleNamespace(
        span_id=sid, name=name, start_us=float(sid * 10), dur_us=5.0,
        parent_id=0, root_id=sid, attrs=attrs,
    )  # fmt: skip


@pytest.mark.parametrize(
    "adds,want",
    [
        ([(ED, 5000, 5000), (SR, 5000, 5000)], 100.0),
        # a class whose verifier loops over add() (a CPU factory)
        ([(ED, 6000, 6000), (SECP, 2000, 0)], 75.0),
        ([(ED, 101, 0)], 0.0),
        # a parent commit's span has no `bulk`: nothing to read
        ([(ED, 5000, None), (SR, 5000, None)], None),
        ([], None),
    ],
    ids=["all-columns", "one-class-loops", "none", "parent", "no-span"],
)
def test_bulk_add_share_reads_the_batch_add_spans(adds, want):
    from chipbench import run as harness

    manifest = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    (entry,) = [m for m in manifest["per_layer"] if m["name"] == "bulk_add_share"]
    assert (entry["unit"], entry["better"], entry["source"], entry["moves"]) == (
        "%", "higher", "program_span", "commits_per_s",
    )  # fmt: skip
    assert entry["workloads"] == [w["name"] for w in manifest["workloads"][:4]]
    assert entry["layer"] == "batch seam (crypto/batch.py, crypto/tpu_verifier.py)"
    spans = [
        _span(i + 1, "batch_add", key=key, sigs=sigs, **({} if bulk is None else {"bulk": bulk}))
        for i, (key, sigs, bulk) in enumerate(adds)
    ]
    ctx = types.SimpleNamespace(spans=spans, requests=1)
    assert harness.load_module("layer_metrics", "bulk_add_share").read(ctx) == want
