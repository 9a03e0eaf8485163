"""Verified-signature cache (crypto/sigcache): safety and bounds.

The cache may only ever skip work a fresh verify would repeat — any
byte difference (forged signature, mutated sign-bytes, an equivocating
vote's other block) is a miss by construction, and every error the
uncached paths raise must be byte-identical with the cache warm, cold,
and disabled. The counting-stub smoke test is the CI tripwire the
bench can't be: a warm verify_commit must perform ZERO underlying
signature verifications (and the expected N when disabled), so a cache
regression fails the suite rather than a bench row.
"""

import pytest

from tendermint_tpu.crypto import sigcache
from tendermint_tpu.crypto.ed25519 import Ed25519BatchVerifier, PubKeyEd25519
from tendermint_tpu.types import (
    PRECOMMIT_TYPE,
    InvalidCommitError,
    VoteSet,
    verify_commit,
)
from tendermint_tpu.types.validation import verify_triples_grouped
from tendermint_tpu.types.vote_set import ConflictingVoteError

from .test_types import (
    CHAIN_ID,
    make_block_id,
    make_validators,
    signed_vote,
)
from .test_validation import make_commit


@pytest.fixture(autouse=True)
def fresh_cache():
    """Each test starts cold and restores the default capacity."""
    sigcache.reset()
    sigcache.set_capacity(sigcache.DEFAULT_CAPACITY)
    yield
    sigcache.reset()
    sigcache.set_capacity(sigcache.DEFAULT_CAPACITY)


class CountingStub:
    """Counts underlying signature verifications through both seams:
    single verifies (PubKeyEd25519.verify_signature) and batch drains
    (Ed25519BatchVerifier.verify, counted per queued item)."""

    def __init__(self, monkeypatch):
        self.singles = 0
        self.batched = 0
        stub = self
        real_single = PubKeyEd25519.verify_signature
        real_batch = Ed25519BatchVerifier.verify

        def counting_single(pk_self, msg, sig):
            stub.singles += 1
            return real_single(pk_self, msg, sig)

        def counting_batch(bv_self):
            stub.batched += len(bv_self._items)
            return real_batch(bv_self)

        monkeypatch.setattr(
            PubKeyEd25519, "verify_signature", counting_single
        )
        monkeypatch.setattr(Ed25519BatchVerifier, "verify", counting_batch)

    @property
    def total(self):
        return self.singles + self.batched

    def reset(self):
        self.singles = 0
        self.batched = 0


# -- cache mechanics --


def test_exact_triple_keying():
    pk, sb, sig = b"\x01" * 32, b"sign-bytes", b"\x02" * 64
    sigcache.add(pk, sb, sig)
    assert sigcache.seen(pk, sb, sig)
    # any byte difference in any component is a miss
    assert not sigcache.seen(b"\x03" + pk[1:], sb, sig)
    assert not sigcache.seen(pk, sb + b"x", sig)
    assert not sigcache.seen(pk, sb, sig[:-1] + b"\x00")


def test_component_boundaries_unambiguous():
    """Shifting bytes between sign_bytes and signature (or pubkey) must
    never alias: the key length-prefixes the fixed-size components."""
    sigcache.add(b"\x01" * 32, b"ab", b"\x02" * 64)
    assert not sigcache.seen(b"\x01" * 32, b"a", b"b" + b"\x02" * 63)


def test_generation_rotation_is_bounded():
    sigcache.set_capacity(100)
    base = sigcache.stats()["evictions"]
    for i in range(1000):
        sigcache.add(b"\x01" * 32, b"msg-%d" % i, b"\x02" * 64)
    # two generations of at most `capacity` entries each
    assert sigcache.entries() <= 200
    assert sigcache.stats()["evictions"] > base


def test_promotion_survives_rotation():
    """A stable signer set's triples outlive rotation: a hit in the old
    generation is promoted into the young one."""
    sigcache.set_capacity(10)
    hot = (b"\x07" * 32, b"hot-triple", b"\x08" * 64)
    sigcache.add(*hot)
    for i in range(200):
        sigcache.add(b"\x01" * 32, b"churn-%d" % i, b"\x02" * 64)
        assert sigcache.seen(*hot)  # each consult re-promotes


def test_disabled_scope_drops_inserts_and_misses():
    with sigcache.disabled():
        assert not sigcache.enabled()
        sigcache.add(b"\x01" * 32, b"m", b"\x02" * 64)
        assert not sigcache.seen(b"\x01" * 32, b"m", b"\x02" * 64)
    assert sigcache.entries() == 0


def test_disabled_scope():
    with sigcache.disabled():
        assert not sigcache.enabled()
    assert sigcache.enabled()


# -- bulk API: one set-intersection replaces the per-triple probes --


def test_bulk_probe_hits_and_promotes():
    keys = [(b"\x01" * 32, b"msg-%d" % i, b"\x02" * 64) for i in range(6)]
    for k in keys[:3]:
        sigcache.add_key(k)
    hits = sigcache.seen_keys_bulk(keys)
    assert hits == set(keys[:3])
    # old-generation hits are promoted, like seen_key
    sigcache.set_capacity(4)
    sigcache.reset()
    hot = (b"\x07" * 32, b"hot", b"\x08" * 64)
    sigcache.add_key(hot)
    for i in range(20):
        sigcache.add_key((b"\x01" * 32, b"churn-%d" % i, b"\x02" * 64))
        assert sigcache.seen_keys_bulk([hot]) == {hot}  # re-promoted
    assert sigcache.seen_keys_bulk([]) == set()


def test_bulk_add_respects_generation_bound():
    sigcache.set_capacity(100)
    base = sigcache.stats()["evictions"]
    for start in range(0, 1000, 250):
        sigcache.add_keys_bulk(
            (b"\x01" * 32, b"bulk-%d" % i, b"\x02" * 64)
            for i in range(start, start + 250)
        )
        # the documented bound survives bulk drains bigger than a
        # whole generation: at most 2 x capacity resident
        assert sigcache.entries() <= 200
    assert sigcache.stats()["evictions"] > base


def test_commit_memo_gates():
    key = ("commit-memo", "chain", True, True, 1, object(), object(), b"")
    sigcache.add_commit(key)
    assert sigcache.seen_commit(key)
    with sigcache.commit_memo_disabled():
        assert not sigcache.commit_memo_enabled()
        assert not sigcache.seen_commit(key)  # probe disabled
        sigcache.add_commit(key)  # insert dropped silently
    assert sigcache.seen_commit(key)
    with sigcache.disabled():  # the cache-wide gate covers commit keys
        assert not sigcache.commit_memo_enabled()
        assert not sigcache.seen_commit(key)


def test_commit_memo_scope_leaves_triples_on():
    with sigcache.commit_memo_disabled():
        assert sigcache.enabled()  # triples unaffected
        assert not sigcache.commit_memo_enabled()
    assert sigcache.commit_memo_enabled()


# -- safety: failures never cached, errors identical warm/cold/disabled --


def test_forged_signature_never_hits():
    vals, bid, commit = make_commit(4)
    verify_commit(CHAIN_ID, vals, bid, 1, commit)  # warm the good sigs
    forged = bytearray(commit.signatures[2].signature)
    forged[0] ^= 0xFF
    commit.signatures[2].signature = bytes(forged)
    # the forged triple differs in bytes -> miss -> real verify -> fail,
    # warm or not, and the failure is never inserted
    for _ in range(2):
        with pytest.raises(InvalidCommitError, match=r"#2"):
            verify_commit(CHAIN_ID, vals, bid, 1, commit)
    sb = commit.vote_sign_bytes(CHAIN_ID, 2)
    assert not sigcache.seen_key(
        sigcache.key_for(
            vals.validators[2].pub_key.bytes(),
            sb,
            commit.signatures[2].signature,
        )
    )


def test_mutated_sign_bytes_never_hit():
    vals, bid, commit = make_commit(4)
    verify_commit(CHAIN_ID, vals, bid, 1, commit)
    # same signatures presented over different sign-bytes (wrong chain)
    # must all miss and fail verification
    with pytest.raises(InvalidCommitError, match="wrong signature"):
        verify_commit("other-chain", vals, bid, 1, commit)


def test_wrong_signature_error_identical_warm_cold_disabled():
    """The `wrong signature (#i)` index attribution must not depend on
    cache state: warm (good sigs cached), cold, and disabled runs all
    raise the same error."""
    vals, bid, commit = make_commit(4)
    forged = bytearray(commit.signatures[1].signature)
    forged[3] ^= 0x10
    commit.signatures[1].signature = bytes(forged)

    def error_text():
        with pytest.raises(InvalidCommitError) as ei:
            verify_commit(CHAIN_ID, vals, bid, 1, commit)
        return str(ei.value)

    cold = error_text()
    warm = error_text()  # good sigs were cached by the cold attempt
    sigcache.reset()
    with sigcache.disabled():
        off = error_text()
    assert cold == warm == off
    assert "wrong signature (#1)" in cold


def test_equivocating_vote_conflict_identical():
    """An equivocating vote (same validator, different block) is a
    different triple — never a hit — and ConflictingVoteError fires
    identically warm, cold, and disabled."""

    def run():
        vals, privs = make_validators(4)
        vs = VoteSet(CHAIN_ID, 1, 0, PRECOMMIT_TYPE, vals)
        a = signed_vote(privs[0], vals, 0, make_block_id(b"\x0a"))
        b = signed_vote(privs[0], vals, 0, make_block_id(b"\x0b"))
        assert vs.add_vote(a)
        with pytest.raises(ConflictingVoteError) as ei:
            vs.add_vote(b)
        return str(ei.value)

    cold = run()
    warm = run()  # both triples cached by the first pass
    with sigcache.disabled():
        off = run()
    assert cold == warm == off


# -- the CI tripwire: warm commits do zero crypto --


def test_warm_verify_commit_does_zero_signature_verifications(monkeypatch):
    stub = CountingStub(monkeypatch)
    vals, bid, commit = make_commit(5)
    n_sigs = 5
    verify_commit(CHAIN_ID, vals, bid, 1, commit)
    assert stub.batched == n_sigs  # cold: every signature verified
    stub.reset()
    verify_commit(CHAIN_ID, vals, bid, 1, commit)
    assert stub.total == 0  # warm: a hash scan, no crypto at all
    # disabled: the full N again, through the same code path
    stub.reset()
    with sigcache.disabled():
        verify_commit(CHAIN_ID, vals, bid, 1, commit)
    assert stub.batched == n_sigs


def test_warm_vote_set_ingest_does_zero_verifications(monkeypatch):
    """add_vote after verify-ahead population: Vote.verify hits the
    cache (the cross-stage half: gossip-verify warms LastCommit and
    vice versa)."""
    stub = CountingStub(monkeypatch)
    vals, privs = make_validators(4)
    bid = make_block_id(b"\x0c")
    votes = [signed_vote(p, vals, i, bid) for i, p in enumerate(privs)]
    # populate as _preverify_votes would (batch verify + cache insert)
    from tendermint_tpu.crypto.batch import (
        create_batch_verifier,
        drain_and_cache,
    )

    bv = create_batch_verifier(privs[0].pub_key(), size_hint=4)
    keys = []
    for v, p in zip(votes, privs):
        sb = v.sign_bytes(CHAIN_ID)
        bv.add(p.pub_key(), sb, v.signature)
        keys.append(sigcache.key_for(p.pub_key().bytes(), sb, v.signature))
    ok, _ = drain_and_cache(bv, keys)
    assert ok
    stub.reset()
    vs = VoteSet(CHAIN_ID, 1, 0, PRECOMMIT_TYPE, vals)
    for v in votes:
        assert vs.add_vote(v)
    assert stub.total == 0


def test_merged_triples_warm_and_group_sized(monkeypatch):
    """verify_triples_grouped consults before assembly (second call is
    crypto-free) and sizes each per-type batch to its own group, not
    the merged total."""
    hints = []
    from tendermint_tpu.crypto import batch as crypto_batch

    real_create = crypto_batch.create_batch_verifier

    def spying_create(pk, size_hint=0):
        hints.append((pk.type(), size_hint))
        return real_create(pk, size_hint=size_hint)

    # the drain both validation entry points share creates the
    # verifiers (crypto.batch.drain_classes)
    monkeypatch.setattr(
        crypto_batch, "create_batch_verifier", spying_create
    )
    vals, privs = make_validators(3)
    bid = make_block_id(b"\x0d")
    triples = []
    for i, p in enumerate(privs):
        v = signed_vote(p, vals, i, bid)
        triples.append(
            (p.pub_key(), v.sign_bytes(CHAIN_ID), v.signature)
        )
    try:
        from tendermint_tpu.crypto.sr25519 import PrivKeySr25519

        sr = PrivKeySr25519.from_seed(b"\x31" * 32)
        msg = b"merged-group-msg"
        triples.append((sr.pub_key(), msg, sr.sign(msg)))
    except ImportError:
        sr = None
    verify_triples_grouped(triples)
    # each group's bucket pads to its own size, not len(triples)
    want = {("ed25519", 3)}
    if sr is not None:
        want.add(("sr25519", 1))
    assert set(hints) == want
    # warm: no verifier is even created
    hints.clear()
    stub = CountingStub(monkeypatch)
    verify_triples_grouped(triples)
    assert hints == [] and stub.total == 0


def test_bounded_over_many_heights():
    """The acceptance bound: heights of churn never grow the cache past
    two generations (the 100-height localnet shape, compressed)."""
    sigcache.set_capacity(100)
    vals, privs = make_validators(4)
    for height in range(1, 101):
        bid = make_block_id(bytes([height]))
        vs = VoteSet(CHAIN_ID, height, 0, PRECOMMIT_TYPE, vals)
        for i, p in enumerate(privs):
            vs.add_vote(signed_vote(p, vals, i, bid, height=height))
        commit = vs.make_commit()
        verify_commit(CHAIN_ID, vals, bid, height, commit)
        assert sigcache.entries() <= 200  # 2 generations x capacity
