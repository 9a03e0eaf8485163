"""Warm commit verification (ISSUE 7): zero-encode/zero-crypto guards,
memo safety, and byte-identical vectorized early exits.

Three families:

- **Counting-stub guards** — the fully-warm verify_commit path must
  perform ZERO canonical-vote encodes (the commit-scoped sign-bytes
  memo) and ZERO underlying signature verifications (sigcache), through
  every seam that can produce either; with the cache disabled the full
  crypto count returns while encodes stay memoized (determinism makes
  the sign-bytes memo legal even then).

- **Memo safety** — a memo may never change an outcome: chain_id
  mismatches miss; a mutated signature or timestamp is rejected with
  byte-identical errors warm/cold/disabled (the _MUT_EPOCH hook); an
  in-place ValidatorSet power mutation invalidates the commit-level
  memo (live powers fingerprint — the ADVICE-r5 staleness class).

- **Property tests** — the vectorized plans (masked-sum tally, prefix
  -sum early exit, bulk probe) must stop at the same vote, verify the
  same signature set, and raise the same error strings as the scalar
  reference loop (_verify_commit_batch_scalar), over randomized
  flag/power layouts including forged signatures, insufficient power,
  duplicate and unknown addresses. The scalar arm is forced exactly
  the way a hostile commit forces it: block_id_flags_array() -> None.
"""

import contextlib

import numpy as np
import pytest

from tendermint_tpu.crypto import sigcache
from tendermint_tpu.crypto.ed25519 import (
    Ed25519BatchVerifier,
    PrivKeyEd25519,
    PubKeyEd25519,
)
from tendermint_tpu.types import canonical
from tendermint_tpu.types.block_id import BlockID
from tendermint_tpu.types.canonical import PRECOMMIT_TYPE, VoteSignTemplate
from tendermint_tpu.types.commit import Commit, CommitSig
from tendermint_tpu.types.validation import (
    InvalidCommitError,
    Fraction,
    NotEnoughVotingPowerError,
    collect_commit_light,
    verify_commit,
    verify_commit_light,
    verify_commit_light_trusting,
)
from tendermint_tpu.types.validator import Validator, ValidatorSet
from tendermint_tpu.types.vote import Vote

from .test_types import CHAIN_ID, make_block_id, make_validators
from .test_validation import make_commit


@pytest.fixture(autouse=True)
def fresh_cache():
    # a device factory left installed by an earlier test FILE would
    # route create_batch_verifier around the Ed25519BatchVerifier seam
    # the counting stubs patch — uninstall so the counts mean what the
    # guards assert regardless of suite ordering
    from tendermint_tpu.crypto import tpu_verifier

    tpu_verifier.uninstall()
    sigcache.reset()
    sigcache.set_capacity(sigcache.DEFAULT_CAPACITY)
    yield
    sigcache.reset()
    sigcache.set_capacity(sigcache.DEFAULT_CAPACITY)


# ---------------------------------------------------------------------------
# helpers


@contextlib.contextmanager
def scalar_reference():
    """Force the scalar reference loop the same way a hostile commit
    does: the flags memo reports unusable."""
    orig = Commit.block_id_flags_array
    Commit.block_id_flags_array = lambda self: None
    try:
        yield
    finally:
        Commit.block_id_flags_array = orig


class Counters:
    """Counts both crypto seams (single + batch verifies) and both
    encode seams (template splice single + batch, plus the plain
    canonical encoder Vote.sign_bytes bottoms out in)."""

    def __init__(self):
        self.singles = 0
        self.batched = 0
        self.encodes = 0

    @property
    def verifies(self):
        return self.singles + self.batched


@contextlib.contextmanager
def counting(monkeypatch_like=None):
    c = Counters()
    real_single = PubKeyEd25519.verify_signature
    real_batch = Ed25519BatchVerifier.verify
    real_tpl_one = VoteSignTemplate.sign_bytes
    real_tpl_batch = VoteSignTemplate.sign_bytes_batch
    real_canonical = canonical.vote_sign_bytes

    def counting_single(pk_self, msg, sig):
        c.singles += 1
        return real_single(pk_self, msg, sig)

    def counting_batch(bv_self):
        c.batched += len(bv_self._items)
        return real_batch(bv_self)

    def counting_tpl_one(tpl_self, ts):
        c.encodes += 1
        return real_tpl_one(tpl_self, ts)

    def counting_tpl_batch(tpl_self, timestamps):
        timestamps = list(timestamps)
        c.encodes += len(timestamps)
        return real_tpl_batch(tpl_self, timestamps)

    def counting_canonical(*a, **kw):
        c.encodes += 1
        return real_canonical(*a, **kw)

    PubKeyEd25519.verify_signature = counting_single
    Ed25519BatchVerifier.verify = counting_batch
    VoteSignTemplate.sign_bytes = counting_tpl_one
    VoteSignTemplate.sign_bytes_batch = counting_tpl_batch
    canonical.vote_sign_bytes = counting_canonical
    try:
        yield c
    finally:
        PubKeyEd25519.verify_signature = real_single
        Ed25519BatchVerifier.verify = real_batch
        VoteSignTemplate.sign_bytes = real_tpl_one
        VoteSignTemplate.sign_bytes_batch = real_tpl_batch
        canonical.vote_sign_bytes = real_canonical


def _signed_commit_sig(priv, addr, bid, height, round_, ts, nil=False):
    vote = Vote(
        type=PRECOMMIT_TYPE,
        height=height,
        round=round_,
        block_id=BlockID() if nil else bid,
        timestamp_ns=ts,
        validator_address=addr,
        validator_index=0,
    )
    sig = priv.sign(vote.sign_bytes(CHAIN_ID))
    if nil:
        return CommitSig.for_nil(sig, addr, ts)
    return CommitSig.for_block(sig, addr, ts)


def _random_layout(rng, n, forge=False):
    """A commit over n validators with randomized powers and a random
    ABSENT/NIL/COMMIT flag layout (>=2 non-absent so the batch path
    engages); optionally one forged signature at a random non-absent
    index."""
    privs = [PrivKeyEd25519.from_seed(bytes([i + 1]) * 32) for i in range(n)]
    powers = [int(rng.integers(1, 60)) for _ in range(n)]
    vals = ValidatorSet(
        [
            Validator(pub_key=p.pub_key(), voting_power=pw)
            for p, pw in zip(privs, powers)
        ]
    )
    by_addr = {p.pub_key().address(): p for p in privs}
    bid = make_block_id(b"\x0e")
    sigs = []
    n_signed = 0
    for v in vals.validators:
        r = float(rng.random())
        if r < 0.2:
            sigs.append(CommitSig.absent())
            continue
        nil = r < 0.35
        sigs.append(
            _signed_commit_sig(
                by_addr[v.address], v.address, bid, 1, 0, 1000, nil=nil
            )
        )
        n_signed += 1
    if n_signed < 2:
        # force the batch path: sign the first two validators
        for i in (0, 1):
            v = vals.validators[i]
            sigs[i] = _signed_commit_sig(
                by_addr[v.address], v.address, bid, 1, 0, 1000
            )
    commit = Commit(height=1, round=0, block_id=bid, signatures=sigs)
    if forge:
        non_absent = [
            i for i, cs in enumerate(sigs) if not cs.is_absent()
        ]
        j = int(rng.choice(non_absent))
        forged = bytearray(sigs[j].signature)
        forged[0] ^= 0xFF
        sigs[j].signature = bytes(forged)
    return vals, bid, commit


def _run_arm(fn, scalar):
    """One cold run of a verification callable: (error string or None,
    verify count, frozenset of cached triple keys)."""
    sigcache.reset()
    ctx = scalar_reference() if scalar else contextlib.nullcontext()
    err = None
    with counting() as c, ctx:
        try:
            fn()
        except Exception as e:  # noqa: BLE001 - error parity is the point
            err = f"{type(e).__name__}: {e}"
    cached = frozenset(
        k for k in (sigcache._gen0 | sigcache._gen1) if len(k) == 3
    )
    return err, c.verifies, cached


def _assert_arms_identical(fn, label):
    """The vectorized plan and the scalar reference must agree on the
    outcome, the number of signatures verified (the early-exit stop
    point), and the exact triples proven (which signatures were
    checked)."""
    v_err, v_cnt, v_keys = _run_arm(fn, scalar=False)
    s_err, s_cnt, s_keys = _run_arm(fn, scalar=True)
    assert v_err == s_err, f"{label}: error diverged\n  vector: {v_err}\n  scalar: {s_err}"
    assert v_cnt == s_cnt, f"{label}: verify count diverged ({v_cnt} vs {s_cnt}); err={v_err}"
    assert v_keys == s_keys, f"{label}: proven triple sets diverged"


# ---------------------------------------------------------------------------
# tier-1 counting-stub guards: warm = zero encodes AND zero verifies


def test_fully_warm_commit_zero_encodes_zero_verifies():
    vals, bid, commit = make_commit(6)
    with counting() as cold:
        verify_commit(CHAIN_ID, vals, bid, 1, commit)
    assert cold.verifies == 6
    assert cold.encodes >= 6  # sanity: the encode seam is counted
    with counting() as warm:
        verify_commit(CHAIN_ID, vals, bid, 1, commit)
    assert warm.verifies == 0
    assert warm.encodes == 0
    # the commit-level memo short-circuits the second warm pass: zero
    # triple probes on top of zero crypto/encodes
    s0 = sigcache.stats()
    with counting() as warm2:
        verify_commit(CHAIN_ID, vals, bid, 1, commit)
    s1 = sigcache.stats()
    assert warm2.verifies == 0 and warm2.encodes == 0
    assert s1["commit_hits"] - s0["commit_hits"] == 1
    assert s1["hits"] - s0["hits"] == 0  # no per-triple scan at all
    # disabled: the full crypto count returns through the same path;
    # encodes stay memoized (pure function of frozen inputs)
    with sigcache.disabled():
        with counting() as off:
            verify_commit(CHAIN_ID, vals, bid, 1, commit)
    assert off.verifies == 6
    assert off.encodes == 0


def test_fully_warm_light_and_trusting_zero_encodes(monkeypatch):
    vals, bid, commit = make_commit(6)
    verify_commit_light(CHAIN_ID, vals, bid, 1, commit)
    with counting() as warm:
        verify_commit_light(CHAIN_ID, vals, bid, 1, commit)
        verify_commit_light_trusting(CHAIN_ID, vals, commit, Fraction(1, 3))
    assert warm.verifies == 0
    assert warm.encodes == 0


def test_fresh_commit_object_same_bytes_still_warm():
    """The cross-HEIGHT warm shape: LastCommit arrives as a NEW Commit
    object with the same wire content. Triple keys are value-equal, so
    the bulk probe fully hits (zero crypto, fresh encodes only)."""
    vals, bid, commit = make_commit(5)
    verify_commit(CHAIN_ID, vals, bid, 1, commit)
    clone = Commit.from_proto(commit.to_proto())
    with counting() as c:
        verify_commit(CHAIN_ID, vals, bid, 1, clone)
    assert c.verifies == 0  # all 5 triples proven via the bulk probe
    assert c.encodes == 5  # a new object encodes once, then memoizes


# ---------------------------------------------------------------------------
# memo safety


def test_chain_id_mismatch_misses_and_fails():
    """The sign-bytes memo is keyed per chain_id and the commit memo
    binds it: warming on one chain must not leak into another."""
    vals, bid, commit = make_commit(4)
    verify_commit(CHAIN_ID, vals, bid, 1, commit)
    with pytest.raises(InvalidCommitError, match="wrong signature"):
        verify_commit("other-chain", vals, bid, 1, commit)
    # and the original chain is still warm and correct
    with counting() as c:
        verify_commit(CHAIN_ID, vals, bid, 1, commit)
    assert c.verifies == 0


def _error_text(fn):
    with pytest.raises(InvalidCommitError) as ei:
        fn()
    return str(ei.value)


def test_mutated_timestamp_rejected_identically_warm_cold_disabled():
    """A post-construction timestamp write changes the signed bytes:
    the _MUT_EPOCH hook must drop the sign-bytes memo AND the
    commit-level memo, so the warm path re-encodes, misses, and fails
    with the reference error — byte-identical to cold and disabled."""
    vals, bid, commit = make_commit(4)
    verify_commit(CHAIN_ID, vals, bid, 1, commit)  # fully warm + memoized
    commit.signatures[2].timestamp_ns += 1

    def run():
        return _error_text(
            lambda: verify_commit(CHAIN_ID, vals, bid, 1, commit)
        )

    warm = run()
    sigcache.reset()
    cold = run()
    with sigcache.disabled():
        off = run()
    assert warm == cold == off
    assert "wrong signature (#2)" in warm


def test_mutated_signature_rejected_identically_with_commit_memo():
    """Same for a signature write: the commit-level memo recorded by
    the first verify must not survive the mutation."""
    vals, bid, commit = make_commit(4)
    verify_commit(CHAIN_ID, vals, bid, 1, commit)
    forged = bytearray(commit.signatures[1].signature)
    forged[3] ^= 0x10
    commit.signatures[1].signature = bytes(forged)

    def run():
        return _error_text(
            lambda: verify_commit(CHAIN_ID, vals, bid, 1, commit)
        )

    warm = run()
    sigcache.reset()
    cold = run()
    with sigcache.disabled():
        off = run()
    assert warm == cold == off
    assert "wrong signature (#1)" in warm


def test_inplace_power_mutation_invalidates_commit_memo():
    """The ADVICE-r5 staleness class: an in-place voting_power write
    does not pass through _reindex, so the commit-memo key covers the
    LIVE powers bytes. Shrinking the signers' power below 2/3 must
    surface as NotEnoughVotingPower, never as a stale memo hit."""
    vals, bid, commit = make_commit(4, signers={0, 1, 2})
    verify_commit(CHAIN_ID, vals, bid, 1, commit)  # 30 of 40 > 26
    s0 = sigcache.stats()
    for i in range(3):
        vals.validators[i].voting_power = 1  # live tally: 3 + 10 absent
    with pytest.raises(NotEnoughVotingPowerError):
        verify_commit(CHAIN_ID, vals, bid, 1, commit)
    s1 = sigcache.stats()
    assert s1["commit_hits"] == s0["commit_hits"]  # key changed: no hit
    assert s1["commit_misses"] > s0["commit_misses"]


def test_inplace_pubkey_swap_invalidates_commit_memo():
    """An in-place pub_key re-assignment moves neither fingerprint
    token nor the powers bytes, so the commit-memo key binds the
    validator-mutation epoch (_VAL_MUT_EPOCH) too: the next verify
    must rebuild real keys against the NEW pub_key and reject the old
    signatures, never serve the stale success."""
    vals, bid, commit = make_commit(4)
    verify_commit(CHAIN_ID, vals, bid, 1, commit)
    s0 = sigcache.stats()
    vals.validators[1].pub_key = PrivKeyEd25519.from_seed(
        b"\x5a" * 32
    ).pub_key()
    with pytest.raises(InvalidCommitError):
        verify_commit(CHAIN_ID, vals, bid, 1, commit)
    s1 = sigcache.stats()
    assert s1["commit_hits"] == s0["commit_hits"]  # epoch moved: no hit


def test_validator_set_fingerprint_token_identity():
    vals, _ = make_validators(3)
    t = vals.fingerprint_token()
    assert vals.fingerprint_token() is t
    assert vals.copy().fingerprint_token() is not t  # copies diverge
    vals.update_with_change_set(
        [Validator(pub_key=PrivKeyEd25519.from_seed(b"\x77" * 32).pub_key(),
                   voting_power=5)]
    )
    assert vals.fingerprint_token() is not t  # membership change


def test_commit_fingerprint_token_replaced_on_mutation():
    _, _, commit = make_commit(3)
    t = commit.fingerprint_token()
    assert commit.fingerprint_token() is t
    commit.signatures[0].timestamp_ns += 1
    assert commit.fingerprint_token() is not t


def test_sign_bytes_memo_matches_fresh_encode():
    """The memoized rows must be byte-identical to a fresh encode of
    the reconstructed votes (the PR-2 contract, now across the memo)."""
    vals, bid, commit = make_commit(5, signers={0, 1, 2, 4})
    rows = commit.sign_bytes_batch(CHAIN_ID)
    again = commit.sign_bytes_batch(CHAIN_ID)
    assert rows is again  # memo hit returns the same list
    for i, cs in enumerate(commit.signatures):
        if cs.is_absent():
            assert rows[i] is None
            continue
        assert rows[i] == commit.get_vote(i).sign_bytes(CHAIN_ID)
        assert commit.vote_sign_bytes(CHAIN_ID, i) == rows[i]


def test_lazy_vote_sign_bytes_shares_rows_with_batch():
    vals, bid, commit = make_commit(4)
    a = commit.vote_sign_bytes(CHAIN_ID, 2)  # lazy fill first
    rows = commit.sign_bytes_batch(CHAIN_ID)  # completes the rest
    assert rows[2] == a
    assert all(rows[i] is not None for i in range(4))


# ---------------------------------------------------------------------------
# property tests: vectorized plans vs the scalar reference loop


N_SEEDS = 24


@pytest.mark.parametrize("seed", range(N_SEEDS))
def test_property_verify_commit_vector_matches_scalar(seed):
    rng = np.random.default_rng(seed)
    n = int(rng.integers(4, 24))
    vals, bid, commit = _random_layout(rng, n, forge=(seed % 3 == 0))
    _assert_arms_identical(
        lambda: verify_commit(CHAIN_ID, vals, bid, 1, commit),
        f"verify_commit seed={seed}",
    )


@pytest.mark.parametrize("seed", range(N_SEEDS))
def test_property_light_early_exit_matches_scalar(seed):
    rng = np.random.default_rng(1000 + seed)
    n = int(rng.integers(4, 24))
    vals, bid, commit = _random_layout(rng, n, forge=(seed % 3 == 0))
    _assert_arms_identical(
        lambda: verify_commit_light(CHAIN_ID, vals, bid, 1, commit),
        f"verify_commit_light seed={seed}",
    )


@pytest.mark.parametrize("seed", range(N_SEEDS))
def test_property_trusting_early_exit_matches_scalar(seed):
    rng = np.random.default_rng(2000 + seed)
    n = int(rng.integers(4, 24))
    vals, bid, commit = _random_layout(rng, n, forge=(seed % 4 == 0))
    sigs = commit.signatures
    non_absent = [i for i, cs in enumerate(sigs) if not cs.is_absent()]
    if seed % 3 == 0 and len(non_absent) >= 2:
        # duplicate address: the second occurrence must raise the
        # reference double-vote error iff the scan reaches it
        i, j = non_absent[0], non_absent[-1]
        sigs[j].validator_address = sigs[i].validator_address
    if seed % 5 == 0 and non_absent:
        # unknown address: skipped without verification
        sigs[non_absent[-1]].validator_address = b"\xfe" * 20
    trust = Fraction(1, 3) if seed % 2 else Fraction(2, 3)
    _assert_arms_identical(
        lambda: verify_commit_light_trusting(CHAIN_ID, vals, commit, trust),
        f"verify_commit_light_trusting seed={seed}",
    )


@pytest.mark.parametrize("seed", range(8))
def test_property_collect_commit_light_matches_scalar(seed):
    rng = np.random.default_rng(3000 + seed)
    n = int(rng.integers(4, 20))
    vals, bid, commit = _random_layout(rng, n)

    def run(scalar):
        ctx = scalar_reference() if scalar else contextlib.nullcontext()
        with ctx:
            try:
                triples, indexes = collect_commit_light(
                    CHAIN_ID, vals, bid, 1, commit
                )
                assert [
                    commit.signatures[i].signature for i in indexes
                ] == [sig for _pk, _sb, sig in triples]
                return [
                    (pk.bytes(), sb, sig, i)
                    for (pk, sb, sig), i in zip(triples, indexes)
                ], None
            except Exception as e:  # noqa: BLE001
                return None, f"{type(e).__name__}: {e}"

    v_t, v_err = run(False)
    s_t, s_err = run(True)
    assert v_err == s_err
    assert v_t == s_t  # same triples, same order, same stop point


def test_light_early_exit_stop_index_exact():
    """Deterministic pin of the prefix-sum crossing: with powers
    10,10,10,10 and 2/3 of 40 = 26, the light loop must stop after the
    THIRD for-block vote — the fourth signature is never verified, so
    forging it must not fail the verify (reference semantics)."""
    vals, bid, commit = make_commit(4)
    forged = bytearray(commit.signatures[3].signature)
    forged[0] ^= 0xFF
    commit.signatures[3].signature = bytes(forged)
    with counting() as c:
        verify_commit_light(CHAIN_ID, vals, bid, 1, commit)  # no raise
    assert c.verifies == 3
    # verify_commit checks ALL signatures and must reject the forgery
    with pytest.raises(InvalidCommitError, match=r"#3"):
        verify_commit(CHAIN_ID, vals, bid, 1, commit)


def test_insufficient_power_error_identical():
    vals, bid, commit = make_commit(4, signers={0})  # 10 of 40
    for fn in (
        lambda: verify_commit(CHAIN_ID, vals, bid, 1, commit),
        lambda: verify_commit_light(CHAIN_ID, vals, bid, 1, commit),
    ):
        v_err, _, _ = _run_arm(fn, scalar=False)
        s_err, _, _ = _run_arm(fn, scalar=True)
        assert v_err == s_err
        assert "insufficient voting power" in v_err
