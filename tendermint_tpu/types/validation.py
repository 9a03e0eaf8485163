"""Commit verification — the framework's north-star hot path.

Mirrors types/validation.go exactly: VerifyCommit (:25, checks ALL sigs
for incentivization), VerifyCommitLight (:59, stops at 2/3),
VerifyCommitLightTrusting (:94, fraction of a *trusted* set, lookup by
address), and the batch/single pair (:152/:265). The batch path packs a
whole Commit's (pubkey, sign-bytes, signature) triples into one
crypto.batch verifier — on TPU that is a single device program over the
padded batch (tendermint_tpu.ops.ed25519_kernel), sharded across the
mesh for large validator sets (tendermint_tpu.parallel.sharding).

Every path here consults the process-wide verified-signature cache
(crypto.sigcache) BEFORE batch assembly and populates it on success:
only cache misses are assembled, so a LastCommit whose precommits were
gossip-verified re-verifies with zero crypto calls, and device buckets
pad to the real miss count. A sigcache.disabled() scope restores the
uncached behavior exactly (same errors, same tallies — just slower).

The WARM path additionally does zero encoding and (near-)zero per-vote
Python work (PERF.md "Warm path"): sign-bytes come from the commit-
scoped memo (Commit.sign_bytes_batch / vote_sign_bytes), the cache
scan is one bulk set-intersection (sigcache.seen_keys_bulk) instead of
a per-triple probe loop, tallies are masked-numpy sums / prefix-sums
over ValidatorSet.powers_array(), and a commit that verified fully
before short-circuits to the tally via the commit-level memo
(sigcache.seen_commit) in O(1) probes. Every vectorized plan computes
the SAME processed-index set and error as the scalar reference loop
(_verify_commit_batch_scalar — kept as the fallback for hostile
flag encodings and locked byte-identical by the property tests in
tests/test_warmpath.py); the memo-soundness argument is machine-
checked by `scripts/lint.py --memo-audit` (docs/static_analysis.md).
"""

from __future__ import annotations

import bisect
from collections import defaultdict
from dataclasses import dataclass
from operator import attrgetter, itemgetter
from typing import Callable, Optional

import numpy as np

from ..crypto import sigcache
from ..crypto.batch import Columns, drain_classes, supports_batch_verifier
from ..libs import trace
from .block_id import BlockID
from .commit import (
    BLOCK_ID_FLAG_ABSENT,
    BLOCK_ID_FLAG_COMMIT,
    Commit,
    CommitSig,
)
from .validator import ValidatorSet, key_type_codes

__all__ = [
    "BATCH_VERIFY_THRESHOLD",
    "Fraction",
    "NotEnoughVotingPowerError",
    "InvalidCommitError",
    "collect_commit_light",
    "verify_commit",
    "verify_commit_light",
    "verify_commit_light_bulk",
    "verify_commit_light_trusting",
    "verify_triples_grouped",
]

BATCH_VERIFY_THRESHOLD = 2  # reference: types/validation.go:12


@dataclass(frozen=True)
class Fraction:
    """Trust level, e.g. 1/3 (reference: libs/math/fraction.go)."""

    numerator: int
    denominator: int

    def validate(self) -> None:
        if self.denominator == 0:
            raise ValueError("fraction has zero denominator")


class InvalidCommitError(ValueError):
    """A commit failed verification. Where a bulk verification can say
    which of its inputs failed, the error carries it: `position`, the
    place in verify_triples_grouped's merged triple list; `row` and
    `index`, the commit among verify_commit_light_bulk's rows and the
    vote in it. None where the failure is not attributed."""

    position: Optional[int] = None
    row: Optional[int] = None
    index: Optional[int] = None


class NotEnoughVotingPowerError(InvalidCommitError):
    def __init__(self, got: int, needed: int) -> None:
        super().__init__(
            f"invalid commit -- insufficient voting power: got {got}, "
            f"needed more than {needed}"
        )
        self.got = got
        self.needed = needed


def _should_batch_verify(vals: ValidatorSet, commit: Commit) -> bool:
    return len(
        commit.signatures
    ) >= BATCH_VERIFY_THRESHOLD and supports_batch_verifier(
        vals.get_proposer().pub_key
    )


def verify_commit(
    chain_id: str,
    vals: ValidatorSet,
    block_id: BlockID,
    height: int,
    commit: Commit,
) -> None:
    """+2/3 signed, verifying ALL signatures (incentivization needs the
    full bitmap — reference: types/validation.go:18-51)."""
    _verify_basic(vals, commit, height, block_id)
    voting_power_needed = vals.total_voting_power() * 2 // 3
    ignore = lambda c: c.is_absent()  # noqa: E731
    count = lambda c: c.is_for_block()  # noqa: E731
    if _should_batch_verify(vals, commit):
        _verify_commit_batch(
            chain_id, vals, commit, voting_power_needed,
            ignore, count, True, True, vector_tally=True,
        )
    else:
        _verify_commit_single(
            chain_id, vals, commit, voting_power_needed,
            ignore, count, True, True,
        )


def verify_commit_light(
    chain_id: str,
    vals: ValidatorSet,
    block_id: BlockID,
    height: int,
    commit: Commit,
) -> None:
    """+2/3 signed, early exit once the tally crosses 2/3
    (reference: types/validation.go:55-85)."""
    _verify_basic(vals, commit, height, block_id)
    voting_power_needed = vals.total_voting_power() * 2 // 3
    ignore = lambda c: not c.is_for_block()  # noqa: E731
    count = lambda c: True  # noqa: E731
    if _should_batch_verify(vals, commit):
        _verify_commit_batch(
            chain_id, vals, commit, voting_power_needed,
            ignore, count, False, True, vector_tally=True,
        )
    else:
        _verify_commit_single(
            chain_id, vals, commit, voting_power_needed,
            ignore, count, False, True,
        )


def verify_commit_light_trusting(
    chain_id: str,
    vals: ValidatorSet,
    commit: Commit,
    trust_level: Fraction,
) -> None:
    """trust_level (e.g. 1/3) of a TRUSTED validator set signed; lookup
    by address since sets needn't match
    (reference: types/validation.go:87-131)."""
    if vals is None:
        raise InvalidCommitError("nil validator set")
    trust_level.validate()
    if commit is None:
        raise InvalidCommitError("nil commit")
    total_mul = vals.total_voting_power() * trust_level.numerator
    if total_mul >= 1 << 63:
        raise InvalidCommitError(
            "int64 overflow while calculating voting power needed"
        )
    voting_power_needed = total_mul // trust_level.denominator
    ignore = lambda c: not c.is_for_block()  # noqa: E731
    count = lambda c: True  # noqa: E731
    if _should_batch_verify(vals, commit):
        _verify_commit_batch(
            chain_id, vals, commit, voting_power_needed,
            ignore, count, False, False, vector_tally=True,
        )
    else:
        _verify_commit_single(
            chain_id, vals, commit, voting_power_needed,
            ignore, count, False, False,
        )


def collect_commit_light(
    chain_id: str,
    vals: ValidatorSet,
    block_id: BlockID,
    height: int,
    commit: Commit,
) -> tuple:
    """verify_commit_light's host-side half: run every non-signature
    check (set size, height, block ID, 2/3 tally with the same
    early-exit) and return (triples, indexes): the (pub_key,
    sign_bytes, signature) triples verify_commit_light would have
    signature-checked — without checking them — and the commit index
    of each triple's vote. Callers fold triples from MANY commits into
    one device batch (verify_commit_light_bulk) and name a failing
    triple's vote from its index. Mirrors the tally semantics of
    types/validation.go:55-85."""
    _verify_basic(vals, commit, height, block_id)
    voting_power_needed = vals.total_voting_power() * 2 // 3
    flags = commit.block_id_flags_array()
    if flags is not None:
        # prefix-sum form of the early-exit tally (the same
        # _prefix_crossing plan as the vectorized verify_commit_light,
        # under the same two spans): the crossing index is the exact
        # vote the reference loop below returns after, so the collected
        # triples are identical — and the per-index encodes hit the
        # commit-scoped sign-bytes memo
        with trace.span("commit_plan") as plan:
            powers = vals.powers_array()
            tallied, end = _prefix_crossing(
                np.where(flags == BLOCK_ID_FLAG_COMMIT, powers, 0),
                voting_power_needed,
            )
            if end is None:
                raise NotEnoughVotingPowerError(
                    tallied, voting_power_needed
                )
            indexes = np.flatnonzero(
                flags[:end] == BLOCK_ID_FLAG_COMMIT
            ).tolist()
            plan.set(processed=len(indexes))
        validators = vals.validators
        signatures = commit.signatures
        with trace.span("sign_bytes", rows=len(indexes)):
            vsb = commit.vote_sign_bytes
            return [
                (
                    validators[i].pub_key,
                    vsb(chain_id, i),
                    signatures[i].signature,
                )
                for i in indexes
            ], indexes
    # scalar reference loop (kept for hostile flag encodings); lazy
    # per-index encode: this early-exit variant skips nil votes and
    # stops at 2/3, so a full precompute would pay for rows it discards
    tallied = 0
    out = []
    indexes = []
    for idx, commit_sig in enumerate(commit.signatures):
        if not commit_sig.is_for_block():
            continue
        # look_up_by_index semantics (same-set verification)
        val = vals.validators[idx]
        out.append(
            (
                val.pub_key,
                commit.vote_sign_bytes(chain_id, idx),
                commit_sig.signature,
            )
        )
        indexes.append(idx)
        tallied += val.voting_power
        if tallied > voting_power_needed:
            return out, indexes
    raise NotEnoughVotingPowerError(tallied, voting_power_needed)


def verify_triples_grouped(triples) -> None:
    """One merged signature check over (pub_key, sign_bytes, signature)
    triples collected from MANY commits (collect_commit_light), grouped
    per key type — the same grouping _verify_commit_batch applies
    within one commit. Triples already proven by the verified-signature
    cache (crypto.sigcache) are skipped before assembly; the rest
    populate it as far as they are proven. On a false bit raises an
    InvalidCommitError whose `position` is the LOWEST failing place in
    `triples` over every key class, which the caller maps back to its
    commit and vote (verify_commit_light_bulk). Every place below it
    is then proven: a cache hit or a true bit. The one failure without
    a position is a key type no batch verifier is registered for (an
    embedder's; every type in the tree has one): it is checked inline
    while routing and raises before the batched classes have answered,
    so a lower place may still be bad; callers then re-verify per
    commit for the precise error (light/client.py's per-hop
    fallback)."""
    with trace.span(
        "batch_accumulate", sigs=len(triples), merged=True
    ):
        if not triples:
            return
        use_cache = sigcache.enabled()
        # the merged window as columns, a row a triple; assembly is
        # deferred so each class's size_hint is its OWN miss count, not
        # the merged total every device bucket would otherwise pad to
        pub_keys, messages, signatures = zip(*triples)
        key_bytes = keys = None
        # the misses' places in `triples`, ascending; None: all of them
        places = None
        if use_cache:
            # one bulk set-intersection over the whole merged window
            # replaces the per-triple generation probes (the light
            # client's 32-hop sequential windows are ~5k triples)
            with trace.span("sigcache_probe") as probe:
                key_bytes = [pk.bytes() for pk in pub_keys]
                keys = list(zip(key_bytes, messages, signatures))
                places, hits, misses = _cache_misses(keys)
                probe.set(hits=hits, misses=misses)
            sigcache.observe(hits, misses)
            trace.add_attrs(sigcache_hits=hits, sigcache_misses=misses)
        with trace.span("batch_route") as route:
            if key_bytes is None:
                key_bytes = [pk.bytes() for pk in pub_keys]
                keys = [None] * len(triples)
            pending, inline = _route_misses(
                *key_type_codes(pub_keys),
                Columns(
                    pub_keys, key_bytes, messages, signatures,
                    range(len(triples)), keys,
                ),
                places,
                lambda _position, _signature: InvalidCommitError(
                    "wrong signature in merged batch"
                ),
            )
            route.set(inline=inline)
        lowest = _drain_lowest_bad(pending)
        if lowest is not None:
            err = InvalidCommitError(
                f"wrong signature in merged batch (#{lowest})"
            )
            err.position = lowest
            raise err


def verify_commit_light_bulk(chain_id: str, rows) -> None:
    """One sigcache-aware pass over M commits' light verifications —
    the fleet-serving form of verify_commit_light. `rows` is a
    sequence of (vals, block_id, height, commit), verified in order.

    Extends the PR-7 warm machinery ACROSS commits instead of within
    one: each row first probes the commit-level memo (the SAME
    `_commit_memo_key` verify_commit_light's vectorized path writes,
    so the two paths warm each other) — a warm fleet pass is M O(1)
    probes plus M basic checks, zero key building and zero crypto.
    Misses run collect_commit_light (the reference tally with its
    exact NotEnoughVotingPowerError / _verify_basic errors) and the
    collected triples from ALL cold commits are proven in ONE merged
    call (verify_triples_grouped: one bulk sigcache set-intersection,
    one grouped batch verify); only then is each cold commit's memo
    recorded. A wrong signature raises the error verify_commit_light
    raises for its commit — the same class and text, `wrong signature
    (#<idx>): <signature hex>` of the lowest bad vote of the FIRST bad
    row — with `row` and `index` on the exception: every row before it
    is then proven and its memo recorded, no other's. A failure the
    merged check cannot place (verify_triples_grouped's inline key
    types) propagates without them, and callers needing the exact
    per-commit error re-verify per commit (light/client.py's per-hop
    fallback)."""
    rows = list(rows)
    with trace.span("verify_commit_light_bulk", commits=len(rows)):
        use_memo = sigcache.enabled() and sigcache.commit_memo_enabled()
        triples: list = []
        # a cold row: (row number, its first place in `triples`, the
        # commit index of each of its triples, its memo key)
        cold: list = []
        hits = 0
        for n, (vals, block_id, height, commit) in enumerate(rows):
            _verify_basic(vals, commit, height, block_id)
            ckey = None
            if use_memo:
                with trace.span("commit_plan") as plan:
                    needed = vals.total_voting_power() * 2 // 3
                    ckey = _commit_memo_key(
                        chain_id, vals, commit, needed, False, True,
                        vals.powers_array(),
                    )
                    memo_hit = sigcache.seen_commit(ckey)
                    plan.set(memo_hit=memo_hit)
                if memo_hit:
                    hits += 1
                    continue
            found, indexes = collect_commit_light(
                chain_id, vals, block_id, height, commit
            )
            cold.append((n, len(triples), indexes, ckey))
            triples.extend(found)
        if use_memo:
            trace.add_attrs(
                sigcache_commit_hits=hits, commits_cold=len(cold)
            )
        proven = len(cold)
        failure: Optional[InvalidCommitError] = None
        if triples:
            try:
                verify_triples_grouped(triples)
            except InvalidCommitError as e:
                if e.position is None:
                    raise
                # the cold row that holds the failing place: the last
                # one that starts at or below it
                proven = (
                    bisect.bisect_right(
                        [start for _n, start, _i, _k in cold], e.position
                    )
                    - 1
                )
                n, start, indexes, _ckey = cold[proven]
                idx = indexes[e.position - start]
                signature = rows[n][3].signatures[idx].signature
                failure = InvalidCommitError(
                    f"wrong signature (#{idx}): {signature.hex()}"
                )
                failure.row, failure.index = n, idx
        for _n, _start, _indexes, ckey in cold[:proven]:
            if ckey is not None:
                sigcache.add_commit(ckey)
        if failure is not None:
            raise failure


def _verify_basic(
    vals: Optional[ValidatorSet],
    commit: Optional[Commit],
    height: int,
    block_id: BlockID,
) -> None:
    """reference: types/validation.go:330-352."""
    if vals is None:
        raise InvalidCommitError("nil validator set")
    if commit is None:
        raise InvalidCommitError("nil commit")
    if vals.size() != len(commit.signatures):
        raise InvalidCommitError(
            f"invalid commit -- wrong set size: {vals.size()} vs "
            f"{len(commit.signatures)}"
        )
    if height != commit.height:
        raise InvalidCommitError(
            f"invalid commit -- wrong height: {height} vs {commit.height}"
        )
    if block_id != commit.block_id:
        raise InvalidCommitError(
            f"invalid commit -- wrong block ID: want {block_id}, "
            f"got {commit.block_id}"
        )


def _verify_commit_batch(
    chain_id: str,
    vals: ValidatorSet,
    commit: Commit,
    voting_power_needed: int,
    ignore_sig: Callable[[CommitSig], bool],
    count_sig: Callable[[CommitSig], bool],
    count_all_signatures: bool,
    look_up_by_index: bool,
    vector_tally: bool = False,
) -> None:
    """Span-wrapped shim: the accumulate loop AND the verifier drains
    run under one `batch_accumulate` span, so the tpu_dispatch spans
    opened by BatchVerifier.verify() nest inside it — the trace shape
    PERF.md needs to split host assembly from device time per commit."""
    with trace.span(
        "batch_accumulate",
        sigs=len(commit.signatures),
        height=commit.height,
    ):
        _verify_commit_batch_impl(
            chain_id, vals, commit, voting_power_needed,
            ignore_sig, count_sig, count_all_signatures, look_up_by_index,
            vector_tally,
        )


def _verify_commit_batch_impl(
    chain_id: str,
    vals: ValidatorSet,
    commit: Commit,
    voting_power_needed: int,
    ignore_sig: Callable[[CommitSig], bool],
    count_sig: Callable[[CommitSig], bool],
    count_all_signatures: bool,
    look_up_by_index: bool,
    vector_tally: bool = False,
) -> None:
    """reference: types/validation.go:152-262, extended for mixed-key
    validator sets (the BASELINE mixed ed25519/sr25519 stress shape):
    one batch verifier PER KEY TYPE so ed25519 signatures ride the
    device path while other types use their own CPU batch verifiers.
    The reference's single-verifier form errors out of mixed sets (its
    BatchVerifier.Add rejects foreign key types with no fallback);
    grouping by type preserves its semantics for uniform sets and makes
    mixed sets first-class. A key type with no batch support at all
    (secp256k1) verifies inline.

    `vector_tally=True` asserts that ignore_sig/count_sig are the
    STANDARD predicates for this (count_all_signatures,
    look_up_by_index) combination — absent-skip/commit-count for
    verify_commit, for-block-only/count-all for the light and trusting
    variants — and routes through the vectorized plans in
    _verify_commit_batch_vector, which compute the same processed-index
    set, tally, and errors as the scalar reference loop below (pinned
    by the property tests in tests/test_warmpath.py). A commit whose
    BlockIDFlags don't fit uint8 (hostile from_proto input) falls back
    to the scalar loop so the failure surfaces as the reference
    InvalidCommitError."""
    if vector_tally:
        flags = commit.block_id_flags_array()
        if flags is not None:
            _verify_commit_batch_vector(
                chain_id, vals, commit, voting_power_needed,
                count_all_signatures, look_up_by_index, flags,
            )
            return
    _verify_commit_batch_scalar(
        chain_id, vals, commit, voting_power_needed,
        ignore_sig, count_sig, count_all_signatures, look_up_by_index,
    )


def _prefix_crossing(masked_powers, voting_power_needed: int):
    """(tallied, end) of the reference early-exit scan over
    `masked_powers` — the per-position powers the scalar loop would ADD
    (zeros where it skips). The reference breaks AFTER the vote whose
    running total crosses the threshold, i.e. at the first index where
    the prefix sum exceeds it; `end` is that index + 1 (the exclusive
    scan bound), or None when the whole array is scanned without
    crossing. Single home for the cum/argmax subtlety shared by the
    vectorized light/trusting plans and collect_commit_light."""
    cum = masked_powers.cumsum()
    total = int(cum[-1]) if cum.size else 0
    if total > voting_power_needed:
        cross = int(np.argmax(cum > voting_power_needed))
        return int(cum[cross]), cross + 1
    return total, None


def _commit_memo_key(
    chain_id: str,
    vals: ValidatorSet,
    commit: Commit,
    voting_power_needed: int,
    count_all_signatures: bool,
    look_up_by_index: bool,
    powers,
) -> tuple:
    """The commit-level sigcache key (crypto/sigcache seen_commit /
    add_commit): binds the verification mode, threshold, a content-
    identity token per commit and validator set, the process-wide
    validator-mutation epoch (so an in-place pub_key/address swap —
    which moves neither fingerprint token nor the powers bytes — can
    never serve a stale success; types/validator.py _VAL_MUT_EPOCH),
    and the live powers bytes as defense in depth. Single home shared
    with bench_commit_warm_breakdown's commit_probe phase so the
    measured probe can't drift from the production key shape."""
    from .validator import _VAL_MUT_EPOCH

    return (
        "commit-memo",
        chain_id,
        count_all_signatures,
        look_up_by_index,
        voting_power_needed,
        commit.fingerprint_token(),
        vals.fingerprint_token(),
        _VAL_MUT_EPOCH[0],
        powers.tobytes(),
    )


def _verify_commit_batch_vector(
    chain_id: str,
    vals: ValidatorSet,
    commit: Commit,
    voting_power_needed: int,
    count_all_signatures: bool,
    look_up_by_index: bool,
    flags,
) -> None:
    """The warm-path engine: zero encoding (commit-scoped sign-bytes
    memo), one bulk cache probe (sigcache.seen_keys_bulk) instead of a
    per-triple loop, a masked-sum / prefix-sum tally instead of
    per-vote predicate calls, and an O(1) commit-level short-circuit
    (sigcache.seen_commit) for a commit this process fully verified
    before. Behavior — processed indexes, early-exit points, error
    strings — is byte-identical to _verify_commit_batch_scalar by
    construction and by property test:

    - verify_commit (count_all, by index): processes every non-absent
      index; tally = sum of powers where flag == COMMIT.
    - verify_commit_light (early exit, by index): the reference loop
      counts every for-block vote in index order and breaks after the
      vote that crosses 2/3 — exactly the first index where the
      prefix-sum of COMMIT-masked powers exceeds the threshold. The
      processed set is the for-block prefix through that crossing.
    - verify_commit_light_trusting (early exit, by address): same
      prefix-sum over powers resolved through the trusted set's address
      index (missing addresses contribute 0, exactly like the
      reference's skip). Duplicate addresses can only INFLATE the
      prefix-sum, so the computed crossing k never lies beyond the
      reference's scan end: a duplicate at index j <= k is re-detected
      by the per-index replay below and raises the reference's double-
      vote error; a duplicate at j > k was never reached by the
      reference loop either, and then the prefix through k is
      duplicate-free so its sums agree exactly.

    Only the inline (non-batchable key) failure path accounts cache
    metrics differently: the scalar loop observes the counts scanned so
    far, this path observes the full plan's counts up front. Errors and
    verification work are identical."""
    use_cache = sigcache.enabled()
    sigs = commit.signatures
    with trace.span("commit_plan") as plan:
        powers = vals.powers_array()

        # --- the plan: processed indexes (ascending) + precomputed
        # tally
        if count_all_signatures:
            tallied = int(powers[flags == BLOCK_ID_FLAG_COMMIT].sum())
            idx_arr = np.flatnonzero(flags != BLOCK_ID_FLAG_ABSENT)
        elif look_up_by_index:
            tallied, end = _prefix_crossing(
                np.where(flags == BLOCK_ID_FLAG_COMMIT, powers, 0),
                voting_power_needed,
            )
            idx_arr = np.flatnonzero(
                (flags if end is None else flags[:end])
                == BLOCK_ID_FLAG_COMMIT
            )
        else:
            fb = np.flatnonzero(flags == BLOCK_ID_FLAG_COMMIT)
            addr_index = vals._addr_index
            vi = np.fromiter(
                (
                    addr_index.get(sigs[i].validator_address, -1)
                    for i in fb.tolist()
                ),
                dtype=np.int64,
                count=fb.size,
            )
            tallied, end = _prefix_crossing(
                np.where(vi >= 0, powers[np.maximum(vi, 0)], 0),
                voting_power_needed,
            )
            idx_arr = fb if end is None else fb[:end]
        idx_list = idx_arr.tolist()

        # --- commit-level memo: a commit this process fully verified
        # before, in this mode, against this exact set composition and
        # these exact live powers, short-circuits to the
        # (deterministic) success in O(1) probes. Failures are never
        # recorded, the token components die with any mutation, and a
        # sigcache.disabled() / commit_memo_disabled() scope disables
        # the whole consult.
        ckey_commit = None
        memo_hit = False
        if use_cache and sigcache.commit_memo_enabled():
            ckey_commit = _commit_memo_key(
                chain_id, vals, commit, voting_power_needed,
                count_all_signatures, look_up_by_index, powers,
            )
            memo_hit = sigcache.seen_commit(ckey_commit)
        plan.set(processed=len(idx_list), memo_hit=memo_hit)
    if memo_hit:
        trace.add_attrs(sigcache_commit_hit=True)
        return

    if look_up_by_index:
        # the processed votes as columns aligned with idx_list (whole
        # lists where nobody is absent): sign-bytes, then key bytes and
        # signatures, zipped into the cache keys where the cache is on
        with trace.span("sign_bytes", rows=len(idx_list)):
            if count_all_signatures:
                # None exactly at the absent indexes, the complement of
                # idx_list
                sb_col = _take(commit.sign_bytes_batch(chain_id), idx_list)
            else:
                # early-exit variant: encode only the processed prefix,
                # in one pass and memoized — no discarded rows are paid
                # for
                vsb = commit.vote_sign_bytes
                sb_col = [vsb(chain_id, i) for i in idx_list]
        pkb_col = keys = None
        # the misses' places in idx_list, ascending; None: all of them
        places = None
        if use_cache:
            with trace.span("sigcache_probe") as probe:
                pkb_col, sig_col = _key_and_signature_columns(
                    vals, sigs, idx_list
                )
                keys = list(zip(pkb_col, sb_col, sig_col))
                places, hits_n, misses_n = _cache_misses(keys)
                probe.set(hits=hits_n, misses=misses_n)
            sigcache.observe(hits_n, misses_n)
            trace.add_attrs(
                sigcache_hits=hits_n, sigcache_misses=misses_n
            )
        with trace.span("batch_route") as route:
            if pkb_col is None:
                pkb_col, sig_col = _key_and_signature_columns(
                    vals, sigs, idx_list
                )
                keys = [None] * len(idx_list)
            types, codes, pub_keys = vals.key_classes()
            if len(idx_list) != len(pub_keys):
                codes = codes[idx_arr]
            pending, inline = _route_misses(
                types,
                codes,
                Columns(
                    _take(pub_keys, idx_list), pkb_col, sb_col, sig_col,
                    idx_list, keys,
                ),
                places,
                lambda i, sig: InvalidCommitError(
                    f"wrong signature (#{i}): {sig.hex()}"
                ),
            )
            route.set(inline=inline)
    else:
        # trusting: per-index replay of the reference body over the
        # precomputed prefix — the double-vote ordering machinery stays
        # scalar, only ignore/count/early-exit bookkeeping is gone
        _seen_key = sigcache.seen_key
        hits_n = misses_n = 0
        seen_vals: dict[int, int] = {}
        # key type -> Columns: the cache misses awaiting batch
        # verification
        pending: dict[str, Columns] = defaultdict(Columns.new)
        # key type -> supports_batch_verifier
        batchable: dict[str, bool] = {}
        with trace.span("batch_route") as route:
            inline = 0
            for idx in idx_list:
                commit_sig = sigs[idx]
                val_idx, val = vals.get_by_address(
                    commit_sig.validator_address
                )
                if val is None:
                    continue
                if val_idx in seen_vals:
                    raise InvalidCommitError(
                        f"double vote from {val.address.hex()} "
                        f"({seen_vals[val_idx]} and {idx})"
                    )
                seen_vals[val_idx] = idx
                vote_sign_bytes = commit.vote_sign_bytes(chain_id, idx)
                pub_key = val.pub_key
                key_bytes = pub_key.bytes()
                ckey = None
                if use_cache:
                    ckey = (
                        key_bytes, vote_sign_bytes, commit_sig.signature
                    )
                    if _seen_key(ckey):
                        hits_n += 1
                        continue
                    misses_n += 1
                key_type = pub_key.type()
                can_batch = batchable.get(key_type)
                if can_batch is None:
                    can_batch = batchable[key_type] = supports_batch_verifier(
                        pub_key
                    )
                if not can_batch:
                    inline += 1
                    if not pub_key.verify_signature(
                        vote_sign_bytes, commit_sig.signature
                    ):
                        if use_cache:  # keep the scanned hit/miss counts
                            sigcache.observe(hits_n, misses_n)
                        raise InvalidCommitError(
                            f"wrong signature (#{idx}): "
                            f"{commit_sig.signature.hex()}"
                        )
                    if ckey is not None:
                        sigcache.add_key(ckey)
                else:
                    pending[key_type].append(
                        pub_key, key_bytes, vote_sign_bytes,
                        commit_sig.signature, idx, ckey,
                    )
            route.set(inline=inline)
        if use_cache:
            sigcache.observe(hits_n, misses_n)
            trace.add_attrs(sigcache_hits=hits_n, sigcache_misses=misses_n)

    if tallied <= voting_power_needed:
        raise NotEnoughVotingPowerError(tallied, voting_power_needed)
    _drain_pending(commit, pending)
    if ckey_commit is not None:
        with trace.span("sigcache_populate", keys=1):
            sigcache.add_commit(ckey_commit)


def _verify_commit_batch_scalar(
    chain_id: str,
    vals: ValidatorSet,
    commit: Commit,
    voting_power_needed: int,
    ignore_sig: Callable[[CommitSig], bool],
    count_sig: Callable[[CommitSig], bool],
    count_all_signatures: bool,
    look_up_by_index: bool,
) -> None:
    """The reference scan loop (types/validation.go:152-262): per-vote
    predicates, incremental tally, early exit by running total. The
    vectorized plans above must stop at the same vote and raise the
    same errors as this loop — it is both the fallback for hostile
    flag encodings and the oracle the property tests compare against.

    Cache-aware batch assembly: each triple is first checked against
    the verified-signature cache (crypto.sigcache); hits skip crypto
    entirely and only MISSES are assembled, deferred until after the
    scan so every group's batch verifier gets size_hint = its own miss
    count — the padded device bucket shrinks to the real work instead
    of the whole commit (and, per key type, to the group rather than
    the merged total)."""
    use_cache = sigcache.enabled()
    _seen_key = sigcache.seen_key  # hoisted: called once per signature
    tallied = 0
    hits = misses = 0
    seen_vals: dict[int, int] = {}
    # key type -> Columns: the cache misses awaiting batch verification
    pending: dict[str, Columns] = defaultdict(Columns.new)
    # key type -> supports_batch_verifier
    batchable: dict[str, bool] = {}
    # one templated pass for all sign-bytes when every signature will
    # be checked (verify_commit); early-exit variants encode lazily per
    # index (memoized) so no discarded rows are paid for
    all_sign_bytes = (
        commit.sign_bytes_batch(chain_id) if count_all_signatures else None
    )
    signatures = commit.signatures
    for idx, commit_sig in enumerate(signatures):
        if ignore_sig(commit_sig):
            continue
        if look_up_by_index:
            val = vals.validators[idx]
        else:
            val_idx, val = vals.get_by_address(
                commit_sig.validator_address
            )
            if val is None:
                continue
            if val_idx in seen_vals:
                raise InvalidCommitError(
                    f"double vote from {val.address.hex()} "
                    f"({seen_vals[val_idx]} and {idx})"
                )
            seen_vals[val_idx] = idx
        vote_sign_bytes = (
            all_sign_bytes[idx]
            if all_sign_bytes is not None
            else commit.vote_sign_bytes(chain_id, idx)
        )
        pub_key = val.pub_key
        key_bytes = pub_key.bytes()
        ckey = None
        if use_cache:
            # inline sigcache.key_for — the tuple IS the key, and the
            # call overhead is measurable at 10k signatures
            ckey = (key_bytes, vote_sign_bytes, commit_sig.signature)
            if _seen_key(ckey):
                hits += 1
                if count_sig(commit_sig):
                    tallied += val.voting_power
                if (
                    not count_all_signatures
                    and tallied > voting_power_needed
                ):
                    break
                continue
            misses += 1
        key_type = pub_key.type()
        can_batch = batchable.get(key_type)
        if can_batch is None:
            can_batch = batchable[key_type] = supports_batch_verifier(
                pub_key
            )
        if not can_batch:
            # no batch support for this type: verify inline
            if not pub_key.verify_signature(
                vote_sign_bytes, commit_sig.signature
            ):
                if use_cache:  # keep the scanned hit/miss counts
                    sigcache.observe(hits, misses)
                raise InvalidCommitError(
                    f"wrong signature (#{idx}): "
                    f"{commit_sig.signature.hex()}"
                )
            if ckey is not None:
                sigcache.add_key(ckey)
        else:
            pending[key_type].append(
                pub_key, key_bytes, vote_sign_bytes,
                commit_sig.signature, idx, ckey,
            )
        if count_sig(commit_sig):
            tallied += val.voting_power
        if not count_all_signatures and tallied > voting_power_needed:
            break
    if use_cache:
        sigcache.observe(hits, misses)
        trace.add_attrs(sigcache_hits=hits, sigcache_misses=misses)
    if tallied <= voting_power_needed:
        raise NotEnoughVotingPowerError(tallied, voting_power_needed)
    _drain_pending(commit, pending)


def _cache_misses(keys) -> tuple:
    """(places, hits, misses) of one bulk cache probe: the places in
    `keys` of those the cache does not hold, ascending (None: all of
    them, nothing hit), and the two counts."""
    hit_set = sigcache.seen_keys_bulk(keys)
    if not hit_set:
        return None, 0, len(keys)
    places = [j for j, key in enumerate(keys) if key not in hit_set]
    return places, len(keys) - len(places), len(places)


def _take(column, places):
    """The rows of `column` at `places` (ascending and distinct), in
    that order; the column itself where they are all of it."""
    if len(places) == len(column):
        return column
    if not places:
        return ()
    if len(places) == 1:  # itemgetter with one place returns the row bare
        return (column[places[0]],)
    return itemgetter(*places)(column)


def _key_and_signature_columns(vals: ValidatorSet, sigs, idx_list) -> tuple:
    """(key bytes, signatures) of the votes at idx_list, as columns."""
    return (
        _take(vals.pubkeys_bytes(), idx_list),
        list(map(attrgetter("signature"), _take(sigs, idx_list))),
    )


def _route_misses(types, codes, rows: Columns, places, wrong) -> tuple:
    """Group the cache misses of one verification by key class, a
    masked select a class and no Python a vote. `rows` holds every
    processed vote as columns, `codes` (np.uint8, aligned with them)
    the place of each vote's key type in `types`, `places` the misses'
    places in the columns, ascending (None: every row is one). Returns
    (pending, inline): key type -> the Columns of its misses, for
    crypto.batch.drain_classes, in the order of each class's lowest
    miss; and how many misses had a key type without a batch verifier.
    Those are verified here, one at a time in ascending place over all
    such types, each proven one recorded in the cache, and the first to
    fail raises wrong(its position, its signature) before any batched
    class is drained."""
    if places is not None:
        places = np.asarray(places, dtype=np.intp)
        codes = codes[places]
    classes = []
    for code, key_type in enumerate(types):
        at = np.flatnonzero(codes == code)
        if at.size:
            if places is not None:
                at = places[at]
            classes.append((int(at[0]), key_type, at))
    classes.sort()
    pending: dict[str, Columns] = {}
    unbatched = []
    for first, key_type, at in classes:
        if supports_batch_verifier(rows.pub_keys[first]):
            at = at.tolist()
            pending[key_type] = Columns(*(_take(col, at) for col in rows))
        else:
            unbatched.append(at)
    inline = 0
    if unbatched:
        for j in np.sort(np.concatenate(unbatched)).tolist():
            inline += 1
            signature = rows.signatures[j]
            if not rows.pub_keys[j].verify_signature(
                rows.messages[j], signature
            ):
                raise wrong(rows.positions[j], signature)
            if rows.cache_keys[j] is not None:
                sigcache.add_key(rows.cache_keys[j])
    return pending, inline


def _drain_lowest_bad(pending: dict) -> Optional[int]:
    """Drain the per-key-type miss batches (crypto.batch.drain_classes:
    every class launched before any is gathered), populating the cache
    for proven triples, and return the LOWEST position whose signature
    failed, over every class; None when all verified. A class's rows
    are in ascending position, so its first bad one is its lowest."""
    lowest: Optional[int] = None
    for key_type, (ok, valid_sigs) in drain_classes(pending).items():
        if ok:
            continue
        try:
            bad = pending[key_type].positions[valid_sigs.index(False)]
        except ValueError:
            raise RuntimeError(
                "BUG: batch verification failed with no invalid signatures"
            ) from None
        if lowest is None or bad < lowest:
            lowest = bad
    return lowest


def _drain_pending(commit: Commit, pending: dict) -> None:
    """Drain one commit's miss batches and raise the reference error
    for the LOWEST bad commit index across groups."""
    first_bad = _drain_lowest_bad(pending)
    if first_bad is not None:
        raise InvalidCommitError(
            f"wrong signature (#{first_bad}): "
            f"{commit.signatures[first_bad].signature.hex()}"
        )


def _verify_commit_single(
    chain_id: str,
    vals: ValidatorSet,
    commit: Commit,
    voting_power_needed: int,
    ignore_sig: Callable[[CommitSig], bool],
    count_sig: Callable[[CommitSig], bool],
    count_all_signatures: bool,
    look_up_by_index: bool,
) -> None:
    """reference: types/validation.go:265-328. Consults the verified-
    signature cache before each verify and populates it on success, so
    the single path and the batch path warm each other."""
    use_cache = sigcache.enabled()
    tallied = 0
    hits = misses = 0
    seen_vals: dict[int, int] = {}
    for idx, commit_sig in enumerate(commit.signatures):
        if ignore_sig(commit_sig):
            continue
        if look_up_by_index:
            val = vals.validators[idx]
        else:
            val_idx, val = vals.get_by_address(
                commit_sig.validator_address
            )
            if val is None:
                continue
            if val_idx in seen_vals:
                raise InvalidCommitError(
                    f"double vote from {val.address.hex()} "
                    f"({seen_vals[val_idx]} and {idx})"
                )
            seen_vals[val_idx] = idx
        vote_sign_bytes = commit.vote_sign_bytes(chain_id, idx)
        if use_cache:
            ckey = (
                val.pub_key.bytes(), vote_sign_bytes, commit_sig.signature
            )
            if sigcache.seen_key(ckey):
                hits += 1
            else:
                misses += 1
                if not val.pub_key.verify_signature(
                    vote_sign_bytes, commit_sig.signature
                ):
                    sigcache.observe(hits, misses)
                    raise InvalidCommitError(
                        f"wrong signature (#{idx}): "
                        f"{commit_sig.signature.hex()}"
                    )
                sigcache.add_key(ckey)
        elif not val.pub_key.verify_signature(
            vote_sign_bytes, commit_sig.signature
        ):
            raise InvalidCommitError(
                f"wrong signature (#{idx}): "
                f"{commit_sig.signature.hex()}"
            )
        if count_sig(commit_sig):
            tallied += val.voting_power
        if not count_all_signatures and tallied > voting_power_needed:
            sigcache.observe(hits, misses)
            return
    sigcache.observe(hits, misses)
    if tallied <= voting_power_needed:
        raise NotEnoughVotingPowerError(tallied, voting_power_needed)
