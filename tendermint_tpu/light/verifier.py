"""Core light-client verification — the batch-verify showcase.

reference: light/verifier.go (VerifyNonAdjacent :33, VerifyAdjacent
:106, Verify :158, verifyNewHeaderAndVals :174, HeaderExpired :214,
VerifyBackwards :228; DefaultTrustLevel :16).

Both verification modes bottom out in the commit-verification family
(types/validation.py), which dispatches whole commits through the
device BatchVerifier when installed — a 10k-header sync is 10-20k
batched device verifies (BASELINE config 4).

Those paths consult the process-wide verified-signature cache
(crypto.sigcache), which matters here twice over: verify_non_adjacent
checks the SAME commit against two validator sets (the trusted set's
trust-level check, then 2/3 of its own set) — the second pass re-meets
every triple the first pass just proved; and the per-hop fallback of
the sequential window (light/client.py, after a failure the merged
batch could not place) re-pays only for what the merged attempt did
not prove.
"""

from __future__ import annotations

from ..libs import trace
from ..types.light import SignedHeader
from ..types.validation import (
    Fraction,
    verify_commit_light,
    verify_commit_light_bulk,
    verify_commit_light_trusting,
)
from ..types.validator import ValidatorSet
from .errors import (
    InvalidHeaderError,
    NewValSetCantBeTrustedError,
    OldHeaderExpiredError,
    VerificationError,
)

__all__ = [
    "DEFAULT_TRUST_LEVEL",
    "MAX_CLOCK_DRIFT_NS",
    "verify",
    "verify_adjacent",
    "verify_adjacent_batch",
    "verify_non_adjacent",
    "verify_backwards",
    "header_expired",
]

# reference: light/verifier.go:16
DEFAULT_TRUST_LEVEL = Fraction(1, 3)
# reference: light/client.go defaultMaxClockDrift (10 s)
MAX_CLOCK_DRIFT_NS = 10 * 1_000_000_000


def header_expired(
    h: SignedHeader, trusting_period_ns: int, now_ns: int
) -> bool:
    """reference: light/verifier.go:214-222."""
    expiration = h.header.time_ns + trusting_period_ns
    return now_ns > expiration


def _validate_trust_level(lvl: Fraction) -> None:
    """Must be in [1/3, 1] (reference: light/verifier.go:251-259)."""
    if (
        lvl.numerator * 3 < lvl.denominator
        or lvl.numerator > lvl.denominator
        or lvl.denominator == 0
    ):
        raise ValueError(f"trust level must be within [1/3, 1], got {lvl}")


def _verify_new_header_and_vals(
    chain_id: str,
    untrusted_header: SignedHeader,
    untrusted_vals: ValidatorSet,
    trusted_header: SignedHeader,
    now_ns: int,
    max_clock_drift_ns: int,
) -> None:
    """reference: light/verifier.go:174-212."""
    try:
        untrusted_header.validate_basic(chain_id)
    except ValueError as e:
        raise InvalidHeaderError(f"untrusted header invalid: {e}") from e
    if untrusted_header.header.height <= trusted_header.header.height:
        raise InvalidHeaderError(
            f"expected new header height {untrusted_header.header.height} "
            f"to be greater than trusted {trusted_header.header.height}"
        )
    if untrusted_header.header.time_ns <= trusted_header.header.time_ns:
        raise InvalidHeaderError(
            "expected new header time after trusted header time"
        )
    if untrusted_header.header.time_ns >= now_ns + max_clock_drift_ns:
        raise InvalidHeaderError(
            "new header time is from the future (beyond clock drift)"
        )
    if (
        untrusted_header.header.validators_hash
        != untrusted_vals.hash()
    ):
        raise InvalidHeaderError(
            "validator set does not match header validators_hash"
        )


def verify_non_adjacent(
    chain_id: str,
    trusted_header: SignedHeader,
    trusted_next_vals: ValidatorSet,
    untrusted_header: SignedHeader,
    untrusted_vals: ValidatorSet,
    trusting_period_ns: int,
    now_ns: int,
    max_clock_drift_ns: int = MAX_CLOCK_DRIFT_NS,
    trust_level: Fraction = DEFAULT_TRUST_LEVEL,
) -> None:
    """Skipping verification: trust-level of the *trusted* set must have
    signed the new header, plus 2/3 of the new header's own set
    (reference: light/verifier.go:33-104).

    Raises NewValSetCantBeTrustedError when the trusting check fails —
    the signal to bisect."""
    if untrusted_header.header.height == trusted_header.header.height + 1:
        raise ValueError("headers must be non-adjacent in height")
    _validate_trust_level(trust_level)
    if header_expired(trusted_header, trusting_period_ns, now_ns):
        raise OldHeaderExpiredError(
            trusted_header.header.time_ns + trusting_period_ns, now_ns
        )
    _verify_new_header_and_vals(
        chain_id, untrusted_header, untrusted_vals, trusted_header,
        now_ns, max_clock_drift_ns,
    )
    # trust-level of the set we trust signed it (batch device verify)
    try:
        verify_commit_light_trusting(
            chain_id,
            trusted_next_vals,
            untrusted_header.commit,
            trust_level,
        )
    except Exception as e:
        raise NewValSetCantBeTrustedError(str(e)) from e
    # 2/3 of its own claimed set signed it (batch device verify)
    try:
        verify_commit_light(
            chain_id,
            untrusted_vals,
            untrusted_header.commit.block_id,
            untrusted_header.header.height,
            untrusted_header.commit,
        )
    except Exception as e:
        raise InvalidHeaderError(str(e)) from e


def adjacent_header_checks(
    chain_id: str,
    trusted_header: SignedHeader,
    untrusted_header: SignedHeader,
    untrusted_vals: ValidatorSet,
    trusting_period_ns: int,
    now_ns: int,
    max_clock_drift_ns: int = MAX_CLOCK_DRIFT_NS,
) -> None:
    """The host-side half of verify_adjacent: every check except the
    commit signature verification. Split out so the light client's
    sequential group path can run all header checks for a window of
    hops first, then verify every commit's signatures in ONE device
    batch (light/client.py _verify_sequential)."""
    if untrusted_header.header.height != trusted_header.header.height + 1:
        raise ValueError("headers must be adjacent in height")
    if header_expired(trusted_header, trusting_period_ns, now_ns):
        raise OldHeaderExpiredError(
            trusted_header.header.time_ns + trusting_period_ns, now_ns
        )
    _verify_new_header_and_vals(
        chain_id, untrusted_header, untrusted_vals, trusted_header,
        now_ns, max_clock_drift_ns,
    )
    if (
        untrusted_header.header.validators_hash
        != trusted_header.header.next_validators_hash
    ):
        raise InvalidHeaderError(
            "header validators_hash does not match trusted header "
            "next_validators_hash"
        )


def verify_adjacent(
    chain_id: str,
    trusted_header: SignedHeader,
    untrusted_header: SignedHeader,
    untrusted_vals: ValidatorSet,
    trusting_period_ns: int,
    now_ns: int,
    max_clock_drift_ns: int = MAX_CLOCK_DRIFT_NS,
) -> None:
    """Sequential verification: the new validator set is pinned by the
    trusted header's next_validators_hash
    (reference: light/verifier.go:106-156)."""
    adjacent_header_checks(
        chain_id, trusted_header, untrusted_header, untrusted_vals,
        trusting_period_ns, now_ns, max_clock_drift_ns,
    )
    try:
        verify_commit_light(
            chain_id,
            untrusted_vals,
            untrusted_header.commit.block_id,
            untrusted_header.header.height,
            untrusted_header.commit,
        )
    except Exception as e:
        raise InvalidHeaderError(str(e)) from e


def verify_adjacent_batch(
    chain_id: str,
    trusted_header: SignedHeader,
    blocks,
    trusting_period_ns: int,
    now_ns: int,
    max_clock_drift_ns: int = MAX_CLOCK_DRIFT_NS,
) -> None:
    """Sequential verification of M height-chained light blocks in ONE
    sigcache-aware call — the bulk form of verify_adjacent and the
    light half of the stateless fleet-serving path.

    `blocks` is an ascending run of LightBlocks starting at
    trusted_header.height + 1. All header-chain checks run first, in
    hop order, with verify_adjacent's exact per-hop errors (the shared
    adjacent_header_checks); every commit's signatures then go through
    verify_commit_light_bulk: a warm fleet pass (a node re-serving
    headers it has verified before) is one commit-memo probe + one
    tally per commit — no sign-bytes encoding, no per-triple cache
    keys, no crypto — and a cold pass is one merged bulk sigcache
    probe + one grouped batch verify for ALL M commits instead of M
    independent verifies. A wrong signature surfaces as the
    InvalidHeaderError verify_adjacent raises for that hop (the text of
    the same commit error) with `hop`, the failing block's place in
    `blocks`, on it: the blocks before it are verified, and a caller
    saves them without verifying anything twice (light/client.py's
    sequential window does). A failure without `hop` (a header-chain
    check, a tally, a key type the merged check cannot place) says
    nothing about the signatures before it; callers needing the
    reference's exact failing hop then fall back to the per-hop
    verify_adjacent loop."""
    blocks = list(blocks)
    prev = trusted_header
    rows = []
    with trace.span("light_header_checks", hops=len(blocks)):
        for b in blocks:
            adjacent_header_checks(
                chain_id, prev, b.signed_header, b.validator_set,
                trusting_period_ns, now_ns, max_clock_drift_ns,
            )
            rows.append(
                (
                    b.validator_set,
                    b.signed_header.commit.block_id,
                    b.signed_header.header.height,
                    b.signed_header.commit,
                )
            )
            prev = b.signed_header
    try:
        verify_commit_light_bulk(chain_id, rows)
    except Exception as e:
        err = InvalidHeaderError(str(e))
        err.hop = getattr(e, "row", None)
        raise err from e


def verify(
    chain_id: str,
    trusted_header: SignedHeader,
    trusted_next_vals: ValidatorSet,
    untrusted_header: SignedHeader,
    untrusted_vals: ValidatorSet,
    trusting_period_ns: int,
    now_ns: int,
    max_clock_drift_ns: int = MAX_CLOCK_DRIFT_NS,
    trust_level: Fraction = DEFAULT_TRUST_LEVEL,
) -> None:
    """Dispatch adjacent/non-adjacent (reference: light/verifier.go:158)."""
    if untrusted_header.header.height != trusted_header.header.height + 1:
        verify_non_adjacent(
            chain_id, trusted_header, trusted_next_vals,
            untrusted_header, untrusted_vals,
            trusting_period_ns, now_ns, max_clock_drift_ns, trust_level,
        )
    else:
        verify_adjacent(
            chain_id, trusted_header, untrusted_header, untrusted_vals,
            trusting_period_ns, now_ns, max_clock_drift_ns,
        )


def verify_backwards(
    chain_id: str,
    untrusted_header: SignedHeader,
    trusted_header: SignedHeader,
) -> None:
    """Verify an OLDER header against a trusted newer one by hash
    chaining (reference: light/verifier.go:228-249). No signature check:
    the hash linkage is the proof."""
    try:
        untrusted_header.validate_basic(chain_id)
    except ValueError as e:
        raise InvalidHeaderError(str(e)) from e
    if untrusted_header.header.height >= trusted_header.header.height:
        raise InvalidHeaderError(
            "untrusted header must have a smaller height"
        )
    if untrusted_header.header.time_ns >= trusted_header.header.time_ns:
        raise InvalidHeaderError(
            "untrusted header must have an earlier time"
        )
    if (
        trusted_header.header.last_block_id.hash
        != untrusted_header.header.hash()
    ):
        raise VerificationError(
            f"trusted header last_block_id does not match untrusted "
            f"header hash at height {untrusted_header.header.height}"
        )
