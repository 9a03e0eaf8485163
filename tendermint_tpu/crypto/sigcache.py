"""Process-wide verified-signature cache — cross-stage dedup of crypto.

The hot path pays for every signature at least twice: a precommit is
verified at gossip time (consensus verify-ahead / VoteSet.add_vote), then
the identical (pubkey, sign_bytes, signature) triple is re-verified from
scratch when verify_commit processes the next height's LastCommit — and
again in replay, blocksync, and light-client re-checks. The committee
signer set is stable across heights, so the re-checks are pure waste
("Performance of EdDSA and BLS Signatures in Committee-Based Consensus",
arXiv:2302.00418, makes the same observation; PERF.md's decoded-point
cache proved the shape one level down). This module remembers which exact
triples have already verified, so every later stage skips the curve math
and the batch paths assemble only cache misses — which also shrinks the
padded device bucket.

Safety model:

- The key is the EXACT (pubkey bytes, sign_bytes, signature) triple — a
  tuple in a set, so a hit requires full byte equality of all three
  components. Any byte difference — forged signature, mutated
  sign-bytes, an equivocating vote's different block ID — is a miss by
  construction; unlike a digest key there is no collision to find, even
  in theory. (The tuple also beats a 128-bit BLAKE2b digest on speed:
  set membership is SipHash — keyed per process, so not
  flood-precomputable — and the pubkey/signature objects are usually
  the same interned bytes across heights, whose hashes CPython caches;
  at 10k signatures the digest alone cost ~10 ms per warm commit.)
- Only SUCCESSFUL verifications are cached; failures are never inserted,
  so a hit can only ever skip work that a fresh verify would repeat.
- The cache carries no acceptance semantics of its own: callers still run
  every address/index/height/double-sign check; only the raw signature
  equation is skipped.
- A second key shape rides the same generations: COMMIT-LEVEL keys
  (seen_commit/add_commit) that record a whole commit verification's
  success, so a fully-warm re-verification short-circuits to the tally
  in O(1) probes. The key binds the verification mode, chain_id, a
  commit content-identity token (Commit.fingerprint_token — replaced on
  any in-place mutation), the validator-set hash, a live fingerprint of
  the voting powers, and the power threshold; the sign-bytes these keys
  implicitly vouch for are machine-proved deterministic in their inputs
  by tmcheck's taint gate, and `scripts/lint.py --memo-audit` re-proves
  that argument for every memoized function on each run — the full
  soundness chain is written up in docs/static_analysis.md
  ("Memo soundness"). Commit keys are 5+-tuples starting with a mode
  string, triples are 3-tuples of bytes: the namespaces cannot collide.

Memory is bounded by two-generation rotation: inserts land in the young
generation; when it fills, the old generation is dropped (counted by
sigcache_evictions) and the young one takes its place. Hits in the old
generation are promoted, so a stable validator set survives rotation
indefinitely. The default per-generation capacity is sized to ~2 heights
of MAX_VOTES_COUNT (types/vote_set.py) precommits, so one rotation spans
several heights even at the 10k-validator stress shape: total resident
keys <= 2 generations x 20k triples; sign-bytes dominate at ~120 bytes
each (pubkeys and signatures are references into live commit/validator
objects), so the full cache tops out around 10 MB.

A `disabled()` scope turns the cache off (lookups miss, inserts are
dropped) with no behavior difference except speed: the tests' and the
bench's A/B arm, and the only way off. Note the consensus verify-ahead
batch (consensus/state.py _preverify_votes) is BUILT ON this cache —
its results are recorded here — so the scope also returns gossiped
votes to sequential per-vote verification, not just commits to cold
batches.

Instruments (process-global on DEFAULT_REGISTRY, like the tpu_* family —
one cache per process): tendermint_tpu_sigcache_hits_total /
sigcache_misses_total / sigcache_evictions_total.
"""

from __future__ import annotations

import contextlib
import threading

from ..libs import metrics as M

__all__ = [
    "DEFAULT_CAPACITY",
    "add",
    "add_commit",
    "add_key",
    "add_keys_bulk",
    "commit_memo_disabled",
    "commit_memo_enabled",
    "disabled",
    "enabled",
    "key_for",
    "observe",
    "reset",
    "seen",
    "seen_commit",
    "seen_key",
    "seen_keys_bulk",
    "set_capacity",
    "stats",
]

# ~2 heights x MAX_VOTES_COUNT (types/vote_set.py) precommits per
# generation: a full rotation spans several heights even at the
# 10k-validator stress shape, so LastCommit triples verified at gossip
# time are still resident when the next height's block arrives.
DEFAULT_CAPACITY = 20_000

_m_hits = M.new_counter(
    "sigcache", "hits_total",
    "Verified-signature cache hits (signature checks skipped).",
)
_m_misses = M.new_counter(
    "sigcache", "misses_total",
    "Verified-signature cache misses (full verification performed).",
)
_m_evictions = M.new_counter(
    "sigcache", "evictions_total",
    "Verified-signature triples dropped by generation rotation.",
)
_m_commit_hits = M.new_counter(
    "sigcache", "commit_hits_total",
    "Commit-level verification memo hits (whole commits short-"
    "circuited to the tally).",
)
_m_commit_misses = M.new_counter(
    "sigcache", "commit_misses_total",
    "Commit-level verification memo misses (per-triple probing "
    "performed).",
)

_capacity = DEFAULT_CAPACITY
_gen0: set = set()  # young generation: inserts and promotions land here
_gen1: set = set()  # old generation: dropped wholesale on rotation
_lock = threading.Lock()  # guards rotation only; set ops are GIL-atomic
_force_off = False  # inside a disabled() scope (tests, bench cold rows)
_force_commit_off = False  # bench A/B arm: triple probes only


def enabled() -> bool:
    """False inside a disabled() scope, the only way off: every lookup
    misses and every insert is dropped — behavior identical to the
    cache never existing, minus the speed."""
    return not _force_off


@contextlib.contextmanager
def disabled():
    """Scope with the cache forced off (bench cold rows, A/B tests)."""
    global _force_off
    prev = _force_off
    _force_off = True
    try:
        yield
    finally:
        _force_off = prev


def key_for(pk_bytes: bytes, sign_bytes: bytes, signature: bytes) -> tuple:
    """The exact triple IS the key (a tuple): a hit requires full byte
    equality of all three components, so distinct triples can never
    alias. Hot loops may build the tuple inline instead of paying this
    call — the representation is part of the module contract."""
    return (pk_bytes, sign_bytes, signature)


def seen_key(key: tuple) -> bool:
    """Membership check for a precomputed key — no metrics, no enabled()
    gate: batch callers check enabled() once per commit, account hits
    and misses in bulk via observe(), and keep the per-triple cost to
    one tuple build + one set lookup."""
    if key in _gen0:
        return True
    if key in _gen1:
        # promote: a stable signer set's triples survive rotation. The
        # old-generation copy is discarded so entries() never double-
        # counts and rotation's eviction count covers only triples that
        # actually leave the cache.
        # tmlint: disable=lock-global-mutation — set ops are single-
        # bytecode GIL-atomic by design (module docstring); _lock
        # guards only generation rotation
        _gen1.discard(key)
        _insert(key)
        return True
    return False


def seen_keys_bulk(keys) -> set:
    """Bulk membership: returns the subset of `keys` already proven, as
    a set. One set-intersection per generation replaces the per-triple
    probe loop — at 10k signatures the warm scan's dominant Python cost
    after the sign-bytes memo (PERF.md warm-path breakdown). Old-
    generation hits are promoted exactly like seen_key. No metrics and
    no enabled() gate, same contract as seen_key: batch callers check
    enabled() once and account via observe()."""
    if not keys:
        return set()
    ks = keys if isinstance(keys, set) else set(keys)
    # tmlint: disable=lock-global-mutation — GIL-atomic set ops by
    # design (module docstring); _lock guards only generation rotation
    hits = ks & _gen0
    old = (ks - hits) & _gen1
    if old:
        # promote survivors of a stable signer set, discarding the
        # old-generation copies so entries()/evictions stay honest —
        # the bulk form of seen_key's promotion
        _gen1.difference_update(old)  # tmlint: disable=lock-global-mutation
        _gen0.update(old)  # tmlint: disable=lock-global-mutation
        hits |= old
        if len(_gen0) >= _capacity:
            _rotate()
    return hits


def add_key(key: tuple) -> None:
    """Record a precomputed key as verified (caller gates on enabled()
    and MUST only call after a successful verification)."""
    _insert(key)


def add_keys_bulk(keys) -> None:
    """Record many precomputed keys as verified (same caller contract
    as add_key). Inserts are chunked to the remaining generation
    capacity so the documented bound — at most 2 generations x
    capacity resident triples — holds even for a 10k-key drain into a
    nearly-full young generation."""
    keys = list(keys)
    pos = 0
    while pos < len(keys):
        room = max(_capacity - len(_gen0), 1)
        chunk = keys[pos:pos + room]
        pos += room
        # tmlint: disable=lock-global-mutation — GIL-atomic set update
        _gen0.update(chunk)
        if len(_gen0) >= _capacity:
            _rotate()


def _insert(key: tuple) -> None:
    # tmlint: disable=lock-global-mutation — GIL-atomic set add by
    # design; worst case a racing rotation re-checks capacity
    _gen0.add(key)
    if len(_gen0) >= _capacity:
        _rotate()


def _rotate() -> None:
    global _gen0, _gen1
    with _lock:
        if len(_gen0) < _capacity:  # lost the race: already rotated
            return
        if _gen1:
            _m_evictions.inc(len(_gen1))
        _gen1 = _gen0
        _gen0 = set()


def commit_memo_enabled() -> bool:
    """The commit-level verification memo rides the same generations
    but has its own off-switch (a commit_memo_disabled() scope) on top
    of the cache-wide gate — the bench's interleaved A/B arm measures
    the bulk triple-probe path with only this half disabled."""
    return enabled() and not _force_commit_off


@contextlib.contextmanager
def commit_memo_disabled():
    """Scope with only the commit-level memo off (bench B arm, tests):
    triple probes still hit, so this isolates what the O(1) commit
    short-circuit buys over the bulk probe."""
    global _force_commit_off
    prev = _force_commit_off
    _force_commit_off = True
    try:
        yield
    finally:
        _force_commit_off = prev


def seen_commit(key: tuple) -> bool:
    """Probe the commit-level verification memo: True iff this exact
    (mode, chain_id, commit fingerprint token, validator-set
    fingerprint, threshold) tuple completed a fully-successful
    verification before (types/validation.py builds the key; failures
    are never recorded, so a hit can only skip work a fresh run would
    repeat). Lives in the same two-generation rotation as the triples
    — promotion keeps a live chain's commit memos resident. Counts
    sigcache_commit_{hits,misses}_total; False when disabled."""
    if not commit_memo_enabled():
        return False
    if seen_key(key):
        _m_commit_hits.inc()
        return True
    _m_commit_misses.inc()
    return False


def add_commit(key: tuple) -> None:
    """Record a commit-level key after a FULLY successful commit
    verification (every required signature proven, tally crossed)."""
    if not commit_memo_enabled():
        return
    _insert(key)


def seen(pk_bytes: bytes, sign_bytes: bytes, signature: bytes) -> bool:
    """Single-triple convenience (Vote.verify, evidence): False when
    disabled; counts one hit or miss."""
    if not enabled():
        return False
    if seen_key(key_for(pk_bytes, sign_bytes, signature)):
        _m_hits.inc()
        return True
    _m_misses.inc()
    return False


def add(pk_bytes: bytes, sign_bytes: bytes, signature: bytes) -> None:
    """Single-triple insert after a SUCCESSFUL verification."""
    if not enabled():
        return
    _insert(key_for(pk_bytes, sign_bytes, signature))


def observe(hits: int, misses: int) -> None:
    """Bulk metric accounting for batch callers (one counter touch per
    commit instead of one per signature)."""
    if hits:
        _m_hits.inc(hits)
    if misses:
        _m_misses.inc(misses)


def stats() -> dict:
    return {
        "hits": int(_m_hits.value()),
        "misses": int(_m_misses.value()),
        "evictions": int(_m_evictions.value()),
        "commit_hits": int(_m_commit_hits.value()),
        "commit_misses": int(_m_commit_misses.value()),
        "entries": len(_gen0) + len(_gen1),
        "capacity": _capacity,
    }


def set_capacity(n: int) -> None:
    """Resize the per-generation capacity (tests; operators with bigger
    validator sets). Existing entries are kept until normal rotation."""
    global _capacity
    if n < 1:
        raise ValueError(f"sigcache capacity must be >= 1: {n}")
    _capacity = int(n)


def reset() -> None:
    """Drop every cached triple (tests, bench cold rows)."""
    global _gen0, _gen1
    with _lock:
        _gen0 = set()
        _gen1 = set()


def entries() -> int:
    """Resident triple count across both generations (bound checks)."""
    return len(_gen0) + len(_gen1)
