"""Validator and ValidatorSet — weighted round-robin proposer selection.

Reference: types/validator.go (Validator, CompareProposerPriority :77,
hash bytes :130), types/validator_set.go (priority increment/rescale
:107-226, GetByAddress :270, Hash :347, change-set application :380-651).

Arithmetic is Python ints (arbitrary precision) clipped to int64 bounds
exactly where the reference uses safeAddClip/safeSubClip, so priority
sequences match Go bit-for-bit even at the clipping edges.
"""

from __future__ import annotations

from dataclasses import dataclass, field, replace
from typing import Dict, Iterable, List, Optional, Tuple

from ..crypto import merkle
from ..crypto.keys import PubKey, pubkey_from_proto, pubkey_to_proto
from ..encoding.proto import FieldReader, ProtoWriter, iter_fields

__all__ = [
    "Validator",
    "ValidatorSet",
    "MAX_TOTAL_VOTING_POWER",
    "PRIORITY_WINDOW_SIZE_FACTOR",
]

INT64_MAX = (1 << 63) - 1
INT64_MIN = -(1 << 63)

# reference: types/validator_set.go:25,29
MAX_TOTAL_VOTING_POWER = INT64_MAX // 8
PRIORITY_WINDOW_SIZE_FACTOR = 2


def _clip(v: int) -> int:
    return INT64_MAX if v > INT64_MAX else INT64_MIN if v < INT64_MIN else v


# Process-wide validator-mutation epoch, the ValidatorSet twin of
# commit.py's _MUT_EPOCH: every epoch-pinned set memo (powers_array,
# pubkeys_bytes) is built under the token stored here, and any
# POST-INIT assignment to a Validator field those memos read
# (voting_power, pub_key, address) replaces the token, so the memos
# re-validate lazily on next access. ValidatorSet hands out live
# Validator references, so in-place `v.voting_power = x` without
# _reindex() is a SUPPORTED mutation (the scalar verify paths read it
# live); the epoch hook is what keeps the vectorized tally in lockstep
# with them — the ADVICE-r5 staleness class, closed by invalidation
# instead of rebuild-per-call. proposer_priority writes (every proposer
# rotation) deliberately do not bump: no epoch-pinned memo reads it.
# tmrace: race-ok — single atomic list-slot store of a fresh token;
# concurrent bumps each publish a token unequal to every pinned memo,
# so any interleaving invalidates (the conservative direction)
_VAL_MUT_EPOCH = [object()]

# the Validator fields the epoch-pinned ValidatorSet memos read
_EPOCH_FIELDS = frozenset({"voting_power", "pub_key", "address"})


def key_type_codes(pub_keys) -> tuple:
    """(key types, codes): the distinct PubKey.type() values of
    `pub_keys` in order of first appearance, and for each key the place
    of its type among them, as a read-only np.uint8 array: what batch
    routing groups signatures by, one masked select a key class
    (types/validation.py)."""
    import numpy as np

    types: Dict[str, int] = {}
    codes = np.fromiter(
        (types.setdefault(pk.type(), len(types)) for pk in pub_keys),
        dtype=np.uint8,
        count=len(pub_keys),
    )
    codes.setflags(write=False)
    return tuple(types), codes


@dataclass
class Validator:
    pub_key: PubKey
    voting_power: int = 0
    proposer_priority: int = 0
    address: bytes = b""

    def __setattr__(self, name: str, value) -> None:
        # a RE-assignment (the attribute already exists — dataclass
        # __init__ sets each field exactly once on a fresh instance)
        # of a memo-read field invalidates every epoch-pinned set memo
        if name in _EPOCH_FIELDS and name in self.__dict__:
            _VAL_MUT_EPOCH[0] = object()
        object.__setattr__(self, name, value)

    def __post_init__(self) -> None:
        if not self.address and self.pub_key is not None:
            # first derivation on a fresh instance, not a mutation of
            # anything a memo could have read yet: skip the epoch hook
            object.__setattr__(self, "address", self.pub_key.address())

    def copy(self) -> "Validator":
        return replace(self)

    def validate_basic(self) -> None:
        if self.pub_key is None:
            raise ValueError("validator does not have a public key")
        if self.voting_power < 0:
            raise ValueError("validator has negative voting power")
        if len(self.address) != 20:
            raise ValueError("validator address is the wrong size")

    def hash_bytes(self) -> bytes:
        """SimpleValidator proto (pubkey + power, no priority/address) —
        the validator-set hash leaf (reference: types/validator.go:130-145,
        proto/tendermint/types/validator.pb.go:156-157)."""
        w = ProtoWriter()
        w.message(1, pubkey_to_proto(self.pub_key))
        w.int(2, self.voting_power)
        return w.finish()

    def to_proto(self) -> bytes:
        w = ProtoWriter()
        w.bytes(1, self.address)
        w.message(2, pubkey_to_proto(self.pub_key))  # nullable=false
        w.int(3, self.voting_power)
        w.int(4, self.proposer_priority)
        return w.finish()

    @classmethod
    def from_proto(cls, data: bytes) -> "Validator":
        r = FieldReader(data)
        pk = r.get(2)
        if pk is None:
            raise ValueError("validator proto missing pub_key")
        return cls(
            pub_key=pubkey_from_proto(pk),
            voting_power=r.int64(3),
            proposer_priority=r.int64(4),
            address=r.bytes(1),
        )


def _cmp_most_priority(a: Validator, b: Validator) -> Validator:
    """Higher priority wins; ties break toward the lower address
    (reference: types/validator.go:77-97)."""
    if a.proposer_priority > b.proposer_priority:
        return a
    if a.proposer_priority < b.proposer_priority:
        return b
    if a.address < b.address:
        return a
    if a.address > b.address:
        return b
    raise ValueError("cannot compare identical validators")


class ValidatorSet:
    """Validators sorted by voting power desc, then address asc.

    reference: types/validator_set.go:50-80. Maintains an address index
    for O(1) GetByAddress (the reference does binary search; same
    observable behavior).
    """

    def __init__(self, validators: Optional[Iterable[Validator]] = None):
        self.validators: List[Validator] = []
        self.proposer: Optional[Validator] = None
        self._total_voting_power = 0
        self._addr_index: Dict[bytes, int] = {}
        self._hash: Optional[bytes] = None
        self._proto_memo: Optional[tuple] = None
        self._fp_token: Optional[object] = None
        self._pkb_memo: Optional[tuple] = None
        self._key_classes_memo: Optional[tuple] = None
        self._powers_memo: Optional[tuple] = None
        valz = [v.copy() for v in validators] if validators else []
        self._update_with_change_set(valz, allow_deletes=False)
        if valz:
            self.increment_proposer_priority(1)

    # -- basic accessors --

    def is_nil_or_empty(self) -> bool:
        return not self.validators

    def size(self) -> int:
        return len(self.validators)

    def __len__(self) -> int:
        return len(self.validators)

    def has_address(self, address: bytes) -> bool:
        return address in self._addr_index

    def get_by_address(
        self, address: bytes
    ) -> Tuple[int, Optional[Validator]]:
        """(index, validator) or (-1, None)
        (reference: types/validator_set.go:270)."""
        i = self._addr_index.get(address)
        if i is None:
            return -1, None
        return i, self.validators[i].copy()

    def get_by_index(
        self, index: int
    ) -> Tuple[bytes, Optional[Validator]]:
        if index < 0 or index >= len(self.validators):
            return b"", None
        v = self.validators[index]
        return v.address, v.copy()

    def powers_array(self):
        """Voting powers as a read-only np.int64 array aligned with
        self.validators, memoized under the process-wide validator-
        mutation epoch (_VAL_MUT_EPOCH). This class hands out live
        Validator references, so in-place power mutation without
        _reindex() is supported and the scalar verify paths see it
        immediately; a plain memo here would split the vectorized
        VerifyCommit tally from them (the to_proto ADVICE-r5 staleness
        class). Validator.__setattr__ replaces the epoch token on any
        post-init voting_power/pub_key/address write, so the memo
        re-validates with one `is` comparison on the warm path — the
        10k-attribute fromiter walk this replaces was the single
        largest slice of the warm verify_commit scan (PERF.md
        warm-path breakdown) — and membership changes clear it through
        _reindex() like every other set memo."""
        epoch = _VAL_MUT_EPOCH[0]
        memo = self._powers_memo
        if memo is not None and memo[0] is epoch:
            return memo[1]
        import numpy as np

        arr = np.fromiter(
            (v.voting_power for v in self.validators),
            dtype=np.int64,
            count=len(self.validators),
        )
        arr.setflags(write=False)
        self._powers_memo = (epoch, arr)
        return arr

    def fingerprint_token(self):
        """Membership-identity token for the commit-level verification
        memo (types/validation.py): a unique object, replaced by
        _reindex() — the single choke point every membership mutation
        path runs through — and never shared with copies (copy() mints
        its own), so a sigcache commit key holding it can only ever hit
        for this exact set composition. In-place voting_power mutation
        does NOT move the token; the commit-memo key covers powers
        separately with the powers_array() bytes, which the epoch hook
        keeps live under in-place mutation (the ADVICE-r5 staleness
        class). An in-place pub_key swap that bypasses
        update_with_change_set is not covered — the same unsupported
        mutation that already leaves hash() and _addr_index stale."""
        if self._fp_token is None:
            self._fp_token = object()
        return self._fp_token

    def pubkeys_bytes(self) -> List[bytes]:
        """Raw pubkey encodings aligned with self.validators, memoized
        under the validator-mutation epoch and treated read-only by
        callers — the warm VerifyCommit scan builds 10k cache keys from
        these and the per-call `v.pub_key.bytes()` walk was a dominant
        slice of its Python cost (PERF.md warm-path breakdown).
        Invalidated by _reindex() like hash(), and additionally by the
        epoch hook on an in-place pub_key re-assignment — a mutation
        that still leaves _addr_index and hash() stale (unsupported as
        before), but can no longer serve this memo stale bytes."""
        epoch = _VAL_MUT_EPOCH[0]
        memo = self._pkb_memo
        if memo is not None and memo[0] is epoch:
            return memo[1]
        pkb = [v.pub_key.bytes() for v in self.validators]
        self._pkb_memo = (epoch, pkb)
        return pkb

    def key_classes(self) -> tuple:
        """(key types, codes, pub_keys), aligned with self.validators:
        the distinct PubKey.type() values in order of first appearance,
        for each validator the place of its key's type among them (a
        np.uint8 array, read-only), and the PubKey objects themselves.
        What batch routing groups a commit's cache misses by
        (types/validation.py): one masked select a key class, no
        attribute read or method call a vote. Memoized beside
        pubkeys_bytes() under the same validator-mutation epoch and
        cleared by _reindex() like it, so an in-place pub_key
        re-assignment can never route a vote to the old key's class."""
        epoch = _VAL_MUT_EPOCH[0]
        memo = self._key_classes_memo
        if memo is not None and memo[0] is epoch:
            return memo[1]
        pub_keys = [v.pub_key for v in self.validators]
        classes = key_type_codes(pub_keys) + (pub_keys,)
        self._key_classes_memo = (epoch, classes)
        return classes

    def total_voting_power(self) -> int:
        if self._total_voting_power == 0:
            self._update_total_voting_power()
        return self._total_voting_power

    def copy(self) -> "ValidatorSet":
        new = ValidatorSet.__new__(ValidatorSet)
        new.validators = [v.copy() for v in self.validators]
        new.proposer = self.proposer.copy() if self.proposer else None
        new._total_voting_power = self._total_voting_power
        new._addr_index = dict(self._addr_index)
        new._hash = self._hash  # same membership -> same merkle root
        new._proto_memo = None
        new._fp_token = None  # copies diverge independently: own token
        new._pkb_memo = None
        new._key_classes_memo = None
        new._powers_memo = None
        return new

    def _reindex(self) -> None:
        self._addr_index = {
            v.address: i for i, v in enumerate(self.validators)
        }
        self._hash = None  # membership changed; recompute lazily
        self._proto_memo = None
        self._fp_token = None
        self._pkb_memo = None
        self._key_classes_memo = None
        self._powers_memo = None

    def _update_total_voting_power(self) -> None:
        total = 0
        for v in self.validators:
            total += v.voting_power
            if total > MAX_TOTAL_VOTING_POWER:
                raise OverflowError(
                    f"total voting power exceeds max {MAX_TOTAL_VOTING_POWER}"
                )
        self._total_voting_power = total

    # -- proposer selection (reference: types/validator_set.go:107-226) --

    def get_proposer(self) -> Validator:
        if not self.validators:
            raise ValueError("empty validator set")
        if self.proposer is None:
            self.proposer = self._find_proposer()
        return self.proposer.copy()

    def _find_proposer(self) -> Validator:
        result = None
        for v in self.validators:
            result = v if result is None else _cmp_most_priority(result, v)
        return result

    def increment_proposer_priority(self, times: int) -> None:
        if not self.validators:
            raise ValueError("empty validator set")
        if times <= 0:
            raise ValueError("times must be positive")
        diff_max = PRIORITY_WINDOW_SIZE_FACTOR * self.total_voting_power()
        self.rescale_priorities(diff_max)
        self._shift_by_avg_proposer_priority()
        proposer = None
        for _ in range(times):
            proposer = self._increment_proposer_priority()
        self.proposer = proposer

    def copy_increment_proposer_priority(self, times: int) -> "ValidatorSet":
        c = self.copy()
        c.increment_proposer_priority(times)
        return c

    def _increment_proposer_priority(self) -> Validator:
        for v in self.validators:
            v.proposer_priority = _clip(
                v.proposer_priority + v.voting_power
            )
        mostest = self._find_proposer()
        mostest.proposer_priority = _clip(
            mostest.proposer_priority - self.total_voting_power()
        )
        return mostest

    def rescale_priorities(self, diff_max: int) -> None:
        if not self.validators:
            raise ValueError("empty validator set")
        if diff_max <= 0:
            return
        prios = [v.proposer_priority for v in self.validators]
        diff = max(prios) - min(prios)
        if diff < 0:
            diff = -diff
        ratio = (diff + diff_max - 1) // diff_max
        if diff > diff_max:
            for v in self.validators:
                # Go integer division truncates toward zero
                p = v.proposer_priority
                v.proposer_priority = (
                    -((-p) // ratio) if p < 0 else p // ratio
                )

    def _compute_avg_proposer_priority(self) -> int:
        n = len(self.validators)
        s = sum(v.proposer_priority for v in self.validators)
        # Go big.Int.Div uses Euclidean... actually Div is floored for
        # positive divisor: rounds toward negative infinity. Python //
        # matches for positive n.
        return s // n

    def _shift_by_avg_proposer_priority(self) -> None:
        avg = self._compute_avg_proposer_priority()
        for v in self.validators:
            v.proposer_priority = _clip(v.proposer_priority - avg)

    # -- hashing --

    def hash(self) -> bytes:
        """Merkle root of SimpleValidator leaves
        (reference: types/validator_set.go:347-353). Memoized: the
        root covers only (pub_key, voting_power) in order — NOT
        proposer priorities — so it survives proposer rotation and is
        invalidated by _reindex(), which every membership/power
        mutation path calls. Light sync and consensus re-hash the
        same 150+ validator set several times per header otherwise."""
        if self._hash is None:
            self._hash = merkle.hash_from_byte_slices(
                [v.hash_bytes() for v in self.validators]
            )
        return self._hash

    # -- change-set application (reference: validator_set.go:380-651) --

    def update_with_change_set(self, changes: List[Validator]) -> None:
        self._update_with_change_set(
            [c.copy() for c in changes], allow_deletes=True
        )

    def _update_with_change_set(
        self, changes: List[Validator], allow_deletes: bool
    ) -> None:
        if not changes:
            return
        updates, deletes = self._process_changes(changes)
        if not allow_deletes and deletes:
            raise ValueError(
                "cannot process validators with voting power 0"
            )
        num_new = sum(
            1 for u in updates if not self.has_address(u.address)
        )
        if num_new == 0 and len(self.validators) == len(deletes):
            raise ValueError(
                "applying the validator changes would result in empty set"
            )
        removed_power = self._verify_removals(deletes)
        tvp_after = self._verify_updates(updates, removed_power)
        # priorities for new validators: -1.125 * updated total power
        for u in updates:
            _, existing = self.get_by_address(u.address)
            if existing is None:
                u.proposer_priority = -(tvp_after + (tvp_after >> 3))
            else:
                u.proposer_priority = existing.proposer_priority
        self._apply_updates(updates)
        self._apply_removals(deletes)
        self._total_voting_power = 0
        self._update_total_voting_power()
        self.rescale_priorities(
            PRIORITY_WINDOW_SIZE_FACTOR * self.total_voting_power()
        )
        self._shift_by_avg_proposer_priority()
        # sort by voting power desc, address asc
        self.validators.sort(key=lambda v: (-v.voting_power, v.address))
        self._reindex()

    @staticmethod
    def _process_changes(
        changes: List[Validator],
    ) -> Tuple[List[Validator], List[Validator]]:
        by_addr = sorted(changes, key=lambda v: v.address)
        updates: List[Validator] = []
        removals: List[Validator] = []
        prev_addr = None
        for c in by_addr:
            if c.address == prev_addr:
                raise ValueError(f"duplicate entry {c.address.hex()}")
            if c.voting_power < 0:
                raise ValueError("voting power can't be negative")
            if c.voting_power > MAX_TOTAL_VOTING_POWER:
                raise ValueError(
                    f"voting power can't be higher than {MAX_TOTAL_VOTING_POWER}"
                )
            (removals if c.voting_power == 0 else updates).append(c)
            prev_addr = c.address
        return updates, removals

    def _verify_removals(self, deletes: List[Validator]) -> int:
        removed = 0
        for d in deletes:
            _, val = self.get_by_address(d.address)
            if val is None:
                raise ValueError(
                    f"failed to find validator {d.address.hex()} to remove"
                )
            removed += val.voting_power
        if len(deletes) > len(self.validators):
            raise ValueError("more deletes than validators")
        return removed

    def _verify_updates(
        self, updates: List[Validator], removed_power: int
    ) -> int:
        def delta(u: Validator) -> int:
            _, val = self.get_by_address(u.address)
            return (
                u.voting_power - val.voting_power
                if val is not None
                else u.voting_power
            )

        tvp_after_removals = self.total_voting_power() - removed_power
        for u in sorted(updates, key=delta):
            tvp_after_removals += delta(u)
            if tvp_after_removals > MAX_TOTAL_VOTING_POWER:
                raise OverflowError(
                    "total voting power of resulting valset exceeds max"
                )
        return tvp_after_removals + removed_power

    def _apply_updates(self, updates: List[Validator]) -> None:
        existing = sorted(self.validators, key=lambda v: v.address)
        updates = sorted(updates, key=lambda v: v.address)
        merged: List[Validator] = []
        i = j = 0
        while i < len(existing) and j < len(updates):
            if existing[i].address < updates[j].address:
                merged.append(existing[i])
                i += 1
            else:
                merged.append(updates[j])
                if existing[i].address == updates[j].address:
                    i += 1
                j += 1
        merged.extend(existing[i:])
        merged.extend(updates[j:])
        self.validators = merged
        self._reindex()

    def _apply_removals(self, deletes: List[Validator]) -> None:
        if not deletes:
            return
        dead = {d.address for d in deletes}
        self.validators = [
            v for v in self.validators if v.address not in dead
        ]
        self._reindex()

    # -- proto --

    def to_proto(self) -> bytes:
        """Memoized: the light client saves one LightBlock per header
        and every one of them embeds the SAME 150-validator set, so
        without the memo the pure-Python proto writer re-serializes
        ~150 pubkeys per header (more than half of measured sync time).
        Unlike hash(), the wire form covers proposer priorities, which
        mutate in place outside _reindex (increment_proposer_priority)
        — so the memo is validated against a cheap fingerprint of
        the mutable inputs on every call instead of trusting an
        invalidation hook. The fingerprint covers EVERY field the wire
        form reads per validator — priority, voting_power, pub_key
        identity, address — because this class hands out live
        Validator references (validators list, get_by_address): an
        embedder mutating a validator's power or key in place must get
        fresh bytes, not the memo (ADVICE r5)."""
        key = (
            tuple(
                (
                    v.address,
                    v.pub_key.bytes() if v.pub_key is not None else b"",
                    v.voting_power,
                    v.proposer_priority,
                )
                for v in self.validators
            ),
            # the proposer's full mutable record, not just its address:
            # copy()/from_proto() can leave self.proposer detached from
            # its list entry, so its fields can change independently
            (
                (
                    self.proposer.address,
                    (
                        self.proposer.pub_key.bytes()
                        if self.proposer.pub_key is not None
                        else b""
                    ),
                    self.proposer.voting_power,
                    self.proposer.proposer_priority,
                )
                if self.proposer is not None
                else None
            ),
        )
        memo = getattr(self, "_proto_memo", None)
        if memo is not None and memo[0] == key:
            return memo[1]
        w = ProtoWriter()
        for v in self.validators:
            w.message(1, v.to_proto())
        if self.proposer is not None:
            w.message(2, self.proposer.to_proto())
        w.int(3, self.total_voting_power())
        out = w.finish()
        self._proto_memo = (key, out)
        return out

    @classmethod
    def from_proto(cls, data: bytes) -> "ValidatorSet":
        # tmcheck: unparsed=3 — total_voting_power is recomputed from
        # the validators (reference ValidatorSetFromProto does the
        # same); trusting the wire value would let a peer lie about it
        vals: List[Validator] = []
        proposer = None
        for f, _wt, v in iter_fields(data):
            if f == 1:
                vals.append(Validator.from_proto(v))
            elif f == 2:
                proposer = Validator.from_proto(v)
        new = cls.__new__(cls)
        new.validators = vals
        new.proposer = proposer
        new._total_voting_power = 0
        new._reindex()  # one invalidation point: index + hash memo
        return new

    def validate_basic(self) -> None:
        if self.is_nil_or_empty():
            raise ValueError("validator set is nil or empty")
        for i, v in enumerate(self.validators):
            try:
                v.validate_basic()
            except ValueError as e:
                raise ValueError(f"invalid validator #{i}: {e}") from e
        if self.proposer is None:
            raise ValueError("proposer failed validate basic: nil")
        self.proposer.validate_basic()

    def __repr__(self) -> str:
        return (
            f"ValidatorSet(n={len(self.validators)}, "
            f"power={self.total_voting_power()})"
        )
