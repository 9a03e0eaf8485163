"""Commit and CommitSig — the 2/3-majority precommit record in a block.

Reference: types/block.go:560-930 (CommitSig :560-700, Commit :760-930),
proto field numbers proto/tendermint/types/types.pb.go:571-574,640-643.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import List, Optional

from ..crypto import merkle
from ..encoding.proto import FieldReader, ProtoWriter, iter_fields
from ..libs import trace
from ..libs.bits import BitArray
from ..native import commit_scan
from .block_id import BlockID
from .canonical import PRECOMMIT_TYPE
from .timestamp import decode_timestamp, encode_timestamp
from .vote import Vote

__all__ = [
    "BLOCK_ID_FLAG_ABSENT",
    "BLOCK_ID_FLAG_COMMIT",
    "BLOCK_ID_FLAG_NIL",
    "CommitSig",
    "Commit",
    "MAX_COMMIT_OVERHEAD_BYTES",
    "MAX_COMMIT_SIG_BYTES",
    "max_commit_bytes",
]

# BlockIDFlag enum (reference: types/block.go:550-558)
BLOCK_ID_FLAG_ABSENT = 1  # no vote was received from this validator
BLOCK_ID_FLAG_COMMIT = 2  # voted for the committed block
BLOCK_ID_FLAG_NIL = 3  # voted nil

MAX_COMMIT_OVERHEAD_BYTES = 94  # reference: types/block.go:597
MAX_COMMIT_SIG_BYTES = 109  # reference: types/block.go:600

MAX_SIGNATURE_SIZE = 64

# Process-wide commit-mutation epoch. Every Commit memo (sign-bytes
# rows, flags array, hash, splice templates, fingerprint token) is
# pinned to the token stored here when it was built; any POST-INIT
# assignment to a Commit or CommitSig wire field replaces the token
# (one atomic STORE_SUBSCR — no read-modify-write), so every memo in
# the process re-validates lazily on next access. In production commits
# are immutable after construction (nothing in the package assigns a
# CommitSig field post-init), so the token never moves and the check is
# one `is` comparison; tests that mutate in place (forged-signature /
# mutated-timestamp safety tests) invalidate conservatively across ALL
# commits, which is always sound — a cleared memo is just rebuilt.
# In-place mutation of the `signatures` LIST (append/slice assignment)
# is not observable here and remains unsupported, exactly as the
# pre-existing _hash/_sign_templates memos already assumed.
# tmrace: race-ok — single atomic list-slot store of a fresh token;
# concurrent bumps each publish a token unequal to every pinned memo,
# so any interleaving invalidates (the conservative direction)
_MUT_EPOCH = [object()]


def max_commit_bytes(val_count: int) -> int:
    """reference: types/block.go:621-625."""
    proto_encoding_overhead = 2
    return MAX_COMMIT_OVERHEAD_BYTES + (
        (MAX_COMMIT_SIG_BYTES + proto_encoding_overhead) * val_count
    )


@dataclass
class CommitSig:
    block_id_flag: int = BLOCK_ID_FLAG_ABSENT
    validator_address: bytes = b""
    timestamp_ns: int = 0
    signature: bytes = b""

    def __setattr__(self, name: str, value) -> None:
        # a RE-assignment (the attribute already exists — dataclass
        # __init__ sets each field exactly once on a fresh instance)
        # mutates a signed record: bump the process-wide epoch so every
        # commit memo derived from CommitSig content re-validates
        if name in self.__dict__:
            _MUT_EPOCH[0] = object()
        object.__setattr__(self, name, value)

    @classmethod
    def absent(cls) -> "CommitSig":
        return cls(block_id_flag=BLOCK_ID_FLAG_ABSENT)

    @classmethod
    def for_block(
        cls, signature: bytes, val_addr: bytes, timestamp_ns: int
    ) -> "CommitSig":
        return cls(
            block_id_flag=BLOCK_ID_FLAG_COMMIT,
            validator_address=val_addr,
            timestamp_ns=timestamp_ns,
            signature=signature,
        )

    @classmethod
    def for_nil(
        cls, signature: bytes, val_addr: bytes, timestamp_ns: int
    ) -> "CommitSig":
        return cls(
            block_id_flag=BLOCK_ID_FLAG_NIL,
            validator_address=val_addr,
            timestamp_ns=timestamp_ns,
            signature=signature,
        )

    def is_absent(self) -> bool:
        return self.block_id_flag == BLOCK_ID_FLAG_ABSENT

    def is_for_block(self) -> bool:
        return self.block_id_flag == BLOCK_ID_FLAG_COMMIT

    def vote_block_id(self, commit_block_id: BlockID) -> BlockID:
        """BlockID this sig's vote was cast for (reference:
        types/block.go:661-674): the commit's for COMMIT, zero otherwise."""
        if self.block_id_flag == BLOCK_ID_FLAG_COMMIT:
            return commit_block_id
        return BlockID()

    def validate_basic(self) -> None:
        if self.block_id_flag not in (
            BLOCK_ID_FLAG_ABSENT,
            BLOCK_ID_FLAG_COMMIT,
            BLOCK_ID_FLAG_NIL,
        ):
            raise ValueError(f"unknown BlockIDFlag: {self.block_id_flag}")
        if self.block_id_flag == BLOCK_ID_FLAG_ABSENT:
            if self.validator_address:
                raise ValueError("validator address is present")
            if self.timestamp_ns:
                raise ValueError("time is present")
            if self.signature:
                raise ValueError("signature is present")
        else:
            if len(self.validator_address) != 20:
                raise ValueError(
                    "expected ValidatorAddress size to be 20 bytes"
                )
            if not self.signature:
                raise ValueError("signature is missing")
            if len(self.signature) > MAX_SIGNATURE_SIZE:
                raise ValueError("signature is too big")

    def to_proto(self) -> bytes:
        w = ProtoWriter()
        w.int(1, self.block_id_flag)
        w.bytes(2, self.validator_address)
        w.message(3, encode_timestamp(self.timestamp_ns))
        w.bytes(4, self.signature)
        return w.finish()

    @classmethod
    def from_proto(cls, data: bytes) -> "CommitSig":
        r = FieldReader(data)
        ts = r.get(3)
        return cls(
            block_id_flag=r.uint(1),
            validator_address=r.bytes(2),
            timestamp_ns=decode_timestamp(ts) if ts is not None else 0,
            signature=r.bytes(4),
        )


@dataclass
class Commit:
    height: int = 0
    round: int = 0
    block_id: BlockID = field(default_factory=BlockID)
    signatures: List[CommitSig] = field(default_factory=list)

    _hash: Optional[bytes] = field(
        default=None, repr=False, compare=False
    )
    # (chain_id, for_block) -> VoteSignTemplate; see vote_sign_bytes
    _sign_templates: Optional[dict] = field(
        default=None, repr=False, compare=False
    )
    # np.uint8 BlockIDFlags per signature; see block_id_flags_array
    _flags_memo: Optional[object] = field(
        default=None, repr=False, compare=False
    )
    # chain_id -> list of Optional[bytes] sign-bytes rows (None at
    # absent or not-yet-encoded indexes); see sign_bytes_batch
    _sb_rows: Optional[dict] = field(
        default=None, repr=False, compare=False
    )
    # chain_ids whose _sb_rows entry covers every non-absent index
    _sb_complete: Optional[set] = field(
        default=None, repr=False, compare=False
    )
    # content-identity token; see fingerprint_token
    _fp_token: Optional[object] = field(
        default=None, repr=False, compare=False
    )
    # the _MUT_EPOCH token the memos above were built under
    _memo_epoch: Optional[object] = field(
        default=None, repr=False, compare=False
    )

    # wire fields: a post-init assignment to one of these mutates the
    # signed record the memos were derived from
    _WIRE_FIELDS = frozenset({"height", "round", "block_id", "signatures"})

    def __setattr__(self, name: str, value) -> None:
        if name in self._WIRE_FIELDS and name in self.__dict__:
            _MUT_EPOCH[0] = object()
        object.__setattr__(self, name, value)

    def _memos_fresh(self) -> None:
        """Pin the memos to the current mutation epoch, dropping them
        all when ANY commit/sig field was re-assigned since they were
        built (see _MUT_EPOCH). Called at the top of every memoized
        accessor; the warm-path cost is one `is` comparison."""
        epoch = _MUT_EPOCH[0]
        if self._memo_epoch is not epoch:
            self._hash = None
            self._sign_templates = None
            self._flags_memo = None
            self._sb_rows = None
            self._sb_complete = None
            self._fp_token = None
            self._memo_epoch = epoch

    def invalidate_memos(self) -> None:
        """Drop every memo on THIS commit (bench cold rows, tests).
        Production code never needs this — memos self-invalidate on
        field mutation via the epoch."""
        self._memo_epoch = None
        self._memos_fresh()

    def fingerprint_token(self):
        """Content-identity token for the commit-level verification
        memo (types/validation.py): a unique object created lazily and
        REPLACED whenever any commit/sig field mutates, so a sigcache
        entry keyed on it can never alias different commit contents —
        unlike id(), a dead token is unreachable rather than reusable,
        and unlike a content digest it costs nothing to compare. The
        soundness argument is the same immutability-after-construction
        property every other memo here relies on, machine-checked by
        `scripts/lint.py --memo-audit` (docs/static_analysis.md)."""
        self._memos_fresh()
        if self._fp_token is None:
            self._fp_token = object()
        return self._fp_token

    def size(self) -> int:
        return len(self.signatures)

    def is_commit(self) -> bool:
        return len(self.signatures) != 0

    def bit_array(self) -> BitArray:
        ba = BitArray(len(self.signatures))
        for i, cs in enumerate(self.signatures):
            ba.set(i, not cs.is_absent())
        return ba

    def block_id_flags_array(self):
        """Per-signature BlockIDFlags as a read-only np.uint8 array,
        memoized — a Commit's signature list never changes after
        construction (the same property _hash and _sign_templates rely
        on). The vectorized VerifyCommit tally masks validator powers
        with it. Returns None when any flag is outside uint8 range
        (from_proto reads an unbounded varint): callers must fall back
        to the scalar loop so a hostile commit gets the reference
        InvalidCommitError, not an OverflowError from the memo."""
        self._memos_fresh()
        if self._flags_memo is None:
            import numpy as np

            try:
                # widen to int64 and range-check explicitly: fromiter
                # straight into uint8 raises on out-of-range only on
                # numpy >= 2 — numpy 1.x wraps modulo 256, which would
                # silently reclassify flag 257 as ABSENT and skip its
                # signature. int64 still overflows (and raises on both
                # majors) for varints past 2**63, hence the except.
                arr = np.fromiter(
                    (cs.block_id_flag for cs in self.signatures),
                    dtype=np.int64,
                    count=len(self.signatures),
                )
            except (OverflowError, ValueError):
                return None
            if arr.size and (arr.min() < 0 or arr.max() > 0xFF):
                return None
            arr = arr.astype(np.uint8)
            arr.setflags(write=False)
            self._flags_memo = arr
        return self._flags_memo

    def get_vote(self, val_idx: int) -> Vote:
        """Reconstruct the precommit vote at a validator index
        (reference: types/block.go:793-805)."""
        cs = self.signatures[val_idx]
        return Vote(
            type=PRECOMMIT_TYPE,
            height=self.height,
            round=self.round,
            block_id=cs.vote_block_id(self.block_id),
            timestamp_ns=cs.timestamp_ns,
            validator_address=cs.validator_address,
            validator_index=val_idx,
            signature=cs.signature,
        )

    def _sign_template(self, chain_id: str, for_block: bool):
        """Cached per-(chain_id, block-id-flag) splice template: only
        the timestamp varies between a commit's signatures, and the
        full proto-marshal path costs ~14 us/vote — the dominant host
        cost of a large VerifyCommit (types/validation.go:152 analog)."""
        from .canonical import VoteSignTemplate

        self._memos_fresh()
        if self._sign_templates is None:
            self._sign_templates = {}
        tpl = self._sign_templates.get((chain_id, for_block))
        if tpl is None:
            tpl = VoteSignTemplate(
                chain_id,
                PRECOMMIT_TYPE,
                self.height,
                self.round,
                self.block_id if for_block else BlockID(),
            )
            self._sign_templates[(chain_id, for_block)] = tpl
        return tpl

    def _rows_for(self, chain_id: str) -> List[Optional[bytes]]:
        """The per-chain sign-bytes row memo, allocated on first use.
        Callers must have run _memos_fresh() this access."""
        if self._sb_rows is None:
            self._sb_rows = {}
            self._sb_complete = set()
        rows = self._sb_rows.get(chain_id)
        if rows is None:
            rows = self._sb_rows[chain_id] = [None] * len(self.signatures)
        return rows

    def vote_sign_bytes(self, chain_id: str, val_idx: int) -> bytes:
        """Sign-bytes of the vote at a validator index. Byte-identical
        to get_vote(i).sign_bytes(chain_id) (tests/test_encoding.py).

        Memoized per (chain_id, index) in the same rows list
        sign_bytes_batch fills: a commit's sign-bytes are a pure
        function of (type, height, round, block_id, timestamp,
        chain_id) — machine-proved deterministic by tmcheck's taint
        gate (docs/static_analysis.md) — and the inputs are frozen
        after construction (mutation drops the memo via _MUT_EPOCH).
        gossip-verify, LastCommit re-verification, and the light
        client's double-verify each re-encoded the same rows before;
        now only the first pass pays, and only for the indexes it
        actually visits (early-exit variants never encode discarded
        rows)."""
        self._memos_fresh()
        cs = self.signatures[val_idx]
        if cs.block_id_flag == BLOCK_ID_FLAG_ABSENT:
            # not memoized: sign_bytes_batch's contract keeps absent
            # rows None, and no verification path requests them
            tpl = self._sign_template(chain_id, False)
            return tpl.sign_bytes(cs.timestamp_ns)
        rows = self._rows_for(chain_id)
        row = rows[val_idx]
        if row is None:
            tpl = self._sign_template(
                chain_id, cs.block_id_flag == BLOCK_ID_FLAG_COMMIT
            )
            row = rows[val_idx] = tpl.sign_bytes(cs.timestamp_ns)
        return row

    def sign_bytes_batch(self, chain_id: str) -> List[Optional[bytes]]:
        """Sign-bytes for every non-absent signature in one pass
        (None at absent indexes). The batch VerifyCommit path uses
        this instead of per-index vote_sign_bytes: template splicing
        plus the tight per-timestamp loop beats the full marshal ~10x
        at 10k signatures.

        Memoized per chain_id (see vote_sign_bytes for the soundness
        argument): the returned list is SHARED with the memo and must
        be treated read-only by callers. Warm verification paths
        (steady-state LastCommit, light-client double-verify) hit this
        memo and perform zero canonical encodes — the tier-1
        counting-stub guard in tests/test_sigcache.py pins that."""
        self._memos_fresh()
        sigs = self.signatures
        if self._sb_complete is not None and chain_id in self._sb_complete:
            return self._sb_rows[chain_id]
        out = self._rows_for(chain_id)
        for for_block in (True, False):
            idxs = [
                i
                for i, cs in enumerate(sigs)
                if not cs.is_absent()
                and (cs.block_id_flag == BLOCK_ID_FLAG_COMMIT) == for_block
                and out[i] is None
            ]
            if not idxs:
                continue
            tpl = self._sign_template(chain_id, for_block)
            rows = tpl.sign_bytes_batch(
                [sigs[i].timestamp_ns for i in idxs]
            )
            for i, row in zip(idxs, rows):
                out[i] = row
        self._sb_complete.add(chain_id)
        return out

    def validate_basic(self) -> None:
        if self.height < 0:
            raise ValueError("negative Height")
        if self.round < 0:
            raise ValueError("negative Round")
        if self.height >= 1:
            if self.block_id.is_zero():
                raise ValueError("commit cannot be for nil block")
            if not self.signatures:
                raise ValueError("no signatures in commit")
            for i, cs in enumerate(self.signatures):
                try:
                    cs.validate_basic()
                except ValueError as e:
                    raise ValueError(f"wrong CommitSig #{i}: {e}") from e

    def hash(self) -> bytes:
        """Merkle root over marshalled CommitSigs
        (reference: types/block.go:902-921)."""
        self._memos_fresh()
        if self._hash is None:
            self._hash = merkle.hash_from_byte_slices(
                [cs.to_proto() for cs in self.signatures]
            )
        return self._hash

    def to_proto(self) -> bytes:
        w = ProtoWriter()
        w.int(1, self.height)
        w.int(2, self.round)
        w.message(3, self.block_id.to_proto())  # nullable=false
        for cs in self.signatures:
            w.message(4, cs.to_proto())
        return w.finish()

    @classmethod
    def from_proto(cls, data: bytes) -> "Commit":
        """Decode a Commit. When the `signatures` entries are laid out
        the way every encoder lays them out (native/commit_scan.c has
        the exact accept set) they are scanned in one native pass and
        only fields 1-3 go through the loop below; any other input —
        one odd entry is enough — is decoded whole by the loop, which
        defines every edge and every error. The choice is made from
        the bytes alone; the span's `path` says which was taken."""
        height = 0
        round_ = 0
        block_id = BlockID()
        sigs: List[CommitSig] = []
        with trace.span("commit_decode", bytes=len(data)) as span:
            scanned = commit_scan(data)
            if scanned is not None:
                head_end, columns = scanned
                sigs = _sigs_from_columns(data, columns)
                data = data[:head_end]
            for f, _wt, v in iter_fields(data):
                if f == 1:
                    height = v
                elif f == 2:
                    round_ = v
                elif f == 3:
                    block_id = BlockID.from_proto(v)
                elif f == 4:
                    sigs.append(CommitSig.from_proto(v))
            span.set(
                sigs=len(sigs),
                path="generic" if scanned is None else "native",
            )
            return cls(
                height=height, round=round_, block_id=block_id,
                signatures=sigs,
            )


def _sigs_from_columns(data: bytes, columns: list) -> List[CommitSig]:
    """CommitSigs from native.commit_scan's columns, equal field for
    field to CommitSig.from_proto's and laid out the same: the fields
    are stored one by one into the instance `__dict__`, which is where
    the dataclass `__init__` puts them through four `__setattr__`
    calls (one `update()` would be a line shorter and leave every
    instance a key table of its own: 70 bytes more a vote and slower
    attribute reads in validation). A later re-assignment finds the
    field there and moves _MUT_EPOCH."""
    new = object.__new__
    sigs = []
    append = sigs.append
    for flag, addr0, addr1, ts, sig0, sig1 in zip(*columns):
        cs = new(CommitSig)
        fields = cs.__dict__
        fields["block_id_flag"] = flag
        fields["validator_address"] = data[addr0:addr1]
        fields["timestamp_ns"] = ts
        fields["signature"] = data[sig0:sig1]
        append(cs)
    return sigs
