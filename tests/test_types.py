"""Types layer: canonical encoding, votes, commits, headers, validator sets.

Mirrors the reference's own test strategy (types/validation_test.go,
types/validator_set_test.go, types/block_test.go): table-driven unit
tests plus batch-vs-single equivalence.
"""

import random
from unittest import mock

import pytest

from tendermint_tpu import native
from tendermint_tpu.crypto.ed25519 import PrivKeyEd25519
from tendermint_tpu.encoding.proto import encode_varint
from tendermint_tpu.libs import trace
from tendermint_tpu.types import commit as commit_mod
from tendermint_tpu.types import (
    PRECOMMIT_TYPE,
    BlockID,
    Commit,
    CommitSig,
    Header,
    PartSetHeader,
    Proposal,
    Validator,
    ValidatorSet,
    Vote,
    VoteSet,
    commit_to_vote_set,
    make_block,
)
from tendermint_tpu.types.part_set import PartSet
from tendermint_tpu.types.vote_set import ConflictingVoteError

CHAIN_ID = "test-chain"


def make_validators(n, power=10):
    """n deterministic validators with their privkeys, sorted as the
    ValidatorSet sorts them."""
    pairs = []
    for i in range(n):
        pk = PrivKeyEd25519.from_seed(bytes([i + 1]) * 32)
        pairs.append(pk)
    vals = ValidatorSet(
        [
            Validator(pub_key=pk.pub_key(), voting_power=power)
            for pk in pairs
        ]
    )
    by_addr = {pk.pub_key().address(): pk for pk in pairs}
    privs = [by_addr[v.address] for v in vals.validators]
    return vals, privs


def make_block_id(seed=b"\x01"):
    return BlockID(
        hash=seed * 32,
        part_set_header=PartSetHeader(total=1, hash=seed * 32),
    )


def signed_vote(priv, vals, idx, block_id, height=1, round_=0, ts=1000):
    v = Vote(
        type=PRECOMMIT_TYPE,
        height=height,
        round=round_,
        block_id=block_id,
        timestamp_ns=ts,
        validator_address=vals.validators[idx].address,
        validator_index=idx,
    )
    v.signature = priv.sign(v.sign_bytes(CHAIN_ID))
    return v


class TestVote:
    def test_sign_verify_roundtrip(self):
        vals, privs = make_validators(1)
        v = signed_vote(privs[0], vals, 0, make_block_id())
        v.verify(CHAIN_ID, privs[0].pub_key())

    def test_verify_rejects_wrong_chain(self):
        vals, privs = make_validators(1)
        v = signed_vote(privs[0], vals, 0, make_block_id())
        with pytest.raises(ValueError):
            v.verify("other-chain", privs[0].pub_key())

    def test_proto_roundtrip(self):
        vals, privs = make_validators(1)
        v = signed_vote(privs[0], vals, 0, make_block_id())
        v2 = Vote.from_proto(v.to_proto())
        assert v2 == v

    def test_nil_vote_sign_bytes_differ(self):
        vals, privs = make_validators(1)
        a = signed_vote(privs[0], vals, 0, make_block_id())
        b = signed_vote(privs[0], vals, 0, BlockID())
        assert a.sign_bytes(CHAIN_ID) != b.sign_bytes(CHAIN_ID)


class TestProposal:
    def test_sign_verify_proto(self):
        priv = PrivKeyEd25519.from_seed(b"\x07" * 32)
        p = Proposal(
            height=3,
            round=1,
            pol_round=-1,
            block_id=make_block_id(),
            timestamp_ns=123456789,
        )
        p.signature = priv.sign(p.sign_bytes(CHAIN_ID))
        assert p.verify(CHAIN_ID, priv.pub_key())
        p2 = Proposal.from_proto(p.to_proto())
        assert p2 == p
        assert p2.pol_round == -1


class TestValidatorSet:
    def test_sorted_by_power_then_address(self):
        privs = [PrivKeyEd25519.from_seed(bytes([i]) * 32) for i in range(1, 5)]
        vals = ValidatorSet(
            [
                Validator(pub_key=privs[0].pub_key(), voting_power=5),
                Validator(pub_key=privs[1].pub_key(), voting_power=50),
                Validator(pub_key=privs[2].pub_key(), voting_power=20),
                Validator(pub_key=privs[3].pub_key(), voting_power=20),
            ]
        )
        powers = [v.voting_power for v in vals.validators]
        assert powers == [50, 20, 20, 5]
        # equal powers tie-break by address ascending
        a, b = vals.validators[1], vals.validators[2]
        assert a.address < b.address
        assert vals.total_voting_power() == 95

    def test_proposer_rotation_weighted(self):
        vals, _ = make_validators(3)
        # equal power: each validator proposes once per 3 rounds
        seen = []
        vs = vals.copy()
        for _ in range(6):
            seen.append(vs.get_proposer().address)
            vs.increment_proposer_priority(1)
        assert len(set(seen[:3])) == 3
        assert seen[:3] == seen[3:6]

    def test_proposer_frequency_proportional(self):
        privs = [PrivKeyEd25519.from_seed(bytes([i]) * 32) for i in (1, 2)]
        vals = ValidatorSet(
            [
                Validator(pub_key=privs[0].pub_key(), voting_power=3),
                Validator(pub_key=privs[1].pub_key(), voting_power=1),
            ]
        )
        heavy = max(
            vals.validators, key=lambda v: v.voting_power
        ).address
        count = 0
        vs = vals.copy()
        for _ in range(40):
            if vs.get_proposer().address == heavy:
                count += 1
            vs.increment_proposer_priority(1)
        assert count == 30  # 3/4 of 40

    def test_update_with_change_set(self):
        vals, privs = make_validators(3)
        new_priv = PrivKeyEd25519.from_seed(b"\x99" * 32)
        vals.update_with_change_set(
            [Validator(pub_key=new_priv.pub_key(), voting_power=7)]
        )
        assert vals.size() == 4
        # remove one
        vals.update_with_change_set(
            [Validator(pub_key=new_priv.pub_key(), voting_power=0)]
        )
        assert vals.size() == 3

    def test_hash_changes_with_membership(self):
        vals, _ = make_validators(3)
        vals2, _ = make_validators(4)
        assert vals.hash() != vals2.hash()

    def test_proto_roundtrip(self):
        vals, _ = make_validators(3)
        vals.get_proposer()
        v2 = ValidatorSet.from_proto(vals.to_proto())
        assert v2.hash() == vals.hash()
        assert [v.address for v in v2.validators] == [
            v.address for v in vals.validators
        ]

    def test_to_proto_memo_tracks_priority_rotation(self):
        """to_proto is memoized (the light store serializes the same
        set once per header), but its wire form covers proposer
        priorities — rotation must invalidate it even though no
        membership changed."""
        vals, _ = make_validators(4)
        first = vals.to_proto()
        assert vals.to_proto() is first  # memo hit, same object
        rotated = vals.copy_increment_proposer_priority(1)
        assert rotated.to_proto() != first
        vals.increment_proposer_priority(1)
        after = vals.to_proto()
        assert after != first
        # the memoized bytes equal a fresh, unmemoized serialization
        rt = ValidatorSet.from_proto(after)
        assert [
            (v.address, v.voting_power, v.proposer_priority)
            for v in rt.validators
        ] == [
            (v.address, v.voting_power, v.proposer_priority)
            for v in vals.validators
        ]
        assert rt.proposer.address == vals.proposer.address

    def test_to_proto_memo_tracks_inplace_power_mutation(self):
        """ADVICE r5: ValidatorSet hands out live Validator references
        (the validators list itself), so an embedder mutating
        voting_power or the pub_key in place — without going through
        the change-set API — must still get fresh wire bytes, not the
        memo's stale ones."""
        vals, _ = make_validators(4)
        first = vals.to_proto()
        assert vals.to_proto() is first
        # in-place power mutation: no _reindex, no priority change
        vals.validators[0].voting_power += 5
        mutated = vals.to_proto()
        assert mutated != first
        rt = ValidatorSet.from_proto(mutated)
        assert rt.validators[0].voting_power == (
            vals.validators[0].voting_power
        )
        # pub_key identity swap on a detached proposer record
        assert vals.to_proto() is vals.to_proto()  # memo re-established
        other = PrivKeyEd25519.from_seed(b"\x99" * 32).pub_key()
        before = vals.to_proto()
        vals.proposer.pub_key = other
        assert vals.to_proto() != before


class TestVoteSet:
    def test_quorum_and_commit(self):
        vals, privs = make_validators(4)
        bid = make_block_id()
        vs = VoteSet(CHAIN_ID, 1, 0, PRECOMMIT_TYPE, vals)
        assert not vs.has_two_thirds_majority()
        for i in range(3):
            assert vs.add_vote(signed_vote(privs[i], vals, i, bid))
        assert vs.has_two_thirds_majority()
        maj, ok = vs.two_thirds_majority()
        assert ok and maj == bid
        commit = vs.make_commit()
        assert commit.size() == 4
        assert commit.signatures[3].is_absent()
        assert sum(1 for s in commit.signatures if s.is_for_block()) == 3

    def test_duplicate_vote_not_added(self):
        vals, privs = make_validators(4)
        bid = make_block_id()
        vs = VoteSet(CHAIN_ID, 1, 0, PRECOMMIT_TYPE, vals)
        v = signed_vote(privs[0], vals, 0, bid)
        assert vs.add_vote(v)
        assert not vs.add_vote(v)

    def test_conflicting_vote_raises(self):
        vals, privs = make_validators(4)
        vs = VoteSet(CHAIN_ID, 1, 0, PRECOMMIT_TYPE, vals)
        assert vs.add_vote(signed_vote(privs[0], vals, 0, make_block_id(b"\x01")))
        with pytest.raises(ConflictingVoteError):
            vs.add_vote(signed_vote(privs[0], vals, 0, make_block_id(b"\x02")))

    def test_nil_votes_tally_but_no_block_majority(self):
        vals, privs = make_validators(4)
        vs = VoteSet(CHAIN_ID, 1, 0, PRECOMMIT_TYPE, vals)
        for i in range(3):
            vs.add_vote(signed_vote(privs[i], vals, i, BlockID()))
        assert vs.has_two_thirds_any()
        maj, ok = vs.two_thirds_majority()
        assert ok and maj == BlockID()  # 2/3 for nil

    def test_commit_roundtrip_through_vote_set(self):
        vals, privs = make_validators(4)
        bid = make_block_id()
        vs = VoteSet(CHAIN_ID, 5, 2, PRECOMMIT_TYPE, vals)
        for i in range(4):
            vs.add_vote(
                signed_vote(privs[i], vals, i, bid, height=5, round_=2)
            )
        commit = vs.make_commit()
        vs2 = commit_to_vote_set(CHAIN_ID, commit, vals)
        assert vs2.has_two_thirds_majority()
        c2 = vs2.make_commit()
        assert c2.hash() == commit.hash()


class TestCommit:
    def test_proto_roundtrip(self):
        vals, privs = make_validators(4)
        bid = make_block_id()
        vs = VoteSet(CHAIN_ID, 1, 0, PRECOMMIT_TYPE, vals)
        for i in range(3):
            vs.add_vote(signed_vote(privs[i], vals, i, bid))
        commit = vs.make_commit()
        c2 = Commit.from_proto(commit.to_proto())
        assert c2.hash() == commit.hash()
        assert c2.block_id == commit.block_id

    def test_validate_basic(self):
        c = Commit(height=1, round=0, block_id=make_block_id(), signatures=[])
        with pytest.raises(ValueError, match="no signatures"):
            c.validate_basic()


class TestHeaderAndBlock:
    def test_header_hash_deterministic_and_field_sensitive(self):
        h = Header(
            chain_id=CHAIN_ID,
            height=3,
            time_ns=1234,
            validators_hash=b"\x01" * 32,
            next_validators_hash=b"\x02" * 32,
            consensus_hash=b"\x03" * 32,
            proposer_address=b"\x04" * 20,
        )
        h1 = h.hash()
        assert len(h1) == 32
        h.height = 4
        assert h.hash() != h1

    def test_header_hash_empty_without_validators_hash(self):
        assert Header(chain_id=CHAIN_ID, height=1).hash() == b""

    def test_header_proto_roundtrip(self):
        h = Header(
            chain_id=CHAIN_ID,
            height=3,
            time_ns=1234,
            validators_hash=b"\x01" * 32,
            proposer_address=b"\x04" * 20,
        )
        h2 = Header.from_proto(h.to_proto())
        assert h2 == h

    def test_block_roundtrip_and_part_set(self):
        commit = Commit()
        b = make_block(1, [b"tx1", b"tx2"], commit, [])
        b.header.validators_hash = b"\x01" * 32
        b.header.next_validators_hash = b"\x01" * 32
        b.header.consensus_hash = b"\x02" * 32
        b.header.proposer_address = b"\x03" * 20
        assert len(b.hash()) == 32
        ps = b.make_part_set(64)
        assert ps.is_complete()
        b2 = type(b).from_proto(ps.assemble())
        assert b2.hash() == b.hash()
        assert b2.txs == [b"tx1", b"tx2"]


class TestPartSet:
    def test_add_part_verifies_proof(self):
        data = bytes(range(256)) * 10
        ps = PartSet.from_data(data, part_size=128)
        rebuilt = PartSet.from_header(ps.header())
        for p in ps.parts:
            assert rebuilt.add_part(p)
        assert rebuilt.is_complete()
        assert rebuilt.assemble() == data

    def test_add_part_rejects_corrupt(self):
        data = b"x" * 300
        ps = PartSet.from_data(data, part_size=128)
        rebuilt = PartSet.from_header(ps.header())
        bad = ps.parts[0]
        bad.bytes = b"y" + bad.bytes[1:]
        with pytest.raises(ValueError, match="invalid proof"):
            rebuilt.add_part(bad)


def test_validator_set_hash_memo_tracks_membership():
    """The memoized ValidatorSet.hash() must change when membership or
    power changes, survive proposer rotation unchanged (priorities are
    not part of the merkle leaves), and round-trip through copy() and
    proto."""
    from tendermint_tpu.crypto.ed25519 import PrivKeyEd25519
    from tendermint_tpu.types.validator import Validator, ValidatorSet

    privs = [
        PrivKeyEd25519.from_seed(bytes([i + 1, 0x5e]) + b"\x24" * 30)
        for i in range(4)
    ]
    vals = ValidatorSet(
        [Validator(pub_key=p.pub_key(), voting_power=10) for p in privs]
    )
    h0 = vals.hash()
    assert vals.hash() == h0  # memo stable
    vals.increment_proposer_priority(3)
    assert vals.hash() == h0  # priorities not hashed

    cp = vals.copy()
    assert cp.hash() == h0

    # power change invalidates
    vals.update_with_change_set(
        [Validator(pub_key=privs[0].pub_key(), voting_power=25)]
    )
    h1 = vals.hash()
    assert h1 != h0
    # and matches a freshly-built set with the same membership
    fresh = ValidatorSet(
        [
            Validator(
                pub_key=p.pub_key(),
                voting_power=25 if i == 0 else 10,
            )
            for i, p in enumerate(privs)
        ]
    )
    assert fresh.hash() == h1
    # removal invalidates too
    vals.update_with_change_set(
        [Validator(pub_key=privs[1].pub_key(), voting_power=0)]
    )
    assert vals.hash() != h1
    # proto round-trip recomputes to the same root
    from tendermint_tpu.types.validator import ValidatorSet as VS

    assert VS.from_proto(vals.to_proto()).hash() == vals.hash()


# -- Commit.from_proto: the native signature scan against the generic
# -- decoder (native/commit_scan.c; the generic loop is the oracle) -----

NS = 10**9
# nanos of every varint width (1 to 5 bytes), and none
NANOS = (0, 1, 127, 128, 16_383, 16_384, 2_097_151, 2_097_152,
         268_435_455, 268_435_456, 999_999_999)  # fmt: skip
SECONDS = (0, 1, 1_700_000_000, -1, -9_223_372_036, 2**31, 9_223_372_035)


def _field(tag: int, body: bytes) -> bytes:
    return bytes([tag]) + encode_varint(len(body)) + body


def _ts(secs: int, nanos: int) -> bytes:
    out = b""
    if secs:
        out += b"\x08" + encode_varint(secs)
    if nanos:
        out += b"\x10" + encode_varint(nanos)
    return out


def _entry(flag=2, addr=b"\xaa" * 20, ts=_ts(1_700_000_000, 5), sig=b"\x55" * 64):
    """One CommitSig body as every encoder writes it; `ts=None` leaves
    the timestamp message out."""
    out = b""
    if flag:
        out += b"\x08" + encode_varint(flag)
    if addr:
        out += _field(0x12, addr)
    if ts is not None:
        out += _field(0x1A, ts)
    if sig:
        out += _field(0x22, sig)
    return out


HEAD = (
    b"\x08\x07\x10\x01"
    + _field(0x1A, BlockID(hash=b"\x01" * 32, part_set_header=PartSetHeader(total=1, hash=b"\x02" * 32)).to_proto())
)  # fmt: skip


def _wire(entries, head=HEAD) -> bytes:
    return head + b"".join(_field(0x22, e) for e in entries)


def _mixed_commit(n: int, seed: int = 7) -> Commit:
    """n votes: COMMIT, NIL and ABSENT mixed, every SECONDS x NANOS
    pair among the timestamps."""
    rng = random.Random(seed)
    sigs = []
    for i in range(n):
        kind = i % 7
        ts = SECONDS[i % len(SECONDS)] * NS + NANOS[(i // len(SECONDS)) % len(NANOS)]
        if kind == 3:
            sigs.append(CommitSig.absent())
        elif kind == 5:
            sigs.append(CommitSig.for_nil(rng.randbytes(64), rng.randbytes(20), ts))
        else:
            sigs.append(CommitSig.for_block(rng.randbytes(64), rng.randbytes(20), ts))
    return Commit(height=12, round=1, block_id=make_block_id(b"\x07"), signatures=sigs)


def _library_unavailable():
    """What TM_TPU_NO_NATIVE or a missing compiler leaves: load() has
    cached None for the unit."""
    return mock.patch.dict(native._LIBS, {"commit_scan": None})


def _outcome(wire):
    """(the decoded Commit or the exception's type and text, the
    `commit_decode` span's path)."""
    trace.reset()
    trace.enable()
    try:
        try:
            got = Commit.from_proto(wire)
        except Exception as e:  # noqa: BLE001 - the exact error is compared
            got = (type(e), str(e))
        spans = [s for s in trace.snapshot() if s.name == "commit_decode"]
    finally:
        trace.disable()
        trace.reset()
    return got, (spans[-1].attrs.get("path") if spans else None)


def _both(wire):
    """(native-enabled outcome, its path, the generic decoder's
    outcome): the second decode runs with the library unavailable."""
    got, path = _outcome(wire)
    with _library_unavailable():
        want, generic_path = _outcome(wire)
    assert generic_path in ("generic", None)
    return got, path, want


needs_native = pytest.mark.skipif(
    native.commit_scan_lib() is None, reason="no native toolchain"
)


@needs_native
@pytest.mark.parametrize("n", [1, 4, 150, 10_000])
def test_commit_decode_native_equals_generic(n):
    commit = _mixed_commit(n)
    wire = commit.to_proto()
    got, path, want = _both(wire)
    assert path == "native"
    assert got == want == commit
    assert [type(cs.block_id_flag) for cs in got.signatures[:4]] == [int] * min(n, 4)
    assert got.to_proto() == wire
    assert got.hash() == want.hash() == commit.hash()
    assert (got.height, got.round, got.block_id) == (12, 1, commit.block_id)


CANONICAL_ENTRIES = {
    "absent": b"\x08\x01\x1a\x00",
    "nil": _entry(flag=3),
    "every_field_omitted": b"",
    "no_timestamp_message": _entry(ts=None),
    "empty_timestamp": _entry(ts=b""),
    "negative_seconds": _entry(ts=_ts(-5, 999_999_999)),
    "largest_timestamp": _entry(ts=_ts(9_223_372_036, 854_775_807)),
    "smallest_timestamp": _entry(ts=_ts(-9_223_372_037, 145_224_192)),
    "flag_127": _entry(flag=127),
    "long_address_and_signature": _entry(addr=b"\x01" * 200, sig=b"\x02" * 20_000),
    "one_byte_address_and_signature": _entry(addr=b"\x01", sig=b"\x02"),
}


@needs_native
@pytest.mark.parametrize("shape", sorted(CANONICAL_ENTRIES))
def test_commit_decode_canonical_entry_is_native(shape):
    """Whatever an encoder can write is scanned natively, lengths the
    decoder has no business judging included, and equals the generic
    decoder's result."""
    wire = _wire([_entry(), CANONICAL_ENTRIES[shape], _entry(flag=3)])
    got, path, want = _both(wire)
    assert path == "native"
    assert isinstance(got, Commit) and got == want
    assert got.to_proto() == want.to_proto()
    # the writer always writes field 3, so these two do not round-trip
    if shape not in ("no_timestamp_message", "every_field_omitted"):
        assert got.to_proto() == wire


@needs_native
@pytest.mark.parametrize(
    "head",
    [b"", b"\x08\x07", b"\x10\x01", HEAD[4:], b"\x08\x07\x10\x01", HEAD],
    ids=["empty", "height", "round", "block_id", "height_round", "all"],
)
def test_commit_decode_head_fields_stay_with_the_generic_loop(head):
    for entries in ([], [_entry()], [_entry(), b"\x08\x01\x1a\x00"]):
        got, path, want = _both(_wire(entries, head))
        assert path == "native" and got == want
        assert len(got.signatures) == len(entries)


_GOOD = _entry()
NON_CANONICAL = {
    # one entry's shape
    "fields_out_of_order": _field(0x12, b"\xaa" * 20) + b"\x08\x02" + _field(0x22, b"\x55" * 64),
    "signature_before_timestamp": b"\x08\x02" + _field(0x22, b"\x55" * 64) + _field(0x1A, _ts(5, 5)),
    "repeated_flag": b"\x08\x02\x08\x03" + _GOOD[2:],
    "repeated_address": b"\x08\x02" + _field(0x12, b"\x01" * 20) + _GOOD[2:],
    "repeated_signature": _GOOD + _field(0x22, b"\x66" * 64),
    "unknown_field": _GOOD + b"\x28\x01",
    "unknown_bytes_field": _GOOD + _field(0x32, b"zz"),
    "two_byte_flag": b"\x08\x82\x01" + _GOOD[2:],
    "overlong_flag": b"\x08\x82\x00" + _GOOD[2:],
    "explicit_zero_flag": b"\x08\x00" + _GOOD[2:],
    "empty_address_written": b"\x08\x02\x12\x00" + _GOOD[24:],
    "empty_signature_written": _GOOD[:-66] + b"\x22\x00",
    "overlong_length": b"\x08\x02\x12\x94\x00" + b"\xaa" * 20 + _GOOD[24:],
    "varint_where_address_belongs": b"\x08\x02\x10\x05" + _GOOD[24:],
    "varint_where_timestamp_belongs": _entry(ts=None, sig=b"") + b"\x18\x05" + _field(0x22, b"\x55" * 64),
    "varint_where_signature_belongs": _entry(sig=b"") + b"\x20\x05",
    "bytes_where_flag_belongs": _field(0x0A, b"\x02") + _GOOD[2:],
    "fixed64_field": _GOOD + b"\x29" + b"\x01" * 8,
    "fixed32_field": _GOOD + b"\x2d" + b"\x01" * 4,
    "group_wire_type": _GOOD + b"\x2b",
    "timestamp_fields_out_of_order": _entry(ts=b"\x10\x05\x08\x05"),
    "timestamp_repeated_seconds": _entry(ts=b"\x08\x05\x08\x06"),
    "timestamp_unknown_field": _entry(ts=_ts(5, 5) + b"\x18\x01"),
    "timestamp_bytes_seconds": _entry(ts=_field(0x0A, b"\x05")),
    "timestamp_explicit_zero_seconds": _entry(ts=b"\x08\x00"),
    "timestamp_nanos_a_whole_second": _entry(ts=_ts(5, 10**9)),
    "timestamp_negative_nanos": _entry(ts=b"\x08\x05\x10" + encode_varint(-7)),
    "timestamp_total_past_int64": _entry(ts=_ts(9_223_372_036, 854_775_808)),
    "timestamp_total_below_int64": _entry(ts=_ts(-9_223_372_037, 145_224_191)),
    "timestamp_seconds_past_int64_times_ns": _entry(ts=_ts(2**62, 0)),
    "timestamp_overlong_seconds": _entry(ts=b"\x08\x85\x00"),
    "timestamp_eleven_byte_varint": _entry(ts=b"\x08" + b"\xff" * 10 + b"\x01"),
    "timestamp_varint_past_64_bits": _entry(ts=b"\x08" + b"\xff" * 9 + b"\x02"),
    "timestamp_truncated_inside": _entry(ts=b"\x08\x85"),
    "address_longer_than_the_entry": b"\x08\x02\x12\x7f" + b"\xaa" * 20,
    "signature_length_past_63_bits": _entry(sig=b"") + b"\x22" + encode_varint(2**63 + 5) + b"\x55",
}


@needs_native
@pytest.mark.parametrize("shape", sorted(NON_CANONICAL))
def test_commit_decode_non_canonical_entry_takes_the_generic_path(shape):
    """One odd entry sends the whole commit through the generic
    decoder: its result, or its exact exception."""
    wire = _wire([_entry(), NON_CANONICAL[shape], _entry(flag=3)])
    got, path, want = _both(wire)
    assert path in ("generic", None)  # None: it raised inside the span
    assert got == want
    assert (path is None) == (not isinstance(want, Commit))


NON_CANONICAL_OUTER = {
    "height_after_the_entries": _wire([_entry()]) + b"\x08\x09",
    "entries_before_the_head": _field(0x22, _entry()) + HEAD,
    "round_before_height": b"\x10\x01\x08\x07" + HEAD[4:] + _field(0x22, _entry()),
    "repeated_height": b"\x08\x07\x08\x08" + HEAD[2:] + _field(0x22, _entry()),
    "unknown_outer_field": HEAD + b"\x28\x01" + _field(0x22, _entry()),
    "unknown_field_between_entries": _wire([_entry()]) + _field(0x32, b"x") + _field(0x22, _entry()),
    "entry_as_varint": HEAD + b"\x20\x05",
    "block_id_as_varint": b"\x08\x07\x18\x05" + _field(0x22, _entry()),
    "height_as_bytes": _field(0x0A, b"\x07") + _field(0x22, _entry()),
    "overlong_height": b"\x08\x87\x00" + _field(0x22, _entry()),
    "entry_length_past_the_end": HEAD + b"\x22\x7f" + _entry(),
    "trailing_tag": _wire([_entry()]) + b"\x22",
    "bad_block_id_then_bad_entry": b"\x1a\x02\x0a\x7f" + _field(0x22, b"\x08"),
}


@needs_native
@pytest.mark.parametrize("shape", sorted(NON_CANONICAL_OUTER))
def test_commit_decode_non_canonical_outer_message_takes_the_generic_path(shape):
    got, path, want = _both(NON_CANONICAL_OUTER[shape])
    assert path in ("generic", None)
    assert got == want
    assert (path is None) == (not isinstance(want, Commit))


@needs_native
def test_commit_decode_head_error_is_the_generic_decoders():
    """A head the generic loop rejects, before entries the scan
    accepts: the error is raised from the same loop, unchanged."""
    wire = b"\x1a\x02\x0a\x7f" + _field(0x22, _entry())
    got, path, want = _both(wire)
    assert path is None and got == want
    assert got[0] is ValueError


@needs_native
def test_commit_decode_every_truncation_of_an_entry():
    """Each cut through the last entry, and through the head: the
    generic decoder's Commit or its exact error, never a native
    result built from a partial entry."""
    wire = _wire([_entry(), _entry(flag=3, ts=_ts(-1, 999_999_999))])
    native_cuts = 0
    for cut in range(len(wire) + 1):
        got, path, want = _both(wire[:cut])
        assert got == want, cut
        native_cuts += path == "native"
    # whole messages only: nothing, each head prefix that ends on a
    # field boundary, head + one entry, head + both
    assert native_cuts == 6


@needs_native
@pytest.mark.parametrize("seed", range(4))
def test_commit_decode_seeded_mutations_match_the_generic_decoder(seed):
    """Byte flips, splices and truncations of a small mixed commit:
    both paths give the same Commit or the same error, whichever path
    the bytes select."""
    rng = random.Random(0xC0441 + seed)
    golden = _mixed_commit(5, seed).to_proto()
    paths = {"native": 0, "generic": 0, None: 0}
    for _ in range(600):
        b = bytearray(golden)
        for _ in range(rng.randrange(1, 4)):
            op = rng.randrange(4)
            at = rng.randrange(len(b))
            if op == 0:
                b[at] = rng.randrange(256)
            elif op == 1:
                b[at] ^= 1 << rng.randrange(8)
            elif op == 2:
                del b[at : at + rng.randrange(1, 4)]
            else:
                b[at:at] = rng.randbytes(rng.randrange(1, 4))
            if not b:
                b = bytearray(b"\x22")
        got, path, want = _both(bytes(b))
        assert got == want, bytes(b).hex()
        if isinstance(got, Commit):
            assert got.to_proto() == want.to_proto()
        paths[path] += 1
    # the sweep must exercise all three: a flipped signature byte stays
    # native, a flipped tag goes generic, a flipped length raises
    assert all(paths.values()), paths


@pytest.mark.parametrize("n", [0, 4, 150])
def test_commit_decode_with_the_library_unavailable(n):
    """TM_TPU_NO_NATIVE or no compiler: every commit decodes through
    the generic loop, says so, and round-trips."""
    commit = _mixed_commit(n)
    wire = commit.to_proto()
    with _library_unavailable():
        assert native.commit_scan(wire) is None
        got, path = _outcome(wire)
    assert path == "generic"
    assert got == commit and got.to_proto() == wire and got.hash() == commit.hash()


@needs_native
@pytest.mark.parametrize("kind", [bytearray, memoryview])
def test_commit_decode_of_a_buffer_that_is_not_bytes_is_generic(kind):
    wire = _mixed_commit(4).to_proto()
    got, path = _outcome(kind(wire))
    assert path == "generic"
    assert got == Commit.from_proto(wire)


@needs_native
@pytest.mark.parametrize(
    "field,value",
    [
        ("block_id_flag", 3),
        ("validator_address", b"\x09" * 20),
        ("timestamp_ns", 42),
        ("signature", b"\x09" * 64),
    ],
)
def test_reassigning_a_natively_built_commit_sig_invalidates_memos(field, value):
    wire = _mixed_commit(4).to_proto()
    commit, path = _outcome(wire)
    assert path == "native"
    cs = commit.signatures[0]
    # the fields live where the dataclass __init__ puts them
    assert list(cs.__dict__) == list(CommitSig().__dict__)
    before_hash = commit.hash()
    before_rows = list(commit.sign_bytes_batch(CHAIN_ID))
    token = commit.fingerprint_token()
    epoch = commit_mod._MUT_EPOCH[0]
    setattr(cs, field, value)
    assert commit_mod._MUT_EPOCH[0] is not epoch
    assert commit.fingerprint_token() is not token
    assert commit.hash() != before_hash
    if field in ("block_id_flag", "timestamp_ns"):
        assert list(commit.sign_bytes_batch(CHAIN_ID)) != before_rows
    assert commit.hash() == Commit.from_proto(commit.to_proto()).hash()


@needs_native
def test_commit_scan_columns_are_sized_by_the_input_not_by_its_length_fields():
    """The fuzzers' over-allocation levers: a length field that claims
    2**40 bytes, and as many two-byte entries as fit."""
    assert native.commit_scan(b"\x22" + encode_varint(1 << 40) + b"\x08\x02") is None
    head_end, cols = native.commit_scan(b"\x22\x00" * 1000)
    assert head_end == 0 and [len(c) for c in cols] == [1000] * 6
    assert Commit.from_proto(b"\x22\x00" * 1000).signatures == [
        CommitSig(block_id_flag=0)
    ] * 1000
    assert native.commit_scan(b"") == (0, [[]] * 6)
