"""A wrong signature in a merged window is named from the merged
bitmap: verify_triples_grouped says the lowest failing place,
verify_commit_light_bulk the commit and the vote, verify_adjacent_batch
the hop, and the light client's sequential window saves the hops before
it and raises that hop's own error — what the one-hop-at-a-time client
raises, with the same heights stored, no height fetched twice and no
cache hit.

CPU: the group affinity is pinned by hand so that the merged windows
run (an install decides it from the backend). Counts and verdicts,
never a speed.
"""

from __future__ import annotations

import copy

import pytest

from tendermint_tpu.crypto import sigcache
from tendermint_tpu.crypto.batch import (
    group_affinity_state,
    restore_group_affinity,
    set_group_affinity,
)
from tendermint_tpu.crypto.keys import PubKey
from tendermint_tpu.libs import trace
from tendermint_tpu.light.errors import InvalidHeaderError
from tendermint_tpu.light.verifier import verify_adjacent_batch
from tendermint_tpu.types import CommitSig
from tendermint_tpu.types.validation import (
    InvalidCommitError,
    verify_commit_light,
    verify_commit_light_bulk,
    verify_triples_grouped,
)

from .test_drain_classes import ED, SR, indexes_of, mixed_commit
from .test_light import CHAIN, HOUR_NS, DictProvider, build_chain, make_client, run
from .test_types import CHAIN_ID

WINDOW = 4  # hops a merged window
TOP = 1 + 2 * WINDOW  # trust root at 1, two whole windows above it
N_VALS = 150  # the light tally checks votes 0..100
PLACES = {"first": 0, "middle": 2, "last": WINDOW - 1}


@pytest.fixture(autouse=True)
def _fresh():
    sigcache.reset()
    yield
    sigcache.reset()
    trace.disable()
    trace.reset()


@pytest.fixture(scope="module")
def chain():
    return build_chain(TOP, seeds_at=lambda h: list(range(1, N_VALS + 1)))


def flipped(block, *indexes):
    """A copy of `block` with one bit of each of these votes'
    signatures flipped."""
    bad = copy.deepcopy(block)
    sigs = list(bad.signed_header.commit.signatures)
    for i in indexes:
        s = sigs[i]
        sigs[i] = CommitSig.for_block(
            s.signature[:-1] + bytes([s.signature[-1] ^ 1]),
            s.validator_address,
            s.timestamp_ns,
        )
    bad.signed_header.commit.signatures = sigs
    bad.signed_header.commit.invalidate_memos()
    return bad


class Counting(DictProvider):
    """Serves a chain and writes down every height it is asked for."""

    def __init__(self, blocks):
        super().__init__(blocks, "primary")
        self.asked: list = []

    async def light_block(self, height):
        self.asked.append(height)
        return await super().light_block(height)


def sync(blocks, affinity: int):
    """(error or None, heights stored, heights asked of the primary,
    cache hits) of one sequential sync to the chain's top."""
    sigcache.reset()
    state = group_affinity_state()
    set_group_affinity(affinity)
    try:
        client = make_client(blocks, sequential=True)
        client.primary = Counting(blocks)
        hits = sigcache.stats()["hits"]
        error = None
        try:
            run(client.verify_light_block_at_height(TOP))
        except Exception as e:  # noqa: BLE001 - the error is the result
            error = e
        return (
            error,
            client.store._heights(),
            client.primary.asked,
            sigcache.stats()["hits"] - hits,
        )
    finally:
        restore_group_affinity(state)


@pytest.mark.parametrize("vote", [0, 50, 100])
@pytest.mark.parametrize("window", [0, 1])
@pytest.mark.parametrize("place", sorted(PLACES))
def test_a_window_raises_the_per_hop_clients_error(chain, place, window, vote):
    bad_h = 2 + window * WINDOW + PLACES[place]
    blocks = dict(chain)
    blocks[bad_h] = flipped(chain[bad_h], vote)
    hop_err, hop_stored, _asked, _hits = sync(blocks, 1)
    err, stored, asked, hits = sync(blocks, WINDOW)
    signature = blocks[bad_h].signed_header.commit.signatures[vote].signature
    assert type(err) is type(hop_err) is InvalidHeaderError
    assert str(err) == str(hop_err) == f"wrong signature (#{vote}): {signature.hex()}"
    assert stored == hop_stored == list(range(1, bad_h))
    # the windows up to and including the bad one, each height once
    assert sorted(asked) == sorted(set(asked))
    assert max(asked) == TOP and bad_h in asked
    assert hits == 0


def test_a_clean_sync_stores_every_height_and_fetches_each_once(chain):
    err, stored, asked, hits = sync(chain, WINDOW)
    assert err is None and stored == list(range(1, TOP + 1))
    assert sorted(asked) == list(range(1, TOP + 1)) and hits == 0


def test_two_bad_signatures_in_a_window_name_the_lowest_hop_and_vote(chain):
    blocks = dict(chain)
    blocks[3] = flipped(chain[3], 70, 20)
    blocks[4] = flipped(chain[4], 5)
    err, stored, _asked, hits = sync(blocks, WINDOW)
    hop_err, hop_stored, _a, _h = sync(blocks, 1)
    assert str(err) == str(hop_err)
    assert str(err).startswith("wrong signature (#20): ")
    assert stored == hop_stored == [1, 2] and hits == 0


def forked_at(chain, height):
    """The chain's block at `height` from a fork whose validator set
    differs there: well-formed and signed, but not the set the block
    before it announced, so adjacent_header_checks refuses it."""
    seeds = list(range(1, N_VALS + 1))
    fork = build_chain(
        height,
        seeds_at=lambda h: seeds if h != height else seeds[:-1] + [N_VALS + 1],
        base_time_ns=chain[1].signed_header.header.time_ns - 1_000_000_000,
    )
    return fork[height]


@pytest.mark.parametrize("bad_before", [True, False])
def test_a_header_chain_fault_keeps_the_references_order(chain, bad_before):
    """A header-chain fault at hop j is found before any signature of
    the window is checked, so the window falls back to the per-hop
    loop: a bad signature at a hop before j is still the error raised,
    one after j is never reached."""
    blocks = dict(chain)
    blocks[4] = forked_at(chain, 4)
    if bad_before:
        blocks[3] = flipped(chain[3], 7)
    blocks[5] = flipped(chain[5], 9)
    err, stored, asked, _hits = sync(blocks, WINDOW)
    hop_err, hop_stored, _a, _h = sync(blocks, 1)
    assert type(err) is type(hop_err) is InvalidHeaderError
    assert str(err) == str(hop_err)
    if bad_before:
        assert str(err).startswith("wrong signature (#7): ")
        assert stored == hop_stored == [1, 2]
    else:
        assert "next_validators_hash" in str(err)
        assert stored == hop_stored == [1, 2, 3]
    # this is the path that still fetches a window twice
    assert asked.count(2) == 2


def _triples(vals, commit):
    return [
        (v.pub_key, commit.vote_sign_bytes(CHAIN_ID, i), commit.signatures[i].signature)
        for i, v in enumerate(vals.validators)
    ]


def _flip(triples, i):
    pk, sb, sig = triples[i]
    triples[i] = (pk, sb, sig[:3] + bytes([sig[3] ^ 1]) + sig[4:])


@pytest.mark.parametrize("first", [ED, SR])
def test_the_lowest_place_over_both_key_classes(first):
    """ed25519 is drained first whatever the merged order: the place
    named is the lowest of the merged list, not the first class's."""
    vals, _bid, commit, _privs = mixed_commit(first)
    triples = _triples(vals, commit)
    other = SR if first == ED else ED
    low, high = indexes_of(vals, first)[1], indexes_of(vals, other)[2]
    assert low < high
    _flip(triples, low)
    _flip(triples, high)
    with pytest.raises(InvalidCommitError) as caught:
        verify_triples_grouped(triples)
    assert caught.value.position == low
    assert f"(#{low})" in str(caught.value)
    # what was proven is cached, the two bad ones are not
    assert sigcache.entries() == len(triples) - 2


def test_a_mixed_sets_bulk_names_the_row_and_the_vote():
    vals, bid, commit, _privs = mixed_commit(SR)
    bad = copy.deepcopy(commit)
    for i in (indexes_of(vals, ED)[1], indexes_of(vals, SR)[1]):
        s = bad.signatures[i]
        bad.signatures[i] = CommitSig.for_block(
            bytes([s.signature[0] ^ 1]) + s.signature[1:], s.validator_address, s.timestamp_ns
        )
    bad.invalidate_memos()
    want = min(indexes_of(vals, ED)[1], indexes_of(vals, SR)[1])
    with pytest.raises(InvalidCommitError) as single:
        verify_commit_light(CHAIN_ID, vals, bid, 1, bad)
    sigcache.reset()
    with pytest.raises(InvalidCommitError) as bulk:
        verify_commit_light_bulk(CHAIN_ID, [(vals, bid, 1, commit), (vals, bid, 1, bad)])
    assert str(bulk.value) == str(single.value)
    assert (bulk.value.row, bulk.value.index) == (1, want)


class Unbatched(PubKey):
    """A key type no batch verifier is registered for (every key type
    in the tree has one today; an embedder's need not)."""

    def address(self):
        return b"\x00" * 20

    def bytes(self):
        return b"\x01" * 32

    def type(self):
        return "unbatched"

    def verify_signature(self, msg, sig):
        return sig == b"good"


def test_a_key_type_without_a_batch_verifier_keeps_the_unattributed_error():
    """Such a key is checked inline while routing and raises before the
    batched classes have answered: no place is named (a lower one may
    still be bad, as here), and the light client falls back hop by hop."""
    vals, _bid, commit, _privs = mixed_commit(ED)
    triples = _triples(vals, commit)
    triples.append((Unbatched(), b"a vote", b"bad"))
    _flip(triples, 0)
    with pytest.raises(InvalidCommitError, match="wrong signature in merged batch$") as caught:
        verify_triples_grouped(triples)
    assert caught.value.position is None
    triples[-1] = (Unbatched(), b"a vote", b"good")
    with pytest.raises(InvalidCommitError) as caught:
        verify_triples_grouped(triples)
    assert caught.value.position == 0


def _rows(blocks, heights):
    return [
        (
            blocks[h].validator_set,
            blocks[h].signed_header.commit.block_id,
            h,
            blocks[h].signed_header.commit,
        )
        for h in heights
    ]


def test_bulk_puts_the_row_on_the_error_and_memoizes_the_rows_before_it(chain):
    blocks = dict(chain)
    blocks[4] = flipped(chain[4], 33)
    rows = _rows(blocks, [2, 3, 4, 5])
    with pytest.raises(InvalidCommitError) as caught:
        verify_commit_light_bulk(CHAIN, rows)
    assert (caught.value.row, caught.value.index) == (2, 33)
    with pytest.raises(InvalidCommitError) as single:
        verify_commit_light(CHAIN, *rows[2])
    assert str(caught.value) == str(single.value)
    assert type(caught.value) is type(single.value) is InvalidCommitError
    # rows 0 and 1 are proven and memoized; the bad row and the one
    # after it are not, though every good signature of both is cached
    before = sigcache.stats()
    verify_commit_light_bulk(CHAIN, rows[:2])
    after = sigcache.stats()
    assert after["commit_hits"] - before["commit_hits"] == 2
    verify_commit_light_bulk(CHAIN, rows[3:])
    assert sigcache.stats()["commit_hits"] == after["commit_hits"]
    assert sigcache.stats()["commit_misses"] == after["commit_misses"] + 1


def test_a_warm_row_before_the_bad_one_still_counts_as_a_row(chain):
    """`row` is the place among the rows handed in, memo hits among
    them included."""
    blocks = dict(chain)
    blocks[4] = flipped(chain[4], 100)
    verify_commit_light_bulk(CHAIN, _rows(blocks, [2]))
    with pytest.raises(InvalidCommitError) as caught:
        verify_commit_light_bulk(CHAIN, _rows(blocks, [2, 3, 4]))
    assert (caught.value.row, caught.value.index) == (2, 100)


def test_verify_adjacent_batch_names_the_hop(chain):
    blocks = dict(chain)
    blocks[6] = flipped(chain[6], 1)
    now = chain[TOP].signed_header.header.time_ns + 1
    with pytest.raises(InvalidHeaderError) as caught:
        verify_adjacent_batch(
            CHAIN, chain[3].signed_header, [blocks[h] for h in range(4, 9)], 200 * HOUR_NS, now
        )
    assert caught.value.hop == 2
    assert str(caught.value).startswith("wrong signature (#1): ")
    # a header-chain fault names none: nothing is known of the signatures
    blocks[5] = forked_at(chain, 5)
    with pytest.raises(InvalidHeaderError) as caught:
        verify_adjacent_batch(
            CHAIN, chain[3].signed_header, [blocks[h] for h in range(4, 9)], 200 * HOUR_NS, now
        )
    assert caught.value.hop is None


def _spans(blocks):
    trace.reset()
    trace.enable()
    try:
        error, *_rest = sync(blocks, WINDOW)
    finally:
        trace.disable()
    return error, trace.snapshot()


def test_the_spans_of_a_sync(chain):
    error, spans = _spans(chain)
    assert error is None
    by_name: dict = {}
    for s in spans:
        by_name.setdefault(s.name, []).append(s)
    (root,) = by_name["light_sync"]
    assert root.attrs == {"from_height": 1, "to_height": TOP, "mode": "sequential"}
    assert [s.attrs["hops"] for s in by_name["light_window"]] == [WINDOW, WINDOW]
    assert [(s.attrs["first"], s.attrs["last"]) for s in by_name["light_window"]] == [(2, 5), (6, 9)]
    # the trust root (before the sync's span opens), the target, two windows
    fetches = [(s.attrs["first"], s.attrs["last"], s.attrs["bulk"]) for s in by_name["light_fetch"]]
    assert fetches == [(1, 1, False), (TOP, TOP, False), (2, 5, True), (6, 8, True)]
    assert all(s.root_id == root.span_id for s in by_name["light_fetch"][1:])
    # the client's validate_basic pass and the verifier's adjacent checks
    assert [s.attrs["hops"] for s in by_name["light_header_checks"]] == [WINDOW] * 4
    # the second window's last block is the target, saved last and alone
    assert [s.attrs["blocks"] for s in by_name["light_store_save"]] == [WINDOW, WINDOW - 1, 1]
    assert by_name["light_divergence"][0].attrs == {"witnesses": 0}
    assert "light_fallback" not in by_name
    windows = {s.span_id for s in by_name["light_window"]}
    assert all(s.parent_id in windows for s in by_name["verify_commit_light_bulk"])
    # a cold commit's memo probe, tally and sign-bytes, as verify_commit_light names them
    bulks = {s.span_id for s in by_name["verify_commit_light_bulk"]}
    plans = [s for s in by_name["commit_plan"] if s.parent_id in bulks]
    assert [s.attrs for s in plans] == [{"memo_hit": False}, {"processed": 101}] * (2 * WINDOW)
    encodes = [s for s in by_name["sign_bytes"] if s.parent_id in bulks]
    assert [s.attrs for s in encodes] == [{"rows": 101}] * (2 * WINDOW)


def test_the_spans_of_a_forged_sync(chain):
    blocks = dict(chain)
    blocks[7] = flipped(chain[7], 3)
    error, spans = _spans(blocks)
    assert isinstance(error, InvalidHeaderError)
    names = [s.name for s in spans]
    assert "light_fallback" not in names
    saves = [s.attrs["blocks"] for s in spans if s.name == "light_store_save"]
    assert saves == [WINDOW, 1]  # the first window, then height 6 alone
    (root,) = [s for s in spans if s.name == "light_sync"]
    assert root.attrs["error"] == "InvalidHeaderError"
    # a header-chain fault is what still falls back, and says so
    blocks = dict(chain)
    blocks[4] = forked_at(chain, 4)
    error, spans = _spans(blocks)
    (fallback,) = [s for s in spans if s.name == "light_fallback"]
    assert fallback.attrs["hops"] == WINDOW
    assert fallback.attrs["reason"] == "InvalidHeaderError"
