"""Seeded data for the light-client cells: one chain of signed headers
at consecutive heights, cut into the segments a sync walks, each header
as LightBlock wire bytes, and corrupted variants.

Everything is a function of the configuration, the traffic file and
`--seed`, made with the plain reference's encoders and hashes
(reference/commit_verify.py, reference/light_verify.py), gen.py's keys
and signers — none of it by the program under test.

A header is a real one: chain id, height, time (BASE_TIME_S plus one
second a height), the block id of the header before it, the validator
set's hash as `validators_hash` and `next_validators_hash` (a static
set), a proposer from the set, its other hashes seeded. Its block id is
its own hash, and its commit is signed over that by every validator,
with gen.py's one sign-bytes length.

Segment `s` of `hops` headers is the chain's headers `s * hops` to
`(s + 1) * hops`: a sync trusts the first and verifies up to the last,
so neighbouring segments share one header, which the earlier verifies
and the later only trusts.
"""

from __future__ import annotations

import hashlib

import numpy as np

from chipbench import gen
from chipbench.reference import commit_verify as R
from chipbench.reference import light_verify as L


def _material(tag: str, seed: int, i: int) -> bytes:
    return hashlib.sha256(b"chipbench-light|%s|%d|%d" % (tag.encode(), seed, i)).digest()


# -- tendermint.types.LightBlock on the wire ---------------------------


def encode_header(h: dict) -> bytes:
    """tendermint.types.Header."""
    return (
        R.f_bytes(1, R.f_varint(1, h["version_block"]) + R.f_varint(2, h["version_app"]))
        + R.f_bytes(2, h["chain_id"].encode())
        + R.f_varint(3, h["height"])
        + R.f_bytes(4, R.timestamp(h["time_ns"]))
        + R.f_bytes(
            5, R.block_id_body(h["last_block_hash"], h["last_parts_total"], h["last_parts_hash"])
        )
        + R.f_bytes(6, h["last_commit_hash"])
        + R.f_bytes(7, h["data_hash"])
        + R.f_bytes(8, h["validators_hash"])
        + R.f_bytes(9, h["next_validators_hash"])
        + R.f_bytes(10, h["consensus_hash"])
        + R.f_bytes(11, h["app_hash"])
        + R.f_bytes(12, h["last_results_hash"])
        + R.f_bytes(13, h["evidence_hash"])
        + R.f_bytes(14, h["proposer_address"])
    )


def encode_validator(v: dict) -> bytes:
    """tendermint.types.Validator, its proposer priority zero."""
    return (
        R.f_bytes(1, v["address"])
        + R.f_bytes(2, L.public_key(v["kind"], v["pub"]))
        + R.f_varint(3, v["power"])
    )


def encode_validator_set(validators: list) -> bytes:
    """tendermint.types.ValidatorSet: with every priority zero the
    proposer is the first of the set."""
    encoded = [encode_validator(v) for v in validators]
    return (
        b"".join(R.f_bytes(1, e) for e in encoded)
        + R.f_bytes(2, encoded[0])
        + R.f_varint(3, sum(v["power"] for v in validators))
    )


def encode_light_block(block: dict, validator_set: bytes) -> bytes:
    """tendermint.types.LightBlock: a SignedHeader and the set."""
    signed = R.f_bytes(1, encode_header(block["header"])) + R.f_bytes(
        2, gen.encode_commit(block["commit"])
    )
    return R.f_bytes(1, signed) + R.f_bytes(2, validator_set)


# -- the chain ---------------------------------------------------------


def make_headers(chain_id: str, validators: list, seed: int, count: int) -> list:
    """`count` chained headers from BASE_HEIGHT, each with its hash."""
    set_hash = L.validators_hash(validators)
    headers = []
    last_hash, last_parts = _material("genesis", seed, 0), _material("parts", seed, -1)
    for n in range(count):
        header = {
            "version_block": 11,
            "version_app": 0,
            "chain_id": chain_id,
            "height": gen.BASE_HEIGHT + n,
            "time_ns": (gen.BASE_TIME_S + n) * 10**9,
            "last_block_hash": last_hash,
            "last_parts_total": 1,
            "last_parts_hash": last_parts,
            "last_commit_hash": _material("last_commit", seed, n),
            "data_hash": _material("data", seed, n),
            "validators_hash": set_hash,
            "next_validators_hash": set_hash,
            "consensus_hash": _material("consensus", seed, 0),
            "app_hash": _material("app", seed, n),
            "last_results_hash": _material("results", seed, n),
            "evidence_hash": _material("evidence", seed, n),
            "proposer_address": validators[n % len(validators)]["address"],
        }
        header["hash"] = last_hash = L.header_hash(header)
        last_parts = _material("parts", seed, n)
        headers.append(header)
    return headers


def sign_headers(chain_id: str, keys: list, validators: list, seed: int,
                 headers: list, nanos) -> tuple:
    """(commits, sign-bytes lengths met): a commit for each header,
    over its own hash, signed by every validator (gen.sign_commits,
    with the block id the header's)."""
    commits, jobs, lens = [], {}, set()
    for n, header in enumerate(headers):
        commit = {
            "height": header["height"],
            "round": 0,
            "block_hash": header["hash"],
            "parts_total": 1,
            "parts_hash": _material("parts", seed, n),
            "votes": [],
        }
        parts = R.sign_bytes_parts(chain_id, commit)
        for key, val, ns in zip(keys, validators, nanos[n]):
            vote = {
                "flag": R.FLAG_COMMIT,
                "address": val["address"],
                "time_ns": header["time_ns"] + int(ns),
            }
            commit["votes"].append(vote)
            jobs.setdefault(type(key), []).append(
                (vote, key, R.sign_bytes(parts, vote["time_ns"]))
            )
        commits.append(commit)
    for cls, todo in jobs.items():
        lens.update(len(msg) for _vote, _key, msg in todo)
        sigs = cls.sign_all([(key, msg) for _vote, key, msg in todo])
        for (vote, _key, _msg), sig in zip(todo, sigs):
            vote["sig"] = sig
    return commits, lens


class Chain:
    """The headers a cell's syncs walk, and which syncs meet a
    corrupted one.

    `ring_segments` segments of `headers_per_sync` hops, then
    `warmup_segments` more that only set-up's warm-up touches. Request
    `i` syncs over segment `i % ring_segments`; within every block of
    `corrupt_every` requests the one at a seeded offset (drawn from
    `first_corrupted_in`, never 0: the first request of a window is a
    clean one) meets that segment's corrupted variant: one header at a
    seeded hop with one signature bit of a seeded vote below the light
    quorum flipped. The warm-up syncs once over each of its segments,
    the first clean and the last corrupted, so the warm-up's corrupted
    sync is as cold as the window's."""

    def __init__(self, config: dict, traffic: dict, seed: int) -> None:
        self.chain_id = config["chain_id"]
        self.hops = config["headers_per_sync"]
        self.keys, self.validators = gen.make_validators(config, seed)
        self.n_ring = traffic["ring_segments"]
        self.n_warm = traffic["warmup_segments"]
        self.corrupt_every = traffic["corrupt_every"]
        self.checked = gen.light_quorum(self.validators)
        rng = np.random.default_rng(seed)
        segments = self.n_ring + self.n_warm
        count = segments * self.hops + 1
        nanos = rng.integers(gen.NANOS_LO, gen.NANOS_HI, size=(count, len(self.validators)))
        lo, hi = traffic["first_corrupted_in"]
        self.corrupt_offset = int(rng.integers(lo, hi + 1))
        if not 0 < self.corrupt_offset < self.corrupt_every:
            raise RuntimeError("the first request of a window must be a clean one")
        # the hop (1..hops) and the vote of each segment's corrupted variant
        self.bad_hop = rng.integers(1, self.hops + 1, size=segments)
        self.bad_index = rng.integers(0, self.checked, size=segments)
        headers = make_headers(self.chain_id, self.validators, seed, count)
        commits, lens = sign_headers(
            self.chain_id, self.keys, self.validators, seed, headers, nanos
        )
        if len(lens) != 1:
            raise RuntimeError(f"sign-bytes lengths differ: {sorted(lens)}")
        self.sign_bytes_len = lens.pop()
        self.blocks = [
            {"header": h, "commit": c, "validators": self.validators}
            for h, c in zip(headers, commits)
        ]
        self._set_wire = encode_validator_set(self.validators)
        self.wire = [encode_light_block(b, self._set_wire) for b in self.blocks]
        self.now_ns = headers[-1]["time_ns"] + 60 * 10**9
        # every corrupted variant a window or the warm-up can ask for,
        # built now: none is made inside a timed request
        period = self.n_ring * self.corrupt_every
        wanted = {self.segment(i) for i in range(period) if self.is_corrupted(i)}
        wanted.add(segments - 1)
        self._bad = {}
        for s in sorted(wanted):
            at = s * self.hops + int(self.bad_hop[s])
            block = dict(
                self.blocks[at],
                commit=gen.corrupted(self.blocks[at]["commit"], int(self.bad_index[s])),
            )
            self._bad[s] = (at, block, encode_light_block(block, self._set_wire))

    def is_corrupted(self, i: int) -> bool:
        return i % self.corrupt_every == self.corrupt_offset

    def segment(self, i: int) -> int:
        """Ring segment of window request `i`; warm-up request `j`
        syncs over segment `n_ring + j`."""
        return i % self.n_ring

    def span(self, segment: int) -> tuple:
        """(first, last) place in the chain of a segment's headers."""
        return segment * self.hops, (segment + 1) * self.hops

    def bad_variant(self, segment: int) -> tuple:
        """(place in the chain, block, wire bytes) of the one header a
        segment's corrupted variant replaces."""
        return self._bad[segment]

    def segment_blocks(self, segment: int, bad: bool) -> list:
        """The root and every header up to the target, for the
        reference."""
        first, last = self.span(segment)
        blocks = self.blocks[first : last + 1]
        if bad:
            at, block, _wire = self._bad[segment]
            blocks[at - first] = block
        return blocks
