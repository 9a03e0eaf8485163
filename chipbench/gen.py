"""Seeded data for the commit-verification cells: validators, signed
commits at consecutive heights, their wire bytes, corrupted variants.

Everything is a function of the configuration, the traffic file and
`--seed`, made with the plain reference's encoders and OpenSSL keys —
none of it by the program under test — so the reference and the program
are handed the same bytes and neither is handed the other's work.

One sign-bytes length for the whole ring: heights are sfixed64, a
block's time is BASE_TIME_S plus whole seconds (five varint bytes) and
every vote's nanos lie in [2**28, 10**9) (five varint bytes). Each
further length would compile its own SHA-512 program on the device.
"""

from __future__ import annotations

import hashlib

import numpy as np

from chipbench import pool
from chipbench.reference import commit_verify as R

BASE_TIME_S = 1_700_000_000
BASE_HEIGHT = 1_000_000
NANOS_LO, NANOS_HI = 1 << 28, 10**9


def _material(tag: str, seed: int, i: int) -> bytes:
    return hashlib.sha256(b"chipbench|%s|%d|%d" % (tag.encode(), seed, i)).digest()


class _Ed25519Key:
    kind = "ed25519"

    def __init__(self, material: bytes) -> None:
        from cryptography.hazmat.primitives import serialization as ser
        from cryptography.hazmat.primitives.asymmetric.ed25519 import (
            Ed25519PrivateKey,
        )

        self._key = Ed25519PrivateKey.from_private_bytes(material)
        self.pub = self._key.public_key().public_bytes(
            ser.Encoding.Raw, ser.PublicFormat.Raw
        )

    @staticmethod
    def sign_all(jobs: list) -> list:
        """A signature for each (key, message)."""
        return [key._key.sign(msg) for key, msg in jobs]


class _Sr25519Key:
    """A schnorrkel key of the plain reference's own sr25519. Public
    keys and signatures are Python-integer arithmetic, made in bulk
    over worker processes (chipbench/pool.py)."""

    kind = "sr25519"

    def __init__(self, material: bytes) -> None:
        from chipbench.reference import sr25519_plain

        self.secret = sr25519_plain.secret_scalar(material)
        self.pub = b""  # filled by derive_all

    @staticmethod
    def derive_all(keys: list) -> None:
        from chipbench.reference import sr25519_plain

        pubs = pool.map_chunks(sr25519_plain.public_keys, [k.secret for k in keys], 256)
        for key, pub in zip(keys, pubs):
            key.pub = pub

    @staticmethod
    def sign_all(jobs: list) -> list:
        from chipbench.reference import sr25519_plain

        return pool.map_chunks(
            sr25519_plain.sign_jobs, [(k.secret, k.pub, msg) for k, msg in jobs], 512
        )


KEY_CLASSES = {"ed25519": _Ed25519Key, "sr25519": _Sr25519Key}


def make_validators(config: dict, seed: int) -> tuple:
    """(keys, validators) in validator-set order: equal power, so the
    set sorts by address (sha256(pub)[:20], upstream crypto.Address)."""
    kinds = config["key_classes"]
    keys = [
        KEY_CLASSES[kinds[i % len(kinds)]](_material(config["name"], seed, i))
        for i in range(config["validators"])
    ]
    for cls in KEY_CLASSES.values():
        mine = [k for k in keys if isinstance(k, cls)]
        if mine and hasattr(cls, "derive_all"):
            cls.derive_all(mine)
    keys.sort(key=lambda k: hashlib.sha256(k.pub).digest()[:20])
    validators = [
        {
            "kind": k.kind,
            "pub": k.pub,
            "address": hashlib.sha256(k.pub).digest()[:20],
            "power": config["voting_power"],
        }
        for k in keys
    ]
    return keys, validators


def sign_commits(chain_id: str, keys: list, validators: list, seed: int, nanos) -> list:
    """Commits 0..len(nanos)-1 of a seed's chain, each signed by every
    validator; `nanos[n][i]` places validator i's vote within block
    n's second. Signing is a key class at a time over all the commits."""
    commits, jobs = [], {}
    for n in range(len(nanos)):
        commit = {
            "height": BASE_HEIGHT + n,
            "round": 0,
            "block_hash": _material("block", seed, n),
            "parts_total": 1,
            "parts_hash": _material("parts", seed, n),
            "votes": [],
        }
        parts = R.sign_bytes_parts(chain_id, commit)
        block_ns = (BASE_TIME_S + n) * 10**9
        for key, val, ns in zip(keys, validators, nanos[n]):
            vote = {
                "flag": R.FLAG_COMMIT,
                "address": val["address"],
                "time_ns": block_ns + int(ns),
            }
            commit["votes"].append(vote)
            jobs.setdefault(type(key), []).append(
                (vote, key, R.sign_bytes(parts, vote["time_ns"]))
            )
        commits.append(commit)
    for cls, todo in jobs.items():
        sigs = cls.sign_all([(key, msg) for _vote, key, msg in todo])
        for (vote, _key, _msg), sig in zip(todo, sigs):
            vote["sig"] = sig
    return commits


def corrupted(commit: dict, idx: int) -> dict:
    """The same commit with signature `idx` flipped in one bit."""
    votes = list(commit["votes"])
    sig = votes[idx]["sig"]
    votes[idx] = dict(votes[idx], sig=bytes([sig[0] ^ 0x01]) + sig[1:])
    return dict(commit, votes=votes)


def encode_commit(commit: dict) -> bytes:
    """tendermint.types.Commit on the wire."""
    out = [
        R.f_varint(1, commit["height"]),
        R.f_varint(2, commit["round"]),
        R.f_bytes(
            3,
            R.block_id_body(
                commit["block_hash"], commit["parts_total"], commit["parts_hash"]
            ),
        ),
    ]
    for v in commit["votes"]:
        out.append(
            R.f_bytes(
                4,
                R.f_varint(1, v["flag"])
                + R.f_bytes(2, v["address"])
                + R.f_bytes(3, R.timestamp(v["time_ns"]))
                + R.f_bytes(4, v["sig"]),
            )
        )
    return b"".join(out)


def light_quorum(validators: list) -> int:
    """Votes VerifyCommitLight checks when every validator signs."""
    needed = sum(v["power"] for v in validators) * 2 // 3
    tallied = 0
    for i, v in enumerate(validators):
        tallied += v["power"]
        if tallied > needed:
            return i + 1
    return len(validators)


class Ring:
    """The commits a cell's requests walk, and which requests carry a
    corrupted one.

    `ring_commits` distinct commits at consecutive heights, then
    `warmup_commits` more that only set-up's warm-up touches (so the
    window's first lap finds nothing in the program's caches). Request
    `i` takes commit `i % ring_commits`; within every block of
    `corrupt_every` requests one, at a seeded offset, takes that
    commit's corrupted variant, whose bad index is seeded below
    `corrupt_below` (the votes the entry checks). `corrupt_every` is a
    multiple or a divisor of nothing in particular: the schedule is by
    request, the same count for every seed.
    """

    def __init__(self, config: dict, traffic: dict, seed: int, light: bool) -> None:
        self.chain_id = config["chain_id"]
        self.light = light
        self.keys, self.validators = make_validators(config, seed)
        n_vals = len(self.validators)
        self.n_ring = traffic["ring_commits"]
        self.n_warm = traffic["warmup_commits"]
        self.corrupt_every = traffic["corrupt_every"]
        rng = np.random.default_rng(seed)
        total = self.n_ring + self.n_warm
        nanos = rng.integers(NANOS_LO, NANOS_HI, size=(total, n_vals))
        self.checked = light_quorum(self.validators) if light else n_vals
        self.bad_index = rng.integers(0, self.checked, size=total)
        self.corrupt_offset = int(rng.integers(0, self.corrupt_every))
        self.commits = sign_commits(self.chain_id, self.keys, self.validators, seed, nanos)
        self.wire = [encode_commit(c) for c in self.commits]
        lens = self.sign_bytes_lengths()
        if len(lens) != 1:
            raise RuntimeError(f"sign-bytes lengths differ: {sorted(lens)}")
        self.sign_bytes_len = lens.pop()
        # every corrupted variant a window or the warm-up can ask for,
        # built now: none is made inside a timed request
        period = self.n_ring * self.corrupt_every
        slots = {self.slot(i) for i in range(period) if self.is_corrupted(i)}
        slots.update(range(self.n_ring, total))
        self._bad = {}
        for slot in sorted(slots):
            c = corrupted(self.commits[slot], int(self.bad_index[slot]))
            self._bad[slot] = (c, encode_commit(c))

    def is_corrupted(self, i: int) -> bool:
        return i % self.corrupt_every == self.corrupt_offset

    def slot(self, i: int) -> int:
        """Ring index of window request `i`; warm-up request `j` is
        slot `n_ring + j`."""
        return i % self.n_ring

    def bad_variant(self, slot: int) -> tuple:
        """(commit, wire bytes) of a slot's corrupted variant."""
        return self._bad[slot]

    def sign_bytes_lengths(self) -> set:
        lens = set()
        for c in self.commits:
            parts = R.sign_bytes_parts(self.chain_id, c)
            lens.update(len(R.sign_bytes(parts, v["time_ns"])) for v in c["votes"])
        return lens
