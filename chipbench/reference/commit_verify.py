"""The plain reference of commit verification (upstream Tendermint
v0.35 `types/validation.go`: VerifyCommit and VerifyCommitLight).

It imports nothing of the program under test and takes nothing the
program made: sign-bytes are encoded here from the protobuf schema
(`proto/tendermint/types/canonical.proto`, CanonicalVote), ed25519
signatures are checked by OpenSSL through the `cryptography` package,
and the tally is a loop. The harness compares the program's verdict of
every request of the window with `verdict()` of the same data.

A verdict is a short string:
  "ok"                  +2/3 of the voting power signed this block id
                        and every signature the entry checks is valid
  "wrong_signature#<i>" the lowest commit index whose signature fails
  "not_enough_power"    the for-block votes carry 2/3 or less
"""

from __future__ import annotations

import struct

PRECOMMIT_TYPE = 2
FLAG_COMMIT = 2  # BlockIDFlagCommit


def varint(n: int) -> bytes:
    out = bytearray()
    while n > 0x7F:
        out.append((n & 0x7F) | 0x80)
        n >>= 7
    out.append(n)
    return bytes(out)


def f_varint(field: int, v: int) -> bytes:
    """proto3: a zero scalar is not written."""
    return varint(field << 3) + varint(v) if v else b""


def f_bytes(field: int, b: bytes) -> bytes:
    return varint(field << 3 | 2) + varint(len(b)) + b


def f_sfixed64(field: int, v: int) -> bytes:
    return varint(field << 3 | 1) + struct.pack("<q", v) if v else b""


def timestamp(ns: int) -> bytes:
    seconds, nanos = divmod(ns, 10**9)
    return f_varint(1, seconds) + f_varint(2, nanos)


def block_id_body(block_hash: bytes, parts_total: int, parts_hash: bytes) -> bytes:
    """BlockID and CanonicalBlockID share this layout."""
    psh = f_varint(1, parts_total) + f_bytes(2, parts_hash)
    return f_bytes(1, block_hash) + f_bytes(2, psh)


def sign_bytes_parts(chain_id: str, commit: dict) -> tuple:
    """(prefix, suffix) around the timestamp field: within one commit
    only the timestamp differs from vote to vote."""
    prefix = (
        f_varint(1, PRECOMMIT_TYPE)
        + f_sfixed64(2, commit["height"])
        + f_sfixed64(3, commit["round"])
        + f_bytes(
            4,
            block_id_body(
                commit["block_hash"],
                commit["parts_total"],
                commit["parts_hash"],
            ),
        )
    )
    return prefix, f_bytes(6, chain_id.encode())


def sign_bytes(parts: tuple, time_ns: int) -> bytes:
    """The length-prefixed CanonicalVote a validator signs."""
    prefix, suffix = parts
    body = prefix + f_bytes(5, timestamp(time_ns)) + suffix
    return varint(len(body)) + body


def _verify_ed25519(triples: list) -> list:
    from cryptography.exceptions import InvalidSignature
    from cryptography.hazmat.primitives.asymmetric.ed25519 import (
        Ed25519PublicKey,
    )

    out = []
    for pub, msg, sig in triples:
        try:
            Ed25519PublicKey.from_public_bytes(pub).verify(sig, msg)
            out.append(True)
        except (InvalidSignature, ValueError):
            out.append(False)
    return out


def _verify_sr25519(triples: list) -> list:
    from chipbench import pool
    from chipbench.reference import sr25519_plain

    return pool.map_chunks(sr25519_plain.verify_many, triples, 640)


# key class -> verify([(pub, msg, sig)]) -> [bool]; a configuration with
# another key class brings its plain verifier as a file and names it here
VERIFIERS = {"ed25519": _verify_ed25519, "sr25519": _verify_sr25519}


class Reference:
    """Verdicts for one deployment (chain id, validators in set order,
    each `{"kind", "pub", "power"}`). Signature checks are memoised by
    content, so a ring's corrupted variant of a commit costs one check,
    and a commit's unseen signatures are checked a key class at a time."""

    def __init__(self, chain_id: str, validators: list) -> None:
        self.chain_id = chain_id
        self.validators = validators
        self.total_power = sum(v["power"] for v in validators)
        self._seen: dict = {}

    def _check(self, kinds: list, triples: list) -> list:
        """[bool] for triples whose key classes are `kinds`."""
        unseen: dict = {}
        for kind, triple in zip(kinds, triples):
            if triple not in self._seen:
                unseen.setdefault(kind, []).append(triple)
        for kind, group in unseen.items():
            self._seen.update(zip(group, VERIFIERS[kind](group)))
        return [self._seen[t] for t in triples]

    def _tally(self, commit: dict, light: bool) -> tuple:
        """(enough power, indices of the votes the entry checks).
        `light`: stop after the vote that carries the tally past two
        thirds (VerifyCommitLight); otherwise every vote (VerifyCommit)."""
        needed = self.total_power * 2 // 3
        tallied = 0
        checked = []
        for idx, vote in enumerate(commit["votes"]):
            if vote["flag"] != FLAG_COMMIT:
                continue
            checked.append(idx)
            tallied += self.validators[idx]["power"]
            if light and tallied > needed:
                break
        return tallied > needed, checked

    def _triples(self, commit: dict, checked: list) -> tuple:
        parts = sign_bytes_parts(self.chain_id, commit)
        kinds, triples = [], []
        for idx in checked:
            vote, val = commit["votes"][idx], self.validators[idx]
            kinds.append(val["kind"])
            triples.append((val["pub"], sign_bytes(parts, vote["time_ns"]), vote["sig"]))
        return kinds, triples

    def prime(self, commits: list, light: bool) -> None:
        """Check the signatures of many commits in one go, so that a
        key class's verifier is started once for all of them."""
        kinds, triples = [], []
        for commit in commits:
            k, t = self._triples(commit, self._tally(commit, light)[1])
            kinds += k
            triples += t
        self._check(kinds, triples)

    def verdict(self, commit: dict, light: bool, check_signatures: bool = True) -> str:
        """The tally is judged before the signatures, as upstream's
        batch path does. `check_signatures=False` is the CONTROL
        (chipbench/control.py): the same code with the signature
        guarantee dropped, which the comparison must fail."""
        enough, checked = self._tally(commit, light)
        if not enough:
            return "not_enough_power"
        if not check_signatures:
            return "ok"
        kinds, triples = self._triples(commit, checked)
        for idx, valid in zip(checked, self._check(kinds, triples)):
            if not valid:
                return f"wrong_signature#{idx}"
        return "ok"
