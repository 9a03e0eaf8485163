"""The plain reference of the light client's sequential header sync
(upstream Tendermint v0.35 `light/client.go` verifySequential,
`light/verifier.go` VerifyAdjacent, `types/light.go` ValidateBasic,
`types/block.go` Header.Hash, `types/validator_set.go` Hash).

It imports nothing of the program under test and takes nothing the
program made: the header hash and the validator-set hash are RFC 6962
merkle trees (`crypto/merkle/tree.go`) over field encodings written
here from the protobuf schema, with `hashlib`; the adjacent checks are
a loop; the commit of each hop goes through `commit_verify.Reference`
(VerifyCommitLight) as it stands.

A light block here is a dict: `header` (a dict of the 14 fields, see
`header_hash`), `commit` (commit_verify's commit dict) and `validators`
(the list `commit_verify.Reference` takes).

The verdict of a sync from a trusted root to a target, a short string:
  "ok:<height>:<header hash, hex>"      every hop verified; the target
  "wrong_signature:<height>#<idx>"      the first hop with a bad
                                        signature, and its lowest index
  "invalid:<height>:<what>"             any other failing check
each followed by ";stored=<first>-<last>", the heights the client's
store must then hold: the root and every hop verified before the stop.
"""

from __future__ import annotations

import hashlib

from chipbench.reference import commit_verify as R

PUBKEY_FIELD = {"ed25519": 1, "secp256k1": 2, "sr25519": 3}  # crypto/keys.proto


# -- RFC 6962 merkle (crypto/merkle/tree.go, hash.go) ------------------


def _sha256(data: bytes) -> bytes:
    return hashlib.sha256(data).digest()


def merkle_root(items: list) -> bytes:
    """HashFromByteSlices: leaves prefixed 0x00, inner nodes 0x01, the
    split at the largest power of two below the count."""
    n = len(items)
    if n == 0:
        return _sha256(b"")
    if n == 1:
        return _sha256(b"\x00" + items[0])
    k = 1 << ((n - 1).bit_length() - 1)
    return _sha256(b"\x01" + merkle_root(items[:k]) + merkle_root(items[k:]))


# -- the hashes --------------------------------------------------------


def header_hash(h: dict) -> bytes:
    """Header.Hash (types/block.go): the merkle root of the 14 fields
    in declaration order, each proto-encoded; a string, an integer or
    a byte string goes into a gogotypes wrapper message as its field 1
    (cdcEncode, types/encoding_helper.go)."""
    return merkle_root(
        [
            R.f_varint(1, h["version_block"]) + R.f_varint(2, h["version_app"]),
            R.f_bytes(1, h["chain_id"].encode()),
            R.f_varint(1, h["height"]),
            R.timestamp(h["time_ns"]),
            R.block_id_body(h["last_block_hash"], h["last_parts_total"], h["last_parts_hash"]),
            R.f_bytes(1, h["last_commit_hash"]),
            R.f_bytes(1, h["data_hash"]),
            R.f_bytes(1, h["validators_hash"]),
            R.f_bytes(1, h["next_validators_hash"]),
            R.f_bytes(1, h["consensus_hash"]),
            R.f_bytes(1, h["app_hash"]),
            R.f_bytes(1, h["last_results_hash"]),
            R.f_bytes(1, h["evidence_hash"]),
            R.f_bytes(1, h["proposer_address"]),
        ]
    )


def public_key(kind: str, pub: bytes) -> bytes:
    """tendermint.crypto.PublicKey: a oneof of the key classes."""
    return R.f_bytes(PUBKEY_FIELD[kind], pub)


def validators_hash(validators: list) -> bytes:
    """ValidatorSet.Hash: the merkle root of each validator's
    SimpleValidator (public key, voting power), in set order."""
    return merkle_root(
        [
            R.f_bytes(1, public_key(v["kind"], v["pub"])) + R.f_varint(2, v["power"])
            for v in validators
        ]
    )


# -- the sync ----------------------------------------------------------


class Reference:
    """Verdicts of sequential syncs for one deployment: chain id, the
    trusting period and the clock drift the client was configured with,
    and `now_ns`, the time every sync is judged at."""

    def __init__(self, chain_id: str, validators: list, trusting_period_ns: int,
                 max_clock_drift_ns: int, now_ns: int) -> None:
        self.chain_id = chain_id
        self.trusting_period_ns = trusting_period_ns
        self.max_clock_drift_ns = max_clock_drift_ns
        self.now_ns = now_ns
        self.commits = R.Reference(chain_id, validators)
        self._validators = validators
        self._hash = validators_hash(validators)

    def prime(self, blocks: list) -> None:
        """Check the signatures of many hops in one go."""
        self.commits.prime([b["commit"] for b in blocks], True)

    def _set_hash(self, validators: list) -> bytes:
        """A block of a static set carries the deployment's own list."""
        if validators is self._validators:
            return self._hash
        return validators_hash(validators)

    def _block_basic(self, block: dict):
        """LightBlock.ValidateBasic, as far as a dict can be wrong:
        the chain, the heights, the commit signing this header, the
        validator set the header names."""
        header, commit = block["header"], block["commit"]
        if header["chain_id"] != self.chain_id:
            return "other_chain"
        if header["height"] != commit["height"]:
            return "commit_height"
        if header_hash(header) != commit["block_hash"]:
            return "commit_signs_another_header"
        if len(commit["votes"]) != len(block["validators"]):
            return "set_size"
        if header["validators_hash"] != self._set_hash(block["validators"]):
            return "validators_hash"
        return None

    def _adjacent(self, trusted: dict, block: dict):
        """VerifyAdjacent less its commit check."""
        t, h = trusted["header"], block["header"]
        if h["height"] != t["height"] + 1:
            return "not_adjacent"
        if self.now_ns > t["time_ns"] + self.trusting_period_ns:
            return "trusted_header_expired"
        if h["time_ns"] <= t["time_ns"]:
            return "time_not_after_trusted"
        if h["time_ns"] >= self.now_ns + self.max_clock_drift_ns:
            return "time_from_the_future"
        if h["validators_hash"] != t["next_validators_hash"]:
            return "validators_hash_not_next_validators_hash"
        return None

    def verdict(self, blocks: list, trust_hash: bytes, check_signatures: bool = True) -> str:
        """`blocks`: the root and every header up to the target, at
        consecutive heights. `check_signatures=False` is the CONTROL
        (chipbench/control.py): the same code with the signature
        guarantee dropped, which the comparison must fail."""
        root = blocks[0]
        first = last = root["header"]["height"]

        def stop(what: str) -> str:
            return f"{what};stored={first}-{last}"

        if header_hash(root["header"]) != trust_hash or self._block_basic(root):
            return f"invalid:{first}:trust_root;stored="
        if self.now_ns > root["header"]["time_ns"] + self.trusting_period_ns:
            return f"invalid:{first}:trust_root_expired;stored="
        trusted = root
        for block in blocks[1:]:
            height = block["header"]["height"]
            wrong = self._block_basic(block) or self._adjacent(trusted, block)
            if wrong:
                return stop(f"invalid:{height}:{wrong}")
            found = self.commits.verdict(block["commit"], True, check_signatures)
            if found.startswith("wrong_signature#"):
                return stop(f"wrong_signature:{height}{found[len('wrong_signature'):]}")
            if found != "ok":
                return stop(f"invalid:{height}:{found}")
            trusted, last = block, height
        return stop(f"ok:{last}:{header_hash(trusted['header']).hex()}")
