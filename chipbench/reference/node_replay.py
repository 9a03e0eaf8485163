"""The plain reference of a node catching up by block sync (upstream
Tendermint v0.35 `internal/blocksync/reactor.go` poolRoutine and
`pool.go`, `internal/state/execution.go` ApplyBlock and
`validation.go` validateBlock, `abci/example/kvstore`).

It imports nothing of the program under test and takes nothing the
program made. It is handed the bytes the peers served, in the order
they served them, and replays them: a block is decoded here from its
wire bytes (a field reader of twenty lines), block H is accepted only
when the `LastCommit` of the block served for H + 1 passes
`commit_verify.Reference`'s light check for H's own BlockID (header
hash, one part whose hash is the leaf
hash of the served bytes), then validated as upstream validates it
(linkage, the hashes of its own data and commit, its `LastCommit` in
full over every validator, the median time) and executed on a dict
kvstore with this repo's app-hash rule. A commit that fails refuses H:
both providers are banned and every block from H up is forgotten, as
`BlockPool.RedoRequest` does, until it is served again.

A request's verdict, a short string:
  "ok:<height>:<block hash, hex>:<app hash, hex>"
                           the block store's height after the request,
                           the hash of the block at it and the app hash
  ";refused=<H>#<index>"   where a commit was refused: the height it
                           was for and the lowest wrong vote index
  ";banned=<peer>,<peer>"  the providers of that pair, sorted
  ";stored=1-<height>"     what the stores then hold
and "invalid:<height>:<what>;stored=..." for any other failing check.
"""

from __future__ import annotations

import copy
import hashlib

from chipbench.reference import commit_verify as R
from chipbench.reference import light_verify as L

PART_SIZE = 65536  # types/params.go BlockPartSizeBytes
# a header's byte-string fields, 6 to 14 on the wire
HASH_FIELDS = (
    "last_commit_hash", "data_hash", "validators_hash", "next_validators_hash",
    "consensus_hash", "app_hash", "last_results_hash", "evidence_hash", "proposer_address",
)  # fmt: skip
U64 = (1 << 64) - 1


def _sha256(data: bytes) -> bytes:
    return hashlib.sha256(data).digest()


# -- proto, as far as a block needs it ----------------------------------


def fields(data: bytes):
    """(field, wire type, value) of each field of a message: a varint's
    integer, a length-delimited field's bytes."""
    at, end = 0, len(data)
    while at < end:
        key, at = _varint(data, at)
        field, wire = key >> 3, key & 7
        if wire == 0:
            value, at = _varint(data, at)
        elif wire == 2:
            size, at = _varint(data, at)
            value, at = data[at : at + size], at + size
            if len(value) != size:
                raise ValueError("a field runs past the message")
        elif wire == 1:
            value, at = int.from_bytes(data[at : at + 8], "little"), at + 8
        else:
            raise ValueError(f"wire type {wire}")
        yield field, wire, value


def _varint(data: bytes, at: int) -> tuple:
    value = shift = 0
    while True:
        byte = data[at]
        at += 1
        value |= (byte & 0x7F) << shift
        if not byte & 0x80:
            return value, at
        shift += 7


def _one(data: bytes) -> dict:
    """field -> its last value, for a message without repeated fields."""
    return {f: v for f, _w, v in fields(data)}


def _timestamp(data: bytes) -> int:
    t = _one(data)
    return t.get(1, 0) * 10**9 + t.get(2, 0)


def _block_id(data: bytes) -> tuple:
    """(hash, parts total, parts hash) of a BlockID."""
    b = _one(data)
    parts = _one(b.get(2, b""))
    return b.get(1, b""), parts.get(1, 0), parts.get(2, b"")


def f_varint64(field: int, v: int) -> bytes:
    """An int64 field: a negative value is its 64-bit two's complement."""
    return R.f_varint(field, v & U64)


def opt_bytes(field: int, b: bytes) -> bytes:
    """proto3: an empty bytes field is not written."""
    return R.f_bytes(field, b) if b else b""


def block_id(block_hash: bytes, parts_total: int, parts_hash: bytes) -> bytes:
    """BlockID on the wire: the part-set header always written, empty
    fields never (the block before the first has the zero BlockID)."""
    return opt_bytes(1, block_hash) + R.f_bytes(
        2, R.f_varint(1, parts_total) + opt_bytes(2, parts_hash)
    )


def decode_block(wire: bytes) -> dict:
    """tendermint.types.Block: the header's 14 fields as
    `light_verify.header_hash` takes them, the txs, and `last_commit`
    as `commit_verify` takes a commit (None where the block has an
    empty one), with each CommitSig's bytes as served beside it."""
    top = {1: b"", 2: b"", 4: None}
    top.update(_one(wire))
    h = _one(top[1])
    version = _one(h.get(1, b""))
    last_hash, last_total, last_parts = _block_id(h.get(5, b""))
    header = {
        "version_block": version.get(1, 0),
        "version_app": version.get(2, 0),
        "chain_id": h.get(2, b"").decode(),
        "height": h.get(3, 0),
        "time_ns": _timestamp(h.get(4, b"")),
        "last_block_hash": last_hash,
        "last_parts_total": last_total,
        "last_parts_hash": last_parts,
    }
    header.update((name, h.get(6 + i, b"")) for i, name in enumerate(HASH_FIELDS))
    txs = [v for f, _w, v in fields(top[2]) if f == 1]
    commit = sig_bytes = None
    if top[4] is not None:
        c = _one(top[4])
        block_hash, total, parts_hash = _block_id(c.get(3, b""))
        sig_bytes = [v for f, _w, v in fields(top[4]) if f == 4]
        votes = []
        for raw in sig_bytes:
            s = _one(raw)
            votes.append(
                {
                    "flag": s.get(1, 0),
                    "address": s.get(2, b""),
                    "time_ns": _timestamp(s.get(3, b"")),
                    "sig": s.get(4, b""),
                }
            )
        commit = {
            "height": c.get(1, 0),
            "round": c.get(2, 0),
            "block_hash": block_hash,
            "parts_total": total,
            "parts_hash": parts_hash,
            "votes": votes,
        }
    return {
        "header": header,
        "txs": txs,
        "last_commit": commit,
        "sig_bytes": sig_bytes or [],
        "evidence": top.get(3, b""),
        "wire": wire,
    }


def decode_response(wire: bytes) -> bytes:
    """The block's bytes inside a blocksync Message{block_response}."""
    return _one(_one(wire)[3])[1]


# -- the hashes a block is held to --------------------------------------


def header_hash(h: dict) -> bytes:
    """Header.Hash: `light_verify.header_hash`'s tree of the 14 fields,
    with the proto3 rule for what is empty (an empty byte string's
    wrapper encodes to nothing, and so do the empty fields of the zero
    BlockID the first block has for the block before it)."""
    return L.merkle_root(
        [
            R.f_varint(1, h["version_block"]) + R.f_varint(2, h["version_app"]),
            opt_bytes(1, h["chain_id"].encode()),
            R.f_varint(1, h["height"]),
            R.timestamp(h["time_ns"]),
            block_id(h["last_block_hash"], h["last_parts_total"], h["last_parts_hash"]),
        ]
        + [opt_bytes(1, h[name]) for name in HASH_FIELDS]
    )


def parts_header(block_wire: bytes) -> tuple:
    """(total, hash) of the part set of a block's bytes: 64 KiB parts,
    the merkle root of the parts."""
    parts = [block_wire[at : at + PART_SIZE] for at in range(0, len(block_wire), PART_SIZE)]
    return len(parts), L.merkle_root(parts)


def data_hash(txs: list) -> bytes:
    """Txs.Hash: the merkle root of each tx's sha256."""
    return L.merkle_root([_sha256(tx) for tx in txs])


def results_hash(count: int) -> bytes:
    """ABCIResults.Hash of `count` accepted kvstore txs: code 0, no
    data, no gas, so each deterministic result encodes to nothing."""
    return L.merkle_root([b""] * count)


def consensus_hash(max_bytes: int, max_gas: int) -> bytes:
    """ConsensusParams.Hash: sha256 of HashedParams."""
    return _sha256(f_varint64(1, max_bytes) + f_varint64(2, max_gas))


def median_time(commit: dict, validators: list) -> int:
    """The voting-power-weighted median of a commit's timestamps
    (internal/state/state.go MedianTime)."""
    weighted = sorted(
        (vote["time_ns"], val["power"])
        for vote, val in zip(commit["votes"], validators)
        if vote["flag"] != 1
    )
    half = sum(power for _t, power in weighted) // 2
    seen = 0
    for time_ns, power in weighted:
        seen += power
        if seen > half:
            return time_ns
    raise ValueError("no votes")


class KVStore:
    """abci/example/kvstore as this repo has it: `key=value` txs into a
    dict; the app hash the merkle root of the sorted pairs, then the
    validators as `val:<hex key>!<power>`."""

    def __init__(self, validators: list) -> None:
        self.state: dict = {}
        self._validators = sorted(
            f"val:{v['pub'].hex()}!{v['power']}".encode() for v in validators
        )

    def deliver(self, tx: bytes) -> None:
        key, sep, value = tx.partition(b"=")
        self.state[key] = value if sep else key

    def app_hash(self) -> bytes:
        pairs = [k + b"=" + v for k, v in sorted(self.state.items())]
        return L.merkle_root(pairs + self._validators)


# -- the replay ---------------------------------------------------------


class Replay:
    """One node's catch-up from genesis: `serve()` each response in the
    order the peers sent them, `verdict()` after a request's last."""

    def __init__(self, deployment: dict, check_signatures: bool = True) -> None:
        self.d = deployment
        self.validators = deployment["validators"]
        self.commits = deployment["commits"]  # a commit_verify.Reference, shared
        self.check_signatures = check_signatures
        self.set_hash = L.validators_hash(self.validators)
        self.addresses = {v["address"] for v in self.validators}
        self.app = KVStore(self.validators)
        self.app_hash = self.app.app_hash()  # InitChain's
        self.results = results_hash(0)
        self.height = 0  # the block store's
        self.last_id = (b"", 0, b"")
        self.last_time_ns = deployment["genesis_time_ns"]
        self.last_hash = b""
        self.pool: dict = {}  # height -> (decoded block, peer)
        self.refused = None  # (height, index, banned peers) of this request
        self.failed = None

    def fork(self, check_signatures: bool) -> "Replay":
        """A second replay from where this one stands."""
        other = copy.copy(self)
        other.app = copy.copy(self.app)
        other.app.state = dict(self.app.state)
        other.pool = dict(self.pool)
        other.check_signatures = check_signatures
        return other

    def serve(self, peer: str, response_wire: bytes) -> None:
        block = decode_block(decode_response(response_wire))
        height = block["header"]["height"]
        if height <= self.height or height in self.pool or self.failed:
            return
        self.pool[height] = (block, peer)
        self._advance()

    def _advance(self) -> None:
        while self.height + 1 in self.pool and self.height + 2 in self.pool:
            first, first_peer = self.pool[self.height + 1]
            second, second_peer = self.pool[self.height + 2]
            height = self.height + 1
            total, parts_hash = parts_header(first["wire"])
            first_id = (header_hash(first["header"]), total, parts_hash)
            found = self._light(first_id, height, second["last_commit"])
            if found is not None:
                if not found.startswith("wrong_signature#"):
                    self.failed = f"invalid:{height}:{found}"
                    return
                index = int(found[len("wrong_signature#") :])
                self.refused = (height, index, sorted({first_peer, second_peer}))
                for h in [h for h in self.pool if h >= height]:
                    del self.pool[h]
                return
            wrong = self._validate(first)
            if wrong:
                self.failed = f"invalid:{height}:{wrong}"
                return
            for tx in first["txs"]:
                self.app.deliver(tx)
            self.app_hash = self.app.app_hash()
            self.results = results_hash(len(first["txs"]))
            self.height, self.last_id = height, first_id
            self.last_time_ns = first["header"]["time_ns"]
            self.last_hash = first_id[0]
            del self.pool[height]

    def _light(self, block_id_: tuple, height: int, commit):
        """VerifyCommitLight of `commit` for this BlockID; None when it
        passes."""
        if commit is None or commit["height"] != height:
            return "commit_height"
        if (commit["block_hash"], commit["parts_total"], commit["parts_hash"]) != block_id_:
            return "commit_for_another_block"
        if len(commit["votes"]) != len(self.validators):
            return "set_size"
        found = self.commits.verdict(commit, True, self.check_signatures)
        return None if found == "ok" else found

    def _validate(self, block: dict):
        """validateBlock, as far as a dict can be wrong."""
        h, d = block["header"], self.d
        if (h["version_block"], h["version_app"]) != (d["version_block"], d["version_app"]):
            return "version"
        if h["chain_id"] != d["chain_id"]:
            return "other_chain"
        if h["height"] != self.height + 1:
            return "height"
        if (h["last_block_hash"], h["last_parts_total"], h["last_parts_hash"]) != self.last_id:
            return "last_block_id"
        if h["app_hash"] != self.app_hash:
            return "app_hash"
        if h["consensus_hash"] != consensus_hash(d["block_max_bytes"], d["block_max_gas"]):
            return "consensus_hash"
        if h["last_results_hash"] != self.results:
            return "last_results_hash"
        if h["validators_hash"] != self.set_hash or h["next_validators_hash"] != self.set_hash:
            return "validators_hash"
        if h["data_hash"] != data_hash(block["txs"]):
            return "data_hash"
        if h["last_commit_hash"] != L.merkle_root(block["sig_bytes"]):
            return "last_commit_hash"
        if h["evidence_hash"] != L.merkle_root([]) or block["evidence"]:
            return "evidence"
        if h["proposer_address"] not in self.addresses:
            return "proposer"
        commit = block["last_commit"]
        if h["height"] == d["initial_height"]:
            if commit is not None and commit["votes"]:
                return "first_block_with_a_commit"
            return None if h["time_ns"] == self.last_time_ns else "time"
        found = self._light(self.last_id, h["height"] - 1, commit)
        if found is None:
            found = self.commits.verdict(commit, False, self.check_signatures)
        if found not in (None, "ok"):
            return f"last_commit_{found}"
        if h["time_ns"] != median_time(commit, self.validators):
            return "time"
        return None

    def verdict(self) -> str:
        """The request's verdict; what was refused is told once."""
        stored = f";stored=1-{self.height}" if self.height else ";stored="
        if self.failed:
            return self.failed + stored
        out = f"ok:{self.height}:{self.last_hash.hex()}:{self.app_hash.hex()}"
        if self.refused:
            height, index, banned = self.refused
            out += f";refused={height}#{index};banned={','.join(banned)}"
            self.refused = None
        return out + stored
