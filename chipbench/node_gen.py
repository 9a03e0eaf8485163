"""Seeded data for the node cells: whole blocks of one chain from
genesis, each as the blocksync `BlockResponse` a peer sends, and the
corrupted variants a lap's one bad request meets.

Everything is a function of the configuration, the traffic file and
`--seed`, made with the plain reference's encoders and hashes
(reference/commit_verify.py, reference/light_verify.py,
reference/node_replay.py) and gen.py's keys and signer: none of it by
the program under test.

A block is a real one, as `validateBlock` holds it: the header's
`last_block_id` is the block before it (its header hash and the hash of
the one part its bytes make), `last_commit_hash`, `data_hash` and
`evidence_hash` are those of its own content, `app_hash` and
`last_results_hash` are the kvstore's after the block before, its time
is the median of the commit it carries (the genesis time at height 1),
and that commit is signed by every validator over the BlockID of the
block before, with gen.py's one sign-bytes length. Its txs are
`k<j>=<value>` over a ring of keys, so the app's state stays one size.
"""

from __future__ import annotations

import hashlib

import numpy as np

from chipbench import gen
from chipbench.reference import commit_verify as R
from chipbench.reference import light_verify as L
from chipbench.reference import node_replay as N


def _tag_number(tag: str) -> int:
    return int.from_bytes(hashlib.sha256(tag.encode()).digest()[:4], "little")


# -- tendermint.types.Block and the blocksync Message on the wire ------


def encode_header(h: dict) -> bytes:
    """tendermint.types.Header (light_gen.encode_header's, with a
    BlockID whose empty fields are not written: the first block's)."""
    return (
        R.f_bytes(1, R.f_varint(1, h["version_block"]) + R.f_varint(2, h["version_app"]))
        + R.f_bytes(2, h["chain_id"].encode())
        + R.f_varint(3, h["height"])
        + R.f_bytes(4, R.timestamp(h["time_ns"]))
        + R.f_bytes(5, N.block_id(h["last_block_hash"], h["last_parts_total"], h["last_parts_hash"]))
        + b"".join(N.opt_bytes(6 + i, h[name]) for i, name in enumerate(N.HASH_FIELDS))
    )


def encode_last_commit(commit) -> bytes:
    """tendermint.types.Commit; the first block carries an empty one:
    its zero BlockID and nothing else."""
    if commit is None:
        return R.f_bytes(3, N.block_id(b"", 0, b""))
    return gen.encode_commit(commit)


def encode_block(header: dict, txs: list, commit) -> bytes:
    """tendermint.types.Block: header, data, (no) evidence, last commit."""
    return (
        R.f_bytes(1, encode_header(header))
        + R.f_bytes(2, b"".join(R.f_bytes(1, tx) for tx in txs))
        + R.f_bytes(3, b"")
        + R.f_bytes(4, encode_last_commit(commit))
    )


def encode_response(block_wire: bytes) -> bytes:
    """tendermint.blocksync.Message{block_response{block}}."""
    return R.f_bytes(3, R.f_bytes(1, block_wire))


def commit_sig_bytes(commit) -> list:
    """Each CommitSig as the commit carries it, for `last_commit_hash`."""
    if commit is None:
        return []
    return [v for f, _w, v in N.fields(gen.encode_commit(commit)) if f == 4]


# -- the deployment and its chains -------------------------------------


class Deployment:
    """What the node is configured with and the reference is told: the
    validators, the genesis, the constants a header is held to."""

    def __init__(self, config: dict, seed: int) -> None:
        self.chain_id = config["chain_id"]
        self.keys, self.validators = gen.make_validators(config, seed)
        self.checked = gen.light_quorum(self.validators)
        self.genesis_time_ns = gen.BASE_TIME_S * 10**9
        self.set_hash = L.validators_hash(self.validators)
        self.constants = {
            "chain_id": self.chain_id,
            "initial_height": config["initial_height"],
            "genesis_time_ns": self.genesis_time_ns,
            "version_block": config["block_protocol"],
            "version_app": config["app_version"],
            "block_max_bytes": config["block_max_bytes"],
            "block_max_gas": config["block_max_gas"],
        }
        self.consensus_hash = N.consensus_hash(
            config["block_max_bytes"], config["block_max_gas"]
        )

    def reference(self) -> dict:
        """What `node_replay.Replay` takes, the signature checks of all
        its replays memoised in one `commit_verify.Reference`."""
        return dict(
            self.constants,
            validators=self.validators,
            commits=R.Reference(self.chain_id, self.validators),
        )


class Chain:
    """`count` blocks from height 1 over one deployment, each a served
    response; `tag` tells one chain of a seed from another (the
    window's from the warm-up's: other txs and other vote times, so
    other hashes and other signatures throughout)."""

    def __init__(self, deployment: Deployment, config: dict, seed: int, tag: str,
                 count: int) -> None:
        self.d = d = deployment
        self.tag = tag
        n_vals = len(d.validators)
        rng = np.random.default_rng([seed, _tag_number(tag)])
        nanos = rng.integers(gen.NANOS_LO, gen.NANOS_HI, size=(count + 1, n_vals))
        self._values = rng.integers(0, 26, size=(count + 1, config["txs_per_block"]))
        self.txs_per_block = config["txs_per_block"]
        self.tx_bytes = config["tx_bytes"]
        self.key_ring = config["key_ring"]
        app = N.KVStore(d.validators)
        app_hash, results = app.app_hash(), N.results_hash(0)
        last_id, last_commit = (b"", 0, b""), None
        self.blocks: list = []  # place h - 1: the block at height h
        self.lens: set = set()
        for height in range(1, count + 1):
            txs = self.txs_of(height)
            header = {
                "version_block": d.constants["version_block"],
                "version_app": d.constants["version_app"],
                "chain_id": d.chain_id,
                "height": height,
                "time_ns": (
                    d.genesis_time_ns if last_commit is None
                    else N.median_time(last_commit, d.validators)
                ),
                "last_block_hash": last_id[0],
                "last_parts_total": last_id[1],
                "last_parts_hash": last_id[2],
                "last_commit_hash": L.merkle_root(commit_sig_bytes(last_commit)),
                "data_hash": N.data_hash(txs),
                "validators_hash": d.set_hash,
                "next_validators_hash": d.set_hash,
                "consensus_hash": d.consensus_hash,
                "app_hash": app_hash,
                "last_results_hash": results,
                "evidence_hash": L.merkle_root([]),
                "proposer_address": d.validators[height % n_vals]["address"],
            }
            header["hash"] = N.header_hash(header)
            wire = encode_block(header, txs, last_commit)
            last_id = (header["hash"],) + N.parts_header(wire)
            self.blocks.append(
                {
                    "header": header, "txs": txs, "last_commit": last_commit,
                    "wire": wire, "response": encode_response(wire), "block_id": last_id,
                }
            )  # fmt: skip
            for tx in txs:
                app.deliver(tx)
            app_hash, results = app.app_hash(), N.results_hash(len(txs))
            if height < count:
                last_commit = self._sign(height, last_id, nanos[height])
        if len(self.lens) != 1:
            raise RuntimeError(f"sign-bytes lengths differ: {sorted(self.lens)}")
        self.sign_bytes_len = next(iter(self.lens))

    def txs_of(self, height: int) -> list:
        """`txs_per_block` txs `k<j>=<value>` of `tx_bytes` bytes, the
        keys walking a ring of `key_ring`."""
        txs = []
        for i in range(self.txs_per_block):
            key = b"k%d=" % ((height * self.txs_per_block + i) % self.key_ring)
            fill = bytes([97 + int(self._values[height - 1][i])])
            txs.append(key + fill * (self.tx_bytes - len(key)))
        return txs

    def _sign(self, height: int, block_id: tuple, nanos) -> dict:
        """The commit for the block at `height`, signed by everyone."""
        commit = {
            "height": height,
            "round": 0,
            "block_hash": block_id[0],
            "parts_total": block_id[1],
            "parts_hash": block_id[2],
            "votes": [],
        }
        parts = R.sign_bytes_parts(self.d.chain_id, commit)
        block_ns = (gen.BASE_TIME_S + height) * 10**9
        jobs = []
        for key, val, ns in zip(self.d.keys, self.d.validators, nanos):
            vote = {"flag": R.FLAG_COMMIT, "address": val["address"], "time_ns": block_ns + int(ns)}
            commit["votes"].append(vote)
            jobs.append((key, R.sign_bytes(parts, vote["time_ns"])))
        self.lens.update(len(msg) for _key, msg in jobs)
        for vote, sig in zip(commit["votes"], type(self.d.keys[0]).sign_all(jobs)):
            vote["sig"] = sig
        return commit

    def response(self, height: int) -> bytes:
        return self.blocks[height - 1]["response"]

    def corrupted_response(self, height: int, index: int) -> bytes:
        """The block at `height` with signature `index` of its
        LastCommit flipped in one bit, as a peer would serve it."""
        block = self.blocks[height - 1]
        bad = gen.corrupted(block["last_commit"], index)
        return encode_response(encode_block(block["header"], block["txs"], bad))


class Laps:
    """Which request of a lap meets a corrupted block, and where. A lap
    is `requests` requests of `blocks_per_request` heights from a fresh
    node at genesis; its first releases one block more, since height H
    is applied with H + 1 in hand. One request a lap, at a position the
    seed draws from `corrupted_in`, is served, for one seeded height
    H + 1 of those it releases, a block whose LastCommit has one bit
    flipped in a seeded vote below the light quorum."""

    def __init__(self, traffic: dict, seed: int, tag: str, requests: int, checked: int,
                 position=None) -> None:
        self.per = per = traffic["blocks_per_request"]
        self.requests = requests
        rng = np.random.default_rng([seed, _tag_number(tag), 1])
        lo, hi = traffic["corrupted_in"]
        self.position = int(rng.integers(lo, hi + 1)) if position is None else position
        # H is one of the heights the request applies; H + 1 carries the bad commit
        first = self.position * per + 1
        self.refused_height = first + int(rng.integers(0, per))
        self.bad_height = self.refused_height + 1
        self.bad_index = int(rng.integers(0, checked))

    def released(self, position: int) -> int:
        """The highest height the peers may serve during a request."""
        return (position + 1) * self.per + 1

    def target(self, position: int) -> int:
        """The block store's height when the request ends."""
        return (position + 1) * self.per
