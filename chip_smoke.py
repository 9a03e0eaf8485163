"""chip_smoke.py — does the node's device verification path run on the chip?

    python3 chip_smoke.py            one TPU chip, every phase
    python3 chip_smoke.py --chips 4  the mesh path only, on four chips

One process, the entry points a node calls, the sizes a deployment has:
a 150-validator commit (BASELINE config 3), a 10,000-validator mixed
ed25519/sr25519 commit with a 10,000-leaf merkle root and proof batch
(config 5), a light client over a 150-validator chain (config 4's
shape), and one real `Node` with `[tpu] enable = true` serving RPC.
Every verdict is compared with a reference that shares no code with the
device path (the CPU batch factories, a hashlib merkle tree), and every
phase fails if the program's own counters say the device was bypassed:
the fault-containment routes (crypto/tpu_verifier.py) answer correctly
from the CPU when the device does not, so a right answer alone proves
nothing about the chip.

There is no CPU arm: without a TPU the script exits non-zero and prints
no result. The JSON lines before the last are observations (sizes,
buckets, pad waste, first-call and warm seconds, compiles, cache
entries), not metrics — no number printed here is a speed. The last
line is `{"ok": true, "device": {...}}` as JAX reports the device.

Phases are functions of their sizes so tests/test_chip_smoke.py can
rehearse each one at a tiny size on the CPU backend.
"""

from __future__ import annotations

import argparse
import asyncio
import contextlib
import hashlib
import json
import os
import shutil
import sys
import tempfile
import time

import numpy as np

CHAIN_ID = "chip-smoke"
# Sign-bytes carry the timestamp as two varints. Every timestamp this
# script signs is BASE_TIME_NS plus whole seconds, so seconds and nanos
# keep their widths, every sign-bytes has one length, and each bucket
# compiles one SHA-512 program instead of one per length.
BASE_TIME_NS = 1_700_000_000 * 10**9 + 500_000_000

PROBE_WAIT_S = 300.0  # bound on waiting for the install-time sr probe


class SmokeFailure(AssertionError):
    """A wrong verdict, or a sign that the device was bypassed."""


def check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


# -- observation plumbing ---------------------------------------------


class CompileLog:
    """Counts what JAX was asked to compile and what its persistent
    cache served, from jax.monitoring's own events. The duration event
    spans compile-or-load: on a cache hit it is the seconds the
    executable took to read back and load, not a compile."""

    def __init__(self) -> None:
        import jax.monitoring as mon

        self.build_s: list = []
        self.cache_hits = 0
        self.cache_requests = 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def close(self) -> None:
        """Stop listening (a test's teardown; a run just exits)."""
        import jax.monitoring as mon

        mon.unregister_event_duration_listener(self._on_duration)
        mon.unregister_event_listener(self._on_event)

    def _on_duration(self, event: str, duration: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.build_s.append(duration)

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/compile_requests_use_cache":
            self.cache_requests += 1

    def mark(self) -> tuple:
        return (len(self.build_s), self.cache_hits, self.cache_requests)

    def since(self, mark: tuple) -> dict:
        n, hits, reqs = mark
        new = self.build_s[n:]
        hits = self.cache_hits - hits
        return {
            "programs": len(new),
            "cache_hits": hits,
            # JAX persists only compiles of a second and more; the
            # rest (small eager programs) compile again every run
            "compiled": len(new) - hits,
            # compile seconds cold, load seconds on a hit, slowest first
            "compile_or_load_s_over_1s": sorted(
                (round(s, 1) for s in new if s >= 1.0), reverse=True
            ),
        }


def _cache_entries(path: str) -> int:
    try:
        return sum(1 for n in os.listdir(path) if not n.startswith("."))
    except OSError:
        return 0


class Sent:
    """What a phase sends the device, counted from its own sizes: one
    `group()` per batch verifier the phase makes the program build (one
    per key class per verification)."""

    def __init__(self) -> None:
        from tendermint_tpu.crypto import tpu_verifier as T

        self.min_batch = T.installed()
        self.chunk = (
            T._TpuBatchVerifier.STREAM_CHUNK if T.on_accelerator() else None
        )
        self.batches = 0
        self.sigs = 0

    def group(self, n: int, times: int = 1) -> None:
        """`n` signatures of one key class, verified `times` times.
        Under the install's min_batch the CPU factory serves them, by
        design; on an accelerator add() launches a dispatch per full
        STREAM_CHUNK; otherwise a group is one dispatch."""
        if n < self.min_batch:
            return
        self.batches += times * (-(-n // self.chunk) if self.chunk else 1)
        self.sigs += times * n


@contextlib.contextmanager
def device_accounting(out: dict, sent: Sent, log: CompileLog):
    """Run a phase's device work under the program's own counters and
    fail it unless they moved by exactly what the phase sent: no fault,
    no CPU-served batch, no Pallas swap, batch breakers closed."""
    from tendermint_tpu.crypto import breaker, tpu_verifier as T
    from tendermint_tpu.libs import trace

    before = T.stats()
    mark = log.mark()
    trace.reset()
    yield
    after = T.stats()
    delta = {k: after[k] - before[k] for k in after}
    spans = [s for s in trace.snapshot() if s.name == "tpu_dispatch"]
    out["device"] = {
        "batches": delta["batches"],
        "sigs": delta["sigs"],
        "pad_waste_slots": delta["pad_waste"],
        "first_touch_buckets": delta["warm_misses"],
        "buckets": sorted({s.attrs.get("bucket") for s in spans} - {None}),
    }
    out["jax"] = log.since(mark)
    check(delta["faults"] == 0, f"{delta['faults']} device fault(s)")
    cpu_served = [s.attrs for s in spans if s.attrs.get("fallback") == "cpu"]
    check(not cpu_served, f"batches served by the CPU route: {cpu_served}")
    check(
        delta["batches"] == sent.batches,
        f"device dispatches: {delta['batches']}, sent {sent.batches}",
    )
    check(
        delta["sigs"] == sent.sigs,
        f"signatures verified on device: {delta['sigs']}, sent {sent.sigs}",
    )
    for name in ("ed25519", "sr25519"):
        state = breaker.breaker_for(name).state()
        check(state == breaker.CLOSED, f"breaker {name} is {state}")


def timed(fn) -> tuple:
    """(seconds, result) of one call. Every entry point timed here
    returns host values (a verdict, a bitmap, a root), so the device
    work is inside the timing."""
    t0 = time.perf_counter()
    result = fn()
    return round(time.perf_counter() - t0, 3), result


# -- seeded data ------------------------------------------------------


def _priv(kind: str, seed: int, i: int):
    material = hashlib.sha256(b"chip-smoke|%d|%d" % (seed, i)).digest()
    if kind == "sr25519":
        from tendermint_tpu.crypto.sr25519 import PrivKeySr25519

        return PrivKeySr25519.from_seed(material)
    from tendermint_tpu.crypto.ed25519 import PrivKeyEd25519

    return PrivKeyEd25519.from_seed(material)


def make_validators(n_vals: int, seed: int, kinds=("ed25519",)):
    """(privs by address, ValidatorSet): n_vals equal-power validators,
    key classes rotating through `kinds`."""
    from tendermint_tpu.types.validator import Validator, ValidatorSet

    privs = [_priv(kinds[i % len(kinds)], seed, i) for i in range(n_vals)]
    vals = ValidatorSet(
        [Validator(pub_key=p.pub_key(), voting_power=10) for p in privs]
    )
    return {p.pub_key().address(): p for p in privs}, vals


def sign_commit(privs: dict, vals, block_id, height: int, time_ns: int):
    """A Commit for `block_id` signed by every validator of the set."""
    from tendermint_tpu.types.canonical import PRECOMMIT_TYPE
    from tendermint_tpu.types.commit import Commit, CommitSig
    from tendermint_tpu.types.vote import Vote

    sigs = []
    for idx, val in enumerate(vals.validators):
        vote = Vote(
            type=PRECOMMIT_TYPE,
            height=height,
            round=0,
            block_id=block_id,
            timestamp_ns=time_ns,
            validator_address=val.address,
            validator_index=idx,
        )
        sig = privs[val.address].sign(vote.sign_bytes(CHAIN_ID))
        sigs.append(CommitSig.for_block(sig, val.address, time_ns))
    return Commit(height=height, round=0, block_id=block_id, signatures=sigs)


def _block_id(tag: int):
    from tendermint_tpu.types.block_id import BlockID, PartSetHeader

    return BlockID(
        hash=bytes([tag]) * 32,
        part_set_header=PartSetHeader(total=1, hash=bytes([tag ^ 0xFF]) * 32),
    )


def corrupted(commit, idx: int):
    """The same commit with signature `idx` flipped in one bit."""
    from tendermint_tpu.types.commit import Commit, CommitSig

    sigs = list(commit.signatures)
    old = sigs[idx]
    bad = bytes([old.signature[0] ^ 0x01]) + old.signature[1:]
    sigs[idx] = CommitSig.for_block(bad, old.validator_address, old.timestamp_ns)
    return Commit(
        height=commit.height,
        round=commit.round,
        block_id=commit.block_id,
        signatures=sigs,
    )


def commit_triples(vals, commit) -> list:
    """(pub_key, sign_bytes, signature) per validator, in index order."""
    rows = commit.sign_bytes_batch(CHAIN_ID)
    return [
        (v.pub_key, rows[i], commit.signatures[i].signature)
        for i, v in enumerate(vals.validators)
    ]


def cpu_bitmap(triples) -> list:
    """The reference verdicts: the registered CPU batch factory of each
    key class, never the device."""
    from tendermint_tpu.crypto.batch import cpu_factory

    bits = [None] * len(triples)
    by_type: dict = {}
    for i, (pk, _sb, _sig) in enumerate(triples):
        by_type.setdefault(pk.type(), []).append(i)
    for key_type, idxs in by_type.items():
        bv = cpu_factory(key_type)()
        for i in idxs:
            bv.add(*triples[i])
        _ok, got = bv.verify()
        for i, bit in zip(idxs, got):
            bits[i] = bool(bit)
    return bits


def light_quorum(n_vals: int) -> int:
    """Signatures verify_commit_light checks on an equal-power set: it
    stops after the vote that carries the tally past two thirds."""
    return n_vals * 2 // 3 + 1


def _expect_wrong_signature(fn, idx: int, what: str) -> None:
    from tendermint_tpu.types.validation import InvalidCommitError

    try:
        fn()
    except InvalidCommitError as e:
        check(
            f"wrong signature (#{idx})" in str(e),
            f"{what}: the CPU verifier names #{idx}, the device path "
            f"said: {e}",
        )
    else:
        raise SmokeFailure(f"{what}: a corrupted commit verified")


def _sign_bytes_len(commit) -> int:
    lens = {len(sb) for sb in commit.sign_bytes_batch(CHAIN_ID)}
    check(len(lens) == 1, f"sign-bytes lengths differ: {sorted(lens)}")
    return lens.pop()


# -- phases -----------------------------------------------------------


def phase_install(min_batch=None, mesh=None, merkle_min_leaves=None) -> dict:
    """What Node.__init__ does when `[tpu] enable = true`
    (node/node.py): the compile cache, the verifier factories, the
    merkle hooks — default `[tpu]` config unless a rehearsal lowers the
    thresholds through install()'s own arguments."""
    from tendermint_tpu import native
    from tendermint_tpu.config import Config
    from tendermint_tpu.crypto import tpu_verifier
    from tendermint_tpu.libs import trace
    from tendermint_tpu.ops import compile_cache, merkle_kernel

    cfg = Config()
    if min_batch is None:
        min_batch = cfg.tpu.min_batch_size
    cache_dir = compile_cache.enable()
    tpu_verifier.install(min_batch=min_batch, mesh=mesh)
    if merkle_min_leaves is None:
        merkle_kernel.install()
    else:
        merkle_kernel.install(min_leaves=merkle_min_leaves)
    trace.enable()
    libs = {
        name: native.load(name) is not None
        for name in ("keccakf", "signbytes", "ed25519_batch")
    }
    check(all(libs.values()), f"native libraries not loaded: {libs}")
    return {
        "phase": "install",
        "counters_at_start": tpu_verifier.stats(),
        "min_batch": min_batch,
        "merkle_min_leaves": merkle_kernel.installed(),
        "bucket_sizes": list(cfg.tpu.bucket_sizes),
        "mesh_devices": 0 if mesh is None else int(mesh.devices.size),
        "native_libs": libs,
        "cache_dir": cache_dir,
        "cache_entries_at_start": _cache_entries(cache_dir),
    }


def phase_commit(n_vals: int, seed: int, log: CompileLog) -> dict:
    """One ed25519 commit through verify_commit_light and
    verify_commit, cold; then with one seeded signature corrupted."""
    from tendermint_tpu.crypto import sigcache
    from tendermint_tpu.types import validation as V

    privs, vals = make_validators(n_vals, seed)
    bid = _block_id(0xA1)
    commit = sign_commit(privs, vals, bid, 1, BASE_TIME_NS)
    quorum = light_quorum(n_vals)
    bad_idx = int(np.random.default_rng(seed).integers(0, quorum))
    bad = corrupted(commit, bad_idx)
    ref = cpu_bitmap(commit_triples(vals, bad))
    check(
        ref.index(False) == bad_idx and ref.count(False) == 1,
        "the CPU reference does not single out the corrupted signature",
    )
    out = {
        "phase": f"commit-{n_vals}",
        "validators": n_vals,
        "light_quorum_sigs": quorum,
        "sign_bytes_len": _sign_bytes_len(commit),
        "corrupted_index": bad_idx,
    }
    sent = Sent()
    sent.group(quorum, times=3)  # light: first, warm, corrupted
    sent.group(n_vals, times=3)  # full: the same three
    with sigcache.disabled(), device_accounting(out, sent, log):
        for name, fn in (
            ("light", V.verify_commit_light),
            ("full", V.verify_commit),
        ):
            out[f"{name}_first_s"], _ = timed(
                lambda: fn(CHAIN_ID, vals, bid, 1, commit)
            )
            commit.invalidate_memos()
            out[f"{name}_warm_s"], _ = timed(
                lambda: fn(CHAIN_ID, vals, bid, 1, commit)
            )
            _expect_wrong_signature(
                lambda: fn(CHAIN_ID, vals, bid, 1, bad),
                bad_idx,
                f"verify_commit ({name})",
            )
    return out


def reference_merkle_root(items) -> bytes:
    """RFC 6962 root by the recursive definition, hashlib only."""

    def root(hashes):
        if len(hashes) == 1:
            return hashes[0]
        k = 1 << ((len(hashes) - 1).bit_length() - 1)
        return hashlib.sha256(
            b"\x01" + root(hashes[:k]) + root(hashes[k:])
        ).digest()

    return root([hashlib.sha256(b"\x00" + it).digest() for it in items])


def phase_commit_mixed(
    n_vals: int, n_leaves: int, seed: int, log: CompileLog
) -> dict:
    """BASELINE config 5: one commit of n_vals validators, ed25519 and
    sr25519 alternating, every signature verified, cold; the per-lane
    bitmap of each key class against the CPU factory's with one seeded
    corruption per class; and (n_leaves > 0) a merkle root and a proof
    batch through the crypto.merkle device hooks."""
    from tendermint_tpu.crypto import sigcache
    from tendermint_tpu.crypto.batch import create_batch_verifier
    from tendermint_tpu.types import validation as V

    kinds = ("ed25519", "sr25519")
    privs, vals = make_validators(n_vals, seed, kinds)
    bid = _block_id(0xB2)
    commit = sign_commit(privs, vals, bid, 1, BASE_TIME_NS)
    groups = {k: [] for k in kinds}
    for i, v in enumerate(vals.validators):
        groups[v.pub_key.type()].append(i)
    rng = np.random.default_rng(seed)
    bad_idxs = sorted(int(rng.choice(groups[k])) for k in kinds)
    bad = commit
    for i in bad_idxs:
        bad = corrupted(bad, i)
    bad_triples = commit_triples(vals, bad)
    ref = cpu_bitmap(bad_triples)
    check(
        [i for i, bit in enumerate(ref) if not bit] == bad_idxs,
        "the CPU reference does not single out the corrupted signatures",
    )
    out = {
        "phase": f"commit-{n_vals}-mixed",
        "validators": n_vals,
        "key_classes": {k: len(g) for k, g in groups.items()},
        "sign_bytes_len": _sign_bytes_len(commit),
        "corrupted_indexes": bad_idxs,
    }
    def full(c) -> None:
        V.verify_commit(CHAIN_ID, vals, bid, 1, c)

    sent = Sent()
    for g in groups.values():
        # first, warm, corrupted, and the bitmap comparison below
        sent.group(len(g), times=4)
    with sigcache.disabled(), device_accounting(out, sent, log):
        out["full_first_s"], _ = timed(lambda: full(commit))
        commit.invalidate_memos()
        out["full_warm_s"], _ = timed(lambda: full(commit))
        _expect_wrong_signature(
            lambda: full(bad), bad_idxs[0], "verify_commit (mixed)"
        )
        # the seam verify_commit drives, asked for its bitmap
        for kind, idxs in groups.items():
            bv = create_batch_verifier(
                vals.validators[idxs[0]].pub_key, size_hint=len(idxs)
            )
            for i in idxs:
                bv.add(*bad_triples[i])
            ok, bits = bv.verify()
            check(
                [bool(b) for b in bits] == [ref[i] for i in idxs],
                f"{kind}: device bitmap differs from the CPU factory's",
            )
            check(not ok, f"{kind}: a batch with a bad signature passed")
    if n_leaves:
        out.update(merkle_checks(n_leaves, seed, log))
    return out


def merkle_checks(n_leaves: int, seed: int, log: CompileLog) -> dict:
    """A merkle root and a batch of every leaf's proof, one corrupted,
    through the crypto.merkle hooks merkle_kernel.install() set,
    against hashlib's root and the per-proof host recomputation."""
    from tendermint_tpu.crypto import merkle
    from tendermint_tpu.ops import merkle_kernel

    leaves = [
        hashlib.sha256(b"leaf|%d|%d" % (seed, i)).digest() * 2
        for i in range(n_leaves)
    ]
    want_root = reference_merkle_root(leaves)
    before = merkle_kernel.stats()
    mark = log.mark()
    out = {}
    out["merkle_root_first_s"], root = timed(
        lambda: merkle.hash_from_byte_slices(leaves)
    )
    check(root == want_root, "device merkle root differs from hashlib's")
    out["merkle_root_warm_s"], _ = timed(
        lambda: merkle.hash_from_byte_slices(leaves)
    )
    root, proofs = merkle.proofs_from_byte_slices(leaves)
    check(root == want_root, "proof root differs from hashlib's")
    bad_proof = int(np.random.default_rng(seed).integers(0, n_leaves))
    proofs[bad_proof].aunts[0] = bytes(32)
    want_bits = [p.compute_root_hash() == want_root for p in proofs]
    check(
        want_bits.count(False) == 1 and not want_bits[bad_proof],
        "the CPU reference does not single out the corrupted proof",
    )
    out["merkle_proofs_first_s"], bits = timed(
        lambda: merkle.verify_proofs_batch(proofs, want_root, leaves)
    )
    check(
        [bool(b) for b in bits] == want_bits,
        "device proof bitmap differs from the CPU reference's",
    )
    out["merkle_proofs_warm_s"], _ = timed(
        lambda: merkle.verify_proofs_batch(proofs, want_root, leaves)
    )
    after = merkle_kernel.stats()
    through_hooks = {k: after[k] - before[k] for k in after}
    out["merkle"] = {
        "n_leaves": n_leaves,
        "corrupted_proof": bad_proof,
        "through_device_hooks": through_hooks,
        **log.since(mark),
    }
    # three roots (first, warm, the proof builder's) and two proof
    # batches, none answered by the host path
    check(
        through_hooks
        == {"roots": 3, "leaves": 3 * n_leaves, "proofs": 2 * n_leaves},
        f"merkle work did not go through the device hooks: {through_hooks}",
    )
    return out


def make_light_chain(privs: dict, vals, n_heights: int) -> dict:
    """LightBlocks 1..n_heights over a static validator set, one second
    apart from BASE_TIME_NS (fixed-width timestamps, see above)."""
    from tendermint_tpu.types.block_id import BlockID, PartSetHeader
    from tendermint_tpu.types.header import Consensus, Header
    from tendermint_tpu.types.light import LightBlock, SignedHeader

    blocks = {}
    prev = BlockID()
    for h in range(1, n_heights + 1):
        header = Header(
            version=Consensus(block=11),
            chain_id=CHAIN_ID,
            height=h,
            time_ns=BASE_TIME_NS + h * 10**9,
            last_block_id=prev,
            validators_hash=vals.hash(),
            next_validators_hash=vals.hash(),
            app_hash=b"\x07" * 32,
            proposer_address=vals.validators[0].address,
        )
        bid = BlockID(
            hash=header.hash(),
            part_set_header=PartSetHeader(total=1, hash=b"\x22" * 32),
        )
        blocks[h] = LightBlock(
            signed_header=SignedHeader(
                header=header,
                commit=sign_commit(privs, vals, bid, h, header.time_ns),
            ),
            validator_set=vals,
        )
        prev = bid
    return blocks


def phase_light(n_vals: int, n_hops: int, seed: int, log: CompileLog) -> dict:
    """A seeded chain of n_hops + 1 signed headers through the light
    client's sequential verification, through verify_commit_light_bulk,
    and once more with one header's commit corrupted: the client must
    stop at that height with every earlier one saved."""
    from tendermint_tpu.crypto import sigcache
    from tendermint_tpu.crypto.batch import group_affinity
    from tendermint_tpu.light import Client, LightStore, TrustOptions
    from tendermint_tpu.light.client import SEQUENTIAL_BATCH_HOPS
    from tendermint_tpu.light.provider import Provider
    from tendermint_tpu.store.kv import MemKV
    from tendermint_tpu.types.light import LightBlock, SignedHeader
    from tendermint_tpu.types.validation import verify_commit_light_bulk

    privs, vals = make_validators(n_vals, seed)
    chain = make_light_chain(privs, vals, n_hops + 1)
    top = n_hops + 1
    now_ns = BASE_TIME_NS + (top + 60) * 10**9
    quorum = light_quorum(n_vals)
    rng = np.random.default_rng(seed)
    bad_height = int(rng.integers(2, top + 1))
    bad_idx = int(rng.integers(0, quorum))
    good = chain[bad_height]
    forged = dict(chain)
    forged[bad_height] = LightBlock(
        signed_header=SignedHeader(
            header=good.signed_header.header,
            commit=corrupted(good.signed_header.commit, bad_idx),
        ),
        validator_set=vals,
    )

    def client(blocks):
        class Chain(Provider):
            def id(self):
                return "chip-smoke"

            async def light_block(self, height):
                return blocks[height if height > 0 else top]

            async def report_evidence(self, ev):
                pass

        return Client(
            CHAIN_ID,
            TrustOptions(
                period_ns=10**18,
                height=1,
                hash=chain[1].signed_header.hash(),
            ),
            Chain(),
            [],
            LightStore(MemKV()),
            sequential=True,
        )

    window = max(1, min(SEQUENTIAL_BATCH_HOPS, group_affinity()))
    bad_hop = bad_height - 1  # hops count from the trusted height
    sent = Sent()
    # the good sync: each window's hops verified as one merged batch
    for first in range(0, n_hops, window):
        sent.group(min(window, n_hops - first) * quorum)
    # verify_commit_light_bulk: every hop in one merged batch
    sent.group(n_hops * quorum)
    # the forged chain: whole windows up to the bad one, then the bad
    # window once, as the merged batch that names the bad hop (hop by
    # hop up to and including it where the window is one hop)
    before_bad = (bad_hop - 1) // window * window
    for first in range(0, before_bad, window):
        sent.group(window * quorum)
    if window > 1:
        sent.group(min(window, n_hops - before_bad) * quorum)
    else:
        sent.group(quorum, times=bad_hop - before_bad)
    out = {
        "phase": f"light-{n_vals}",
        "validators": n_vals,
        "headers": top,
        "light_quorum_sigs": quorum,
        "window_hops": window,
        "corrupted_height": bad_height,
        "corrupted_index": bad_idx,
    }
    rows = [
        (
            vals,
            chain[h].signed_header.commit.block_id,
            h,
            chain[h].signed_header.commit,
        )
        for h in range(2, top + 1)
    ]

    async def sync(lc):
        return await lc.verify_light_block_at_height(top, now_ns)

    with sigcache.disabled(), device_accounting(out, sent, log):
        lc = client(chain)
        out["sequential_s"], lb = timed(lambda: asyncio.run(sync(lc)))
        check(
            lb.signed_header.hash() == chain[top].signed_header.hash()
            and lc.store.latest_light_block().height == top,
            "sequential sync did not end at the chain's top header",
        )
        out["bulk_s"], _ = timed(
            lambda: verify_commit_light_bulk(CHAIN_ID, rows)
        )
        lc = client(forged)
        try:
            asyncio.run(sync(lc))
        except Exception as e:
            check(
                f"wrong signature (#{bad_idx})" in str(e),
                f"forged header {bad_height}: unexpected error {e!r}",
            )
        else:
            raise SmokeFailure("a chain with a forged commit verified")
        check(
            lc.store.latest_light_block().height == bad_height - 1,
            f"forged header {bad_height}: the client stopped at "
            f"{lc.store.latest_light_block().height}",
        )
    return out


def phase_node(n_txs: int, target_height: int, log: CompileLog) -> dict:
    """One real Node built the way `cmd start` builds it (init a home,
    load its config, make_node, start) with the default `[tpu] enable =
    true`, kvstore over ABCI and RPC on an ephemeral port, in this
    process: the install in Node.__init__ fires the sr25519 probe on a
    thread while this thread goes on using JAX."""
    from tendermint_tpu.cmd.commands import _load_home, main as cli
    from tendermint_tpu.node import make_node
    from tendermint_tpu.ops import merkle_kernel
    from tendermint_tpu.rpc.client import HTTPClient

    home = tempfile.mkdtemp(prefix="chip-smoke-node-")
    out = {"phase": "node", "txs_sent": 0}
    try:
        with contextlib.redirect_stdout(sys.stderr):
            check(
                cli(["--home", home, "init", "validator"]) == 0,
                "`init validator` failed",
            )
        cfg = _load_home(home)
        check(cfg.tpu.enable, "the default [tpu] config has enable = false")
        cfg.rpc.laddr = "tcp://127.0.0.1:0"
        cfg.p2p.laddr = "tcp://127.0.0.1:0"

        async def drive() -> None:
            node = make_node(cfg)
            # what this node's own install set, not an earlier phase's
            threshold = merkle_kernel.installed()
            t0 = time.perf_counter()
            await asyncio.wait_for(node.start(), 120.0)
            out["start_s"] = round(time.perf_counter() - t0, 3)
            addr = f"127.0.0.1:{node.rpc_server.bound_port}"
            rpc = HTTPClient(addr)
            try:
                await node.consensus.wait_for_height(target_height, timeout=120.0)
                # bursts until one block's data hash has crossed the
                # merkle hook's leaf threshold
                sent = 0
                big = None
                for _burst in range(5):
                    txs = [b"k%d=v%d" % (i, i) for i in range(sent, sent + n_txs)]
                    sent += n_txs
                    codes = await _broadcast_all(addr, txs)
                    check(
                        not any(codes),
                        "broadcast_tx_sync rejected a tx",
                    )
                    tip = node.block_store.height()
                    await node.consensus.wait_for_height(tip + 2, timeout=60.0)
                    big = _biggest_block(node)
                    if len(big.txs) >= threshold:
                        break
                out["txs_sent"] = sent
                out["largest_block_txs"] = len(big.txs)
                check(
                    len(big.txs) >= threshold,
                    f"no block reached {threshold} txs after {sent} sent",
                )
                check(
                    big.header.data_hash
                    == reference_merkle_root(
                        [hashlib.sha256(tx).digest() for tx in big.txs]
                    ),
                    "a block's data hash differs from hashlib's",
                )
                status = await rpc.call("status")
                tip = int(status["sync_info"]["latest_block_height"])
                check(tip >= target_height, f"status height {tip}")
                blk = await rpc.call("block", height=big.header.height)
                check(
                    len(blk["block"]["txs"]) == len(big.txs),
                    "RPC `block` disagrees with the block store",
                )
                q = await rpc.call("abci_query", data=b"k7".hex())
                check(
                    bytes.fromhex(q["response"]["value"]) == b"v7",
                    f"abci_query returned {q}",
                )
                out["height"] = tip
            finally:
                await rpc.close()
                t0 = time.perf_counter()
                await asyncio.wait_for(node.stop(), 60.0)
                out["stop_s"] = round(time.perf_counter() - t0, 3)

        before = merkle_kernel.stats()
        # a one-validator chain signs one precommit a block: nothing
        # reaches min_batch, so no signature batch may touch the device
        with device_accounting(out, Sent(), log):
            asyncio.run(drive())
        after = merkle_kernel.stats()
        out["merkle"] = {k: after[k] - before[k] for k in after}
        check(
            out["merkle"]["roots"] >= 1,
            "no data hash went through the device merkle hook",
        )
    finally:
        shutil.rmtree(home, ignore_errors=True)
    return out


async def _broadcast_all(addr: str, txs: list, connections: int = 16) -> list:
    """broadcast_tx_sync every tx over a few keep-alive connections;
    returns the CheckTx codes."""
    import base64

    from tendermint_tpu.rpc.client import HTTPClient

    async def worker(share: list) -> list:
        rpc = HTTPClient(addr)
        try:
            return [
                (
                    await rpc.call(
                        "broadcast_tx_sync",
                        tx=base64.b64encode(tx).decode(),
                    )
                )["code"]
                for tx in share
            ]
        finally:
            await rpc.close()

    shares = [txs[i::connections] for i in range(connections)]
    return [c for part in await asyncio.gather(*map(worker, shares)) for c in part]


def _biggest_block(node):
    store = node.block_store
    blocks = (store.load_block(h) for h in range(1, store.height() + 1))
    return max(blocks, key=lambda b: len(b.txs))


def phase_mesh_placement(n_sigs: int, seed: int) -> dict:
    """Where each stage of one sharded ed25519 dispatch keeps its
    arrays: the placed input rows, the SHA-512 digests, the tile's
    bitmap. Fails unless every stage is spread over all mesh devices."""
    from tendermint_tpu.crypto import tpu_verifier

    v = tpu_verifier._SHARED_VERIFIER
    check(v is not None, "no mesh verifier installed")
    privs, vals = make_validators(n_sigs, seed)
    commit = sign_commit(privs, vals, _block_id(0xC3), 1, BASE_TIME_NS)
    triples = commit_triples(vals, commit)
    pks = [pk.bytes() for pk, _sb, _sig in triples]
    msgs = [sb for _pk, sb, _sig in triples]
    sigs = [sig for _pk, _sb, sig in triples]
    bucket = v._bucket(n_sigs)
    n_dev = int(v.mesh.devices.size)

    def where(arr) -> dict:
        devs = sorted(s.device.id for s in arr.addressable_shards)
        return {
            "devices": devs,
            "shard_shape": list(arr.addressable_shards[0].data.shape),
            "sharding": str(arr.sharding.spec),
        }

    stages = {
        "input_rows": where(v._place(np.zeros((32, bucket), np.uint8))),
        "sha512_digests": where(
            v._third_operand(
                pks, msgs, sigs, bucket,
                v._pack_operand(pks, msgs, sigs, bucket),
            )
        ),
    }
    ok, n, _size_ok = v.dispatch(pks, msgs, sigs)
    stages["tile_bitmap"] = where(ok)
    check(bool(v.gather((ok, n, _size_ok)).all()), "placement batch failed")
    for name, st in stages.items():
        check(
            len(set(st["devices"])) == n_dev,
            f"{name} lives on devices {st['devices']}, mesh has {n_dev}",
        )
    return {
        "phase": "mesh-placement",
        "sigs": n_sigs,
        "bucket": bucket,
        "mesh_devices": n_dev,
        "stages": stages,
    }


def finish(log: CompileLog, install_row: dict, phase_rows: list) -> dict:
    """The whole-run checks: no fault, no device work outside the
    phases' accounting, every breaker closed — the single-verify
    route's too, whose install-time probe compiles the smallest sr25519
    bucket on its own thread — and what the compile cache holds now."""
    from tendermint_tpu.crypto import breaker, tpu_verifier

    single = tpu_verifier.sr_single_breaker()
    deadline = time.monotonic() + PROBE_WAIT_S
    while single.state() != breaker.CLOSED and time.monotonic() < deadline:
        time.sleep(0.1)
    states = {
        name: breaker.breaker_for(name).state()
        for name in ("ed25519", "sr25519")
    }
    states["sr25519-single"] = single.state()
    check(
        all(s == breaker.CLOSED for s in states.values()),
        f"breakers not closed: {states}",
    )
    stats = tpu_verifier.stats()
    before = install_row["counters_at_start"]
    check(
        stats["faults"] == before["faults"],
        f"device faults: {stats['faults'] - before['faults']}",
    )
    # a reference that quietly asked the device, or a route nobody
    # counted, shows as dispatches no phase accounts for
    accounted = sum(r["device"]["batches"] for r in phase_rows)
    check(
        stats["batches"] - before["batches"] == accounted,
        f"{stats['batches'] - before['batches']} device dispatches in "
        f"all, the phases account for {accounted}",
    )
    cache_dir = install_row["cache_dir"]
    entries = _cache_entries(cache_dir)
    return {
        "phase": "summary",
        "breakers": states,
        "tpu_verifier": {k: stats[k] - before[k] for k in stats},
        "jax": log.since((0, 0, 0)),
        "cache_dir": cache_dir,
        "cache_entries": entries,
        "cache_entries_written": entries - install_row["cache_entries_at_start"],
    }


def emit(row: dict) -> dict:
    print(json.dumps(row), flush=True)
    return row


def run_one_chip(seed: int, log: CompileLog) -> None:
    install_row = emit(phase_install())
    rows = [
        emit(phase_commit(150, seed, log)),
        emit(phase_commit_mixed(10_000, 10_000, seed, log)),
        emit(phase_light(150, 48, seed, log)),
        emit(phase_node(700, 3, log)),
    ]
    emit(finish(log, install_row, rows))


def run_mesh(n_chips: int, seed: int, log: CompileLog) -> None:
    import jax

    from tendermint_tpu.parallel import make_mesh

    check(
        len(jax.devices()) >= n_chips,
        f"--chips {n_chips}: jax sees {len(jax.devices())} device(s)",
    )
    mesh = make_mesh(jax.devices()[:n_chips])
    install_row = emit(phase_install(mesh=mesh))
    rows = [emit(phase_commit_mixed(10_000, 0, seed, log))]
    emit(phase_mesh_placement(2048, seed))
    emit(finish(log, install_row, rows))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=21)
    args = ap.parse_args(argv)

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(
            f"chip_smoke: needs a TPU, jax found {dev.platform!r} "
            f"({dev.device_kind}); there is no CPU arm",
            file=sys.stderr,
        )
        return 2
    log = CompileLog()
    try:
        if args.chips == 1:
            run_one_chip(args.seed, log)
        else:
            run_mesh(args.chips, args.seed, log)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(
        json.dumps(
            {
                "ok": True,
                "device": {
                    "platform": dev.platform,
                    "kind": dev.device_kind,
                    "count": len(jax.devices()),
                },
            }
        ),
        flush=True,
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
