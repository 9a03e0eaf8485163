"""The bucketed device verifier both key classes share.

One body for what ed25519 and sr25519 verification do alike: pad the
batch to a configured bucket, mask malformed sizes, join the byte rows
on the host, place them, launch the key class's tile program and gather
the bitmap. A key class (ops/ed25519_kernel.py, ops/sr25519_kernel.py)
names its tile program and builds its third operand: SHA-512 digests
hashed on the device, or merlin challenges from a device program or,
in narrow launches, the host.

Placement is an argument, not a subclass. Without a mesh the rows go to
the default device and the module's shared jitted program runs them.
With one (`tendermint_tpu.parallel.make_mesh`) the layout is 1-D
data-parallel over the mesh's single `sig` axis: buckets round up to a
multiple of the mesh so every chip gets an equal shard, rows are
sharded straight from the host, and every chip runs the same tile
function on its own shard (`shard_map`: the program is lane-local, and
the fused walk inside it, ops/fused_walk.py, is a custom call that the
compiler could not partition by itself), with no cross-device traffic
until the bitmap's gather.
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..config import DEFAULT_BUCKET_SIZES, bucket_for
from ..libs import trace

__all__ = [
    "BucketedVerifier",
    "DEFAULT_BUCKET_SIZES",
    "SIG_AXIS",
    "bucket_for",
]

# the one mesh axis (parallel.make_mesh builds the mesh over it). The
# batch axis is MINOR in every array a program takes or returns
# (field25519's layout note), and the one sharded:
SIG_AXIS = "sig"
ROWS = P(None, SIG_AXIS)  # (rows, N) byte matrices
LANES = P(SIG_AXIS)  # the (N,) bitmap

# lanes a grid step of a TPU's fused window walk takes
# (ops/fused_walk.py): a chip's share of a bucket is one narrower tile
# or whole tiles (`BucketedVerifier._round`)
LANE_TILE = 128


def _join_cols(items: Sequence[bytes], width: int, pad: int) -> np.ndarray:
    """Join n equal-length byte strings into a (width, n+pad) uint8
    array, batch-minor, zero-padded on the right."""
    arr = np.frombuffer(b"".join(items), dtype=np.uint8).reshape(-1, width)
    out = arr.T
    if pad:
        return np.pad(out, ((0, 0), (0, pad)))
    return np.ascontiguousarray(out)


def _program_name(prog) -> str:
    """The traced function's own name (`_verify_tile`, `sha512_fixed`):
    what the profiler calls the program's executions, less its `jit_`
    prefix."""
    return getattr(prog, "__name__", type(prog).__name__)


@functools.lru_cache(maxsize=None)
def _per_chip(fn, mesh, out: P):
    """`fn` over `mesh`, each chip running it on its own shard
    (`shard_map`: the tiles are lane-local, and the fused walk inside
    them is a kernel the compiler cannot partition by itself). One
    program a (function, mesh) for the process, as the shared jitted
    programs are one a function: a verifier built again over the same
    mesh (a node's re-install) traces and loads nothing anew."""
    per_chip = jax.shard_map(
        fn,
        mesh=mesh,
        in_specs=ROWS,
        out_specs=out,
        check_vma=False,  # a pallas_call declares none
    )
    return jax.jit(
        per_chip,
        in_shardings=NamedSharding(mesh, ROWS),
        out_shardings=NamedSharding(mesh, out),
    )


def _holds(jaxpr, primitive: str) -> bool:
    """Whether `jaxpr`, or a jaxpr nested in one of its equations,
    applies `primitive`."""
    nested = jax.core.jaxprs_in_params
    return any(
        eqn.primitive.name == primitive
        or any(_holds(j, primitive) for j in nested(eqn.params))
        for eqn in jaxpr.eqns
    )


_WALKS: dict = {}  # (tile program, bucket) -> the walk it holds


def _walk_of(prog, bucket: int, operands) -> str:
    """Which window walk the tile program `prog` runs over `operands`,
    read off what jit traced for them (the trace this launch compiles,
    or finds compiled): "fused" where it holds a Pallas kernel
    (ops/fused_walk.py), "scan" where it holds none. Read once a
    (program, bucket) for the process."""
    walk = _WALKS.get((prog, bucket))
    if walk is None:
        jaxpr = prog.trace(*operands).jaxpr.jaxpr
        fused = _holds(jaxpr, "pallas_call")
        walk = _WALKS[prog, bucket] = "fused" if fused else "scan"
    return walk


class BucketedVerifier:
    """Compiled, bucketed batch verifier of one key class.

    Subclasses set `_TILE`, the module's shared jitted tile program
    ((32, N) pubkey rows, (64, N) signature rows, a (64, N) third
    operand -> (N,) bitmap), and define `_operand`. Shapes are bucketed
    (pad to the next configured size) so that a handful of programs
    serve every batch. Thread-compatible for the asyncio runtime: a
    dispatch is a synchronous device invocation."""

    _TILE = None  # staticmethod(jax.jit(tile function))

    def __init__(
        self, bucket_sizes: Optional[Sequence[int]] = None, mesh=None
    ) -> None:
        self.mesh = mesh
        self._devices = 1 if mesh is None else int(mesh.devices.size)
        self.bucket_sizes = sorted(
            {self._round(s) for s in bucket_sizes or DEFAULT_BUCKET_SIZES}
        )

    def _round(self, b: int) -> int:
        """`b` lanes rounded up to an equal share a chip, and a share
        above LANE_TILE to whole tiles: the fused walk's grid has no
        ragged last step (a three-chip mesh, an oversized batch)."""
        share = -(-b // self._devices)
        if share > LANE_TILE:
            share = -(-share // LANE_TILE) * LANE_TILE
        return share * self._devices

    def _bucket(self, n: int) -> int:
        """The padded width `n` signatures run at: the smallest
        configured bucket that holds them, a multiple of the mesh even
        for an oversized batch. The one home of the rule (the seam's
        pad-waste telemetry asks here, crypto/tpu_verifier._bucket_of)."""
        return self._round(bucket_for(n, self.bucket_sizes))

    def _program(self, shared, out: P):
        """`shared` (a module's jitted program) as this verifier runs
        it: itself on one device; over a mesh, its function mapped
        over the batch axis of its ROWS inputs and of its output (laid
        out as `out`), so that every chip computes its own shard."""
        if self.mesh is None:
            return shared
        return _per_chip(shared.__wrapped__, self.mesh, out)

    def _place(self, rows):
        """Byte rows (host or already on device) -> the device array
        the programs take. Over a mesh the rows are sharded straight
        from the host: a plain jnp.asarray would land the whole batch
        on the first device and leave the program to reshard it. Each
        such transfer is a `shard_place` span, a child of the
        `device_launch` around it; rows that are on the mesh already
        (the sharded SHA-512's digests, handed to the tile) move
        nothing and open no span."""
        if self.mesh is None:
            return jnp.asarray(rows)
        sharding = NamedSharding(self.mesh, ROWS)
        if isinstance(rows, jax.Array):
            return jax.device_put(rows, sharding)
        with trace.span(
            "shard_place",
            devices=self._devices,
            lanes_per_device=rows.shape[-1] // self._devices,
            bytes=rows.nbytes,
        ):
            return jax.device_put(rows, sharding)

    def _launch(self, shared, out: P, bucket: int, *rows):
        """Place `rows` and enqueue one device program over them (JAX
        dispatch is asynchronous: this returns before the device ends).
        A tile's span says which window walk the launched program holds
        (`_walk_of`); a program without one (SHA-512) says nothing."""
        prog = self._program(shared, out)
        with trace.span(
            "device_launch", program=_program_name(prog), bucket=bucket
        ) as span:
            placed = [self._place(r) for r in rows]
            if shared is self._TILE:
                span.set(walk=_walk_of(prog, bucket, placed))
            return prog(*placed)

    def _pack_operand(self, pubkeys, msgs, sigs, bucket: int):
        """Host rows of the third operand that are byte joins, and so
        belong inside `pack_rows` (ed25519's pre-image); None for a key
        class that has none."""
        return None

    def host_operand(self, n: int) -> bool:
        """Whether a launch of `n` signatures makes its third operand
        on the host (sr25519's merlin in narrow launches) and not in a
        device program of its own (ed25519's SHA-512): the seam
        launches a class that packs byte rows alone first
        (crypto.batch.drain_classes)."""
        return False

    def _operand(self, pubkeys, msgs, sigs, bucket: int, packed):
        """(64, bucket) third operand of the tile for messages of one
        length, on the host or the device; `packed` is what
        `_pack_operand` returned, None for a length group."""
        raise NotImplementedError

    def _third_operand(self, pubkeys, msgs, sigs, bucket: int, packed):
        """(64, bucket) third operand of the tile. One message length
        (every sign-bytes of a Commit): `_operand` over the batch, so
        what the device makes never leaves it. Mixed lengths: one
        `_operand` a length group at the group's own bucket, the groups
        meeting on the host."""
        if len(set(map(len, msgs))) == 1:
            return self._operand(pubkeys, msgs, sigs, bucket, packed)
        groups: dict = {}
        for i, m in enumerate(msgs):
            groups.setdefault(len(m), []).append(i)
        out = np.zeros((64, bucket), dtype=np.uint8)
        for idxs in groups.values():
            g = len(idxs)
            part = self._operand(
                [pubkeys[i] for i in idxs],
                [msgs[i] for i in idxs],
                [sigs[i] for i in idxs],
                self._bucket(g),
                None,
            )
            out[:, idxs] = np.asarray(part)[:, :g]
        return out

    def verify(
        self,
        pubkeys: Sequence[bytes],
        msgs: Sequence[bytes],
        sigs: Sequence[bytes],
    ) -> np.ndarray:
        """Returns a bool bitmap, one per triple. Malformed inputs are
        reported invalid rather than raising (the BatchVerifier.add layer
        enforces sizes upstream)."""
        return self.gather(self.dispatch(pubkeys, msgs, sigs))

    def dispatch(
        self,
        pubkeys: Sequence[bytes],
        msgs: Sequence[bytes],
        sigs: Sequence[bytes],
    ):
        """Asynchronously launch verification; returns an opaque handle
        for gather(). Device dispatch is non-blocking in JAX, so several
        batches can be in flight at once — host packing of the next
        batch overlaps device work on the last (the verify-ahead
        pattern from SURVEY §7: stream commits through the device
        without stalling the consensus loop)."""
        n = len(pubkeys)
        if n == 0:
            return (None, 0, np.zeros(0, dtype=bool))
        bucket = self._bucket(n)
        pad = bucket - n
        with trace.span("pack_rows", n=n, bucket=bucket):
            size_ok = np.array(
                [
                    len(pk) == 32 and len(sig) == 64
                    for pk, sig in zip(pubkeys, sigs)
                ],
                dtype=bool,
            )
            if not size_ok.all():
                pubkeys = [
                    pk if ok else b"\x00" * 32
                    for pk, ok in zip(pubkeys, size_ok)
                ]
                sigs = [
                    sig if ok else b"\x00" * 64
                    for sig, ok in zip(sigs, size_ok)
                ]
            # host work is byte joins only (and sr25519's merlin
            # transcripts in narrow launches); limb unpacking, scalar
            # canonicality, digits and the curve math all run on device
            pk_b = _join_cols(pubkeys, 32, pad)
            sig_b = _join_cols(sigs, 64, pad)
            packed = self._pack_operand(pubkeys, msgs, sigs, bucket)
        third = self._third_operand(pubkeys, msgs, sigs, bucket, packed)
        ok = self._launch(self._TILE, LANES, bucket, pk_b, sig_b, third)
        return (ok, n, size_ok)

    def gather(self, handle) -> np.ndarray:
        """Block on a dispatch() handle and return the bitmap: first the
        device finishing the program (`gather_ready`, however long it
        has yet to run or to start), then the copy to the host, which
        assembles a mesh's shards, and the size mask (`gather_fetch`)."""
        ok, n, size_ok = handle
        if ok is None:
            return size_ok
        with trace.span("gather_ready"):
            ok.block_until_ready()
        with trace.span("gather_fetch"):
            return np.asarray(ok)[:n] & size_ok
