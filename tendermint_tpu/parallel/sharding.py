"""Device-mesh sharding for the batched crypto kernels.

The reference scales signature verification with CPU goroutines behind
crypto.BatchVerifier (reference: crypto/ed25519/ed25519.go:202-237); the
TPU-native framework scales it across a `jax.sharding.Mesh`. The batch
dimension of (pubkey, R, S, k) arrays is embarrassingly parallel, so the
layout is 1-D data-parallel over a single `sig` axis: XLA partitions the
whole verification program with zero cross-device traffic until the final
validity-bitmap gather, which rides ICI.

This module is also what the multi-chip dry-run exercises on a virtual CPU
mesh (`__graft_entry__.dryrun_multichip`).
"""

from __future__ import annotations

from typing import Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..libs import trace
from ..ops import ed25519_kernel as K
from ..ops import sr25519_kernel as SR

__all__ = [
    "make_mesh",
    "ShardedEd25519Verifier",
    "ShardedSr25519Verifier",
    "sharded_batch_verify",
]

SIG_AXIS = "sig"


def make_mesh(devices: Optional[Sequence] = None) -> Mesh:
    """1-D mesh over all (or the given) devices, axis name `sig`.

    Signature verification has no tensor/pipeline dimension worth sharding —
    each (pk, msg, sig) triple is independent — so the whole fleet is one
    data-parallel axis, the analog of the reference fanning votes across
    goroutines (internal/consensus/reactor.go:752).
    """
    devs = list(devices) if devices is not None else jax.devices()
    # tmlint: disable=dev-host-sync — devs is a host-side list of
    # Device handles (mesh topology), not a device array
    return Mesh(np.array(devs), (SIG_AXIS,))


class _MeshSharded:
    """Mixin partitioning a bucketed verifier's device program over a
    mesh. Buckets round up to a multiple of the mesh size so every
    device gets an equal shard; host-side packing is identical to the
    single-chip path — only placement changes. Subclasses name their
    kernel via _TILE_FN / _DEFAULT_SIZES; everything else (bucket
    rounding incl. oversized batches, the sharded jit) is shared so the
    two curves' device layouts cannot drift apart."""

    _TILE_FN = None  # staticmethod: the tile body to jit
    _DEFAULT_SIZES: Sequence[int] = ()

    def __init__(
        self,
        mesh: Mesh,
        bucket_sizes: Optional[Sequence[int]] = None,
    ) -> None:
        self.mesh = mesh
        self._sha512 = None
        n = mesh.devices.size
        sizes = bucket_sizes or self._DEFAULT_SIZES
        super().__init__(sorted({-(-s // n) * n for s in sizes}))

    def _mat(self) -> NamedSharding:
        """Batch axis is MINOR (see field25519 layout note): every
        program takes (rows, N) byte matrices, sharded over N."""
        return NamedSharding(self.mesh, P(None, SIG_AXIS))

    def _place(self, rows):
        """Shard host rows over the mesh straight from the host: a
        plain jnp.asarray would land the whole batch on the first
        device and leave the program to reshard it. Each transfer is a
        `shard_place` span, a child of the `device_launch` around it;
        rows that are on the mesh already (the sharded SHA-512's
        digests, handed to the tile) move nothing and open no span."""
        if isinstance(rows, jax.Array):
            return jax.device_put(rows, self._mat())
        n = self.mesh.devices.size
        with trace.span(
            "shard_place",
            devices=n,
            lanes_per_device=rows.shape[-1] // n,
            bytes=rows.nbytes,
        ):
            return jax.device_put(rows, self._mat())

    def _sha512_program(self):
        """SHA-512 partitioned like the tile, so every device hashes
        its own shard and the digests never gather on the first one
        (only the ed25519 verifier hashes on device)."""
        if self._sha512 is None:
            from ..ops.sha512_kernel import sha512_fixed

            self._sha512 = jax.jit(
                sha512_fixed,
                in_shardings=self._mat(),
                out_shardings=self._mat(),
            )
        return self._sha512

    def _bucket(self, n: int) -> int:
        b = super()._bucket(n)
        devs = self.mesh.devices.size
        return -(-b // devs) * devs  # oversized batches still pad to a multiple

    def _program(self, size: int):
        fn = self._compiled.get(size)
        if fn is None:
            # (32, N) pk bytes, (64, N) sig bytes, and a (64|32, N)
            # digest/challenge matrix in; the (N,) bitmap out
            mat = self._mat()
            fn = jax.jit(
                type(self)._TILE_FN,
                in_shardings=(mat, mat, mat),
                out_shardings=NamedSharding(self.mesh, P(SIG_AXIS)),
            )
            self._compiled[size] = fn
        return fn


class ShardedEd25519Verifier(_MeshSharded, K.Ed25519Verifier):
    """Ed25519Verifier whose device program is partitioned over a mesh."""

    _TILE_FN = staticmethod(K._verify_tile)
    _DEFAULT_SIZES = K.DEFAULT_BUCKET_SIZES


class ShardedSr25519Verifier(_MeshSharded, SR.Sr25519Verifier):
    """Sr25519Verifier partitioned over a mesh — same layout as the
    ed25519 variant: 1-D data-parallel over `sig`, host packing
    (merlin challenges + byte joins) unchanged. Reference analog:
    crypto/sr25519/batch.go behind the crypto.BatchVerifier seam."""

    _TILE_FN = staticmethod(SR._verify_tile_sr)
    _DEFAULT_SIZES = SR.DEFAULT_BUCKET_SIZES


def sharded_batch_verify(mesh, pubkeys, msgs, sigs) -> np.ndarray:
    """One-shot convenience: verify a batch across `mesh`."""
    return ShardedEd25519Verifier(mesh).verify(pubkeys, msgs, sigs)
