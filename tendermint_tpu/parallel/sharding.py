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
from jax.sharding import Mesh

from ..ops import ed25519_kernel as K
from ..ops import sr25519_kernel as SR
from ..ops.verifier import SIG_AXIS

__all__ = [
    "make_mesh",
    "ShardedEd25519Verifier",
    "ShardedSr25519Verifier",
    "sharded_batch_verify",
]


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


class ShardedEd25519Verifier(K.Ed25519Verifier):
    """Ed25519Verifier whose device programs are partitioned over a
    mesh (the placement itself is ops/verifier.py's `mesh=`)."""

    def __init__(
        self, mesh: Mesh, bucket_sizes: Optional[Sequence[int]] = None
    ) -> None:
        super().__init__(bucket_sizes, mesh=mesh)


class ShardedSr25519Verifier(SR.Sr25519Verifier):
    """Sr25519Verifier partitioned over a mesh — same layout as the
    ed25519 variant: 1-D data-parallel over `sig`, the merlin program
    (where the device form runs) partitioned like the tile and its
    challenges left on the mesh for it. Reference analog:
    crypto/sr25519/batch.go behind the crypto.BatchVerifier seam."""

    def __init__(
        self, mesh: Mesh, bucket_sizes: Optional[Sequence[int]] = None
    ) -> None:
        super().__init__(bucket_sizes, mesh=mesh)


def sharded_batch_verify(mesh, pubkeys, msgs, sigs) -> np.ndarray:
    """One-shot convenience: verify a batch across `mesh`."""
    return ShardedEd25519Verifier(mesh).verify(pubkeys, msgs, sigs)
