"""Device merkle: batched tree roots and proof verification.

The module crypto/merkle.py names as its device counterpart. Two
offloads (reference shapes: crypto/merkle/tree.go:68 HashFromByteSlices,
proof.go:52 Proof.Verify):

- tree_root(leaf_hashes): the n-1 inner hashes of an RFC 6962 tree.
  Level-by-level pairwise reduction (odd node passes through), which
  reproduces the reference's split-at-largest-power-of-two shape; each
  level is one device call hashing all pairs at once.

- verify_proofs(...): K inclusion proofs checked in one device program:
  a lax.scan over proof depth where each lane either absorbs its aunt
  on the left, on the right, or passes through (padding for shorter
  proofs) — the select form keeps all lanes busy with no per-lane
  control flow.

Both are installed behind crypto.merkle's device hook by install(),
gated on batch size the same way the ed25519 verifier is
(crypto/tpu_verifier.py): small inputs stay on the host CPU path.
"""

from __future__ import annotations

from typing import List, Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from ..libs import trace
from . import sha256_kernel as S

__all__ = [
    "tree_root",
    "verify_proofs",
    "install",
    "installed",
    "stats",
]

# proof-step flags
_STEP_LEFT = 0  # our hash is the left child:  h = inner(h, aunt)
_STEP_RIGHT = 1  # our hash is the right child: h = inner(aunt, h)
_STEP_NOOP = 2  # padding beyond this proof's depth

_inner_jit = jax.jit(S.inner_hash_batch)


def _bucket(n: int) -> int:
    """Next power of two >= n (min 8): bounds the number of compiled
    program shapes — tree levels halve in width every step, so without
    padding every tree size would compile its own ladder of programs."""
    b = 8
    while b < n:
        b <<= 1
    return b


def _inner_bucketed(left: jnp.ndarray, right: jnp.ndarray) -> jnp.ndarray:
    """Device-resident bucketed inner hash: no host transfer — callers
    chain levels and fetch once at the end."""
    n = left.shape[1]
    b = _bucket(n)
    if b != n:
        left = jnp.pad(left, ((0, 0), (0, b - n)))
        right = jnp.pad(right, ((0, 0), (0, b - n)))
    return _inner_jit(left, right)[:, :n]


def _to_cols(hashes: Sequence[bytes]) -> np.ndarray:
    return np.frombuffer(b"".join(hashes), dtype=np.uint8).reshape(-1, 32).T


def tree_root(leaf_hashes: Sequence[bytes]) -> bytes:
    """Root from already-hashed leaves. Pairwise level reduction: for
    n hashes per level, hash the floor(n/2) adjacent pairs in one
    device call; an odd trailing node passes through unchanged. This
    pairing yields exactly the reference's recursive
    split-point tree (tree.go:94): the split at the largest power of
    two < n is what adjacent pairing produces level by level."""
    n = len(leaf_hashes)
    if n == 0:
        raise ValueError("tree_root requires at least one leaf hash")
    # the whole reduction stays device-resident: one upload, log2(n)
    # async dispatches, ONE blocking download at the end (a host
    # round-trip per level would stall the device log2(n) times)
    level = jnp.asarray(_to_cols(leaf_hashes))  # (32, n)
    while level.shape[1] > 1:
        m = level.shape[1]
        pairs = m // 2
        hashed = _inner_bucketed(
            level[:, 0 : 2 * pairs : 2],
            level[:, 1 : 2 * pairs : 2],
        )
        if m % 2:
            hashed = jnp.concatenate([hashed, level[:, -1:]], axis=1)
        level = hashed
    return np.asarray(level[:, 0]).tobytes()


def _sides_for(index: int, total: int) -> List[int]:
    """Bottom-up left/right flags matching Proof.aunts order
    (reference recursion: crypto/merkle/proof.go:71
    computeHashFromAunts)."""
    out: List[int] = []

    def rec(idx: int, tot: int) -> None:
        if tot == 1:
            return
        k = 1 << ((tot - 1).bit_length() - 1)
        if idx < k:
            rec(idx, k)
            out.append(_STEP_LEFT)
        else:
            rec(idx - k, tot - k)
            out.append(_STEP_RIGHT)

    rec(index, total)
    return out


@jax.jit
def _verify_program(leaf, aunts, flags):
    """leaf (32, K) u8; aunts (D, 32, K) u8; flags (D, K) i32.
    Returns computed roots (32, K)."""

    def step(h, xs):
        aunt, flag = xs
        as_left = S.inner_hash_batch(h, aunt)
        as_right = S.inner_hash_batch(aunt, h)
        h = jnp.where(flag[None, :] == _STEP_LEFT, as_left, h)
        h = jnp.where(flag[None, :] == _STEP_RIGHT, as_right, h)
        return h, None

    root, _ = lax.scan(step, leaf, (aunts, flags))
    return root


def verify_proofs(
    proofs: Sequence,  # crypto.merkle.Proof
    root_hash: bytes,
) -> np.ndarray:
    """Batch-verify K inclusion proofs against one root. Returns a
    bool bitmap (structurally invalid proofs are False, not raised —
    BatchVerifier semantics, crypto/crypto.go:56-60)."""
    k = len(proofs)
    if k == 0:
        return np.zeros(0, dtype=bool)
    sides: List[Optional[List[int]]] = []
    max_d = 0
    for p in proofs:
        if (
            p.index < 0
            or p.total <= 0
            or p.index >= p.total
            or len(p.leaf_hash) != 32
            or any(len(a) != 32 for a in p.aunts)
        ):
            sides.append(None)
            continue
        s = _sides_for(p.index, p.total)
        if len(s) != len(p.aunts):
            sides.append(None)
            continue
        sides.append(s)
        max_d = max(max_d, len(s))
    structural_ok = np.array([s is not None for s in sides], dtype=bool)
    if not structural_ok.any():
        return structural_ok
    kb = _bucket(k)  # pad batch and depth to bound compiled shapes
    db = _bucket(max(max_d, 1))
    leaf = np.zeros((32, kb), dtype=np.uint8)
    aunts = np.zeros((db, 32, kb), dtype=np.uint8)
    flags = np.full((db, kb), _STEP_NOOP, dtype=np.int32)
    for i, (p, s) in enumerate(zip(proofs, sides)):
        if s is None:
            continue
        leaf[:, i] = np.frombuffer(p.leaf_hash, dtype=np.uint8)
        for d, (aunt, side) in enumerate(zip(p.aunts, s)):
            aunts[d, :, i] = np.frombuffer(aunt, dtype=np.uint8)
            flags[d, i] = side
    roots = np.asarray(
        _verify_program(
            jnp.asarray(leaf), jnp.asarray(aunts), jnp.asarray(flags)
        )
    )[:, :k]
    want = np.frombuffer(root_hash, dtype=np.uint8)[:, None]
    return structural_ok & (roots == want).all(axis=0)


# -- crypto.merkle device hook ---------------------------------------------

_installed: Optional[int] = None
_stats = {"roots": 0, "leaves": 0, "proofs": 0}


def installed() -> Optional[int]:
    return _installed


def stats() -> dict:
    return dict(_stats)


def install(min_leaves: int = 512) -> None:
    """Route large merkle roots and proof batches through the device
    (the hook crypto/merkle.py consults; mirrors
    crypto/tpu_verifier.install)."""
    global _installed
    from ..crypto import merkle as cm

    _installed = min_leaves

    def _root_hook(leaf_hashes: List[bytes]) -> Optional[bytes]:
        if len(leaf_hashes) < min_leaves:
            return None
        _stats["roots"] += 1
        _stats["leaves"] += len(leaf_hashes)
        with trace.span("merkle_device_root", leaves=len(leaf_hashes)):
            return tree_root(leaf_hashes)

    def _proofs_hook(proofs, root_hash: bytes):
        if len(proofs) < max(min_leaves // 8, 2):
            return None
        _stats["proofs"] += len(proofs)
        with trace.span("merkle_device_proofs", proofs=len(proofs)):
            return verify_proofs(proofs, root_hash)

    cm._device_root_hook = _root_hook
    cm._device_proofs_hook = _proofs_hook


def uninstall() -> None:
    global _installed
    from ..crypto import merkle as cm

    _installed = None
    cm._device_root_hook = None
    cm._device_proofs_hook = None
