"""Batched sr25519 (schnorrkel over ristretto255) verification on TPU.

The device program takes a batch of (pubkey, signature, challenge-scalar)
and returns a validity bitmap — the TPU replacement for the reference's
sr25519 batch verifier (crypto/sr25519/batch.go via curve25519-voi)
behind the same crypto.BatchVerifier seam (crypto/crypto.go:53-61).

Verification equation (schnorrkel sign.rs, cofactorless — ristretto255
is prime order):

    [s]B - [k]A == R   (as ristretto255 group elements)

with k the merlin-transcript Fiat-Shamir challenge. Its 64 wide bytes
come from a device program of their own where a launch is wide enough
to pay for one (ops/merlin_kernel.py, one program a message length,
MERLIN_DEVICE_LANES), and from the host's batched transcripts below
that (crypto/sr25519.py challenge_wides over the native keccakf);
everything from the wide challenge onward runs in the tile:

    k = wide mod L (the reduction the ed25519 tile applies to SHA-512)
    ristretto decode of A and R (RFC 9496 §4.3.1, incl. canonicity)
    s < L canonicality + v1 marker-bit check
    [s]B - [k]A via the shared Horner dual-mult
        (ops/ed25519_kernel.dual_mult_sb_minus_ka — same -A table,
        same niels B table, same 64-window radix-16 scan)
    ristretto equality (RFC 9496 §4.4), projective so no inversions

Layout: batch-minor throughout, matching field25519's layout note.
Differential oracle: crypto/ristretto.py (Python ints, RFC 9496
vectors) through crypto/sr25519.py's verify_signature.
"""

from __future__ import annotations

import threading
from typing import Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from ..config import MERLIN_DEVICE_LANES
from ..crypto import ed25519_math as em
from ..libs import trace
from . import field25519 as F
from .ed25519_kernel import (
    _TOPCLEAR,
    _bytes_const,
    _fe_from_bytes_dev,
    _lt_const_dev,
    _mod_l_dev,
    _nibbles_dev,
    _s_lt_l_dev,
    dual_mult_sb_minus_ka,
)
from .merlin_kernel import merlin_challenge
from .verifier import ROWS, BucketedVerifier, _join_cols

__all__ = ["Sr25519Verifier", "batch_verify_host"]

_P8 = _bytes_const(em.P, 32)  # field prime as 32 LE byte limbs
_SQRT_M1_INT = em.SQRT_M1
_D_INT = em.D


def _abs_dev(x: jnp.ndarray) -> jnp.ndarray:
    """CT_ABS (RFC 9496 §4.1): negate iff the canonical form is odd."""
    parity = F.canonical(x)[..., 0, :] & 1
    return F.select(parity == 1, F.neg(x), x)


def _is_negative_dev(x: jnp.ndarray) -> jnp.ndarray:
    return (F.canonical(x)[..., 0, :] & 1) == 1


def _sqrt_ratio_m1_dev(
    u: jnp.ndarray, v: jnp.ndarray
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """SQRT_RATIO_M1 (RFC 9496 §4.2), batched.

    Returns (was_square (N,), r (NLIMBS, N)) with r = |sqrt(u/v)| when
    u/v is square, else |sqrt(i*u/v)|. The exponentiation reuses the
    (p-5)/8 addition chain (254 squarings) from the ed25519 kernel's
    decompression path."""
    v2 = F.sqr(v)
    v3 = F.mul(v2, v)
    v7 = F.mul(F.sqr(v3), v)
    r = F.mul(F.mul(u, v3), F.pow_p58(F.mul(u, v7)))
    check = F.mul(v, F.sqr(r))
    u_neg = F.neg(u)
    sqrt_m1 = jnp.broadcast_to(F.const_limbs(_SQRT_M1_INT), u.shape)
    correct = F.eq(check, u)
    flipped = F.eq(check, u_neg)
    flipped_i = F.eq(check, F.mul(u_neg, sqrt_m1))
    r = F.select(flipped | flipped_i, F.mul(r, sqrt_m1), r)
    return correct | flipped, _abs_dev(r)


def ristretto_decode_dev(
    b: jnp.ndarray,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Batched ristretto255 decode (RFC 9496 §4.3.1).

    b: (32, N) int32 byte rows. Returns (point (4, NLIMBS, N) extended
    edwards coords, ok (N,) bool). Invalid encodings (non-canonical,
    negative, non-square, t negative, y = 0) yield ok = False with a
    bounded garbage point that flows safely through the curve math."""
    nonneg = (b[0] & 1) == 0
    canon = _lt_const_dev(b, _P8)  # value < p (bit 255 set fails too)
    s = _fe_from_bytes_dev(
        b & _TOPCLEAR
    )  # mask bit 255 to keep limb bounds; canon already rejects it
    one = jnp.broadcast_to(F.const_limbs(1), s.shape)
    ss = F.sqr(s)
    u1 = F.sub(one, ss)
    u2 = F.add(one, ss)
    u2_sqr = F.sqr(u2)
    d = jnp.broadcast_to(F.const_limbs(_D_INT), s.shape)
    v = F.sub(F.neg(F.mul(d, F.sqr(u1))), u2_sqr)
    was_square, invsqrt = _sqrt_ratio_m1_dev(one, F.mul(v, u2_sqr))
    den_x = F.mul(invsqrt, u2)
    den_y = F.mul(F.mul(invsqrt, den_x), v)
    x = _abs_dev(F.mul(F.add(s, s), den_x))
    y = F.mul(u1, den_y)
    t = F.mul(x, y)
    ok = (
        was_square
        & ~_is_negative_dev(t)
        & ~F.is_zero(y)
        & nonneg
        & canon
    )
    pt = jnp.stack([x, y, one, t], axis=-3)
    return pt, ok


def _ristretto_eq_dev(p3: jnp.ndarray, q: jnp.ndarray) -> jnp.ndarray:
    """Ristretto equality (RFC 9496 §4.4): X1*Y2 == Y1*X2 or
    Y1*Y2 == X1*X2. Projective: the Z factors multiply both sides of
    each equation identically, so T-less (X, Y, Z) stacks suffice.
    p3: (..., >=2, NLIMBS, N) stack; q: same (extra coords ignored)."""
    X1, Y1 = p3[..., 0, :, :], p3[..., 1, :, :]
    X2, Y2 = q[..., 0, :, :], q[..., 1, :, :]
    lhs = jnp.stack([X1, Y1], axis=-3)
    rhs = jnp.stack([Y2, X2], axis=-3)
    cross = F.mul(lhs, rhs)  # X1*Y2, Y1*X2
    eq1 = F.eq(cross[..., 0, :, :], cross[..., 1, :, :])
    straight = F.mul(lhs, jnp.stack([X2, Y2], axis=-3))  # X1*X2, Y1*Y2
    eq2 = F.eq(straight[..., 0, :, :], straight[..., 1, :, :])
    return eq1 | eq2


def _verify_tile_sr(pk_b, sig_b, c_b) -> jnp.ndarray:
    """The full sr25519 device program: byte rows in, bitmap out.

    pk_b (32, N) ristretto pubkey bytes; sig_b (64, N) R || s with the
    schnorrkel v1 marker in bit 511; c_b (64, N) LE bytes of the wide
    merlin challenge, from the device or the host alike. Returns (N,)
    bool."""
    pk = pk_b.astype(jnp.int32)
    sig = sig_b.astype(jnp.int32)
    wide = c_b.astype(jnp.int32)
    # stage names: one vocabulary with ed25519_kernel._verify_tile
    with jax.named_scope("scalar_prep"):
        marker_ok = (sig[63] >> 7) == 1  # schnorrkel v1 marker bit
        s = sig[32:] & _TOPCLEAR
        s_ok = _s_lt_l_dev(s)
        dS = _nibbles_dev(s)
        dk = _nibbles_dev(_mod_l_dev(wide))
    with jax.named_scope("ristretto_decode"):
        A, okA = ristretto_decode_dev(pk)
        R, okR = ristretto_decode_dev(sig[:32])
    acc = dual_mult_sb_minus_ka(A, dS, dk)  # [s]B - [k]A, T-less
    with jax.named_scope("final_check"):
        return _ristretto_eq_dev(acc, R) & okA & okR & s_ok & marker_ok


_MERLIN = jax.jit(merlin_challenge)


def _merlin_rows(pubkeys, msgs, sigs, pad: int) -> np.ndarray:
    """(len + 64, n + pad) rows of M || A || R, the merlin program's
    operand, for messages of one length (three joins: a third less
    host time than one of concatenated rows)."""
    rows = [
        _join_cols(pubkeys, 32, pad),
        _join_cols([sig[:32] for sig in sigs], 32, pad),
    ]
    if len(msgs[0]):  # an empty message has no rows to join
        rows.insert(0, _join_cols(msgs, len(msgs[0]), pad))
    return np.concatenate(rows)


class Sr25519Verifier(BucketedVerifier):
    """Bucketed sr25519 batch verifier: the shared body with
    `_verify_tile_sr` as its program and the wide merlin challenges as
    the third operand, made by a program of their own on the device at
    MERLIN_DEVICE_LANES and up, by the host below."""

    _TILE = staticmethod(jax.jit(_verify_tile_sr))

    def host_operand(self, n: int) -> bool:
        """Below MERLIN_DEVICE_LANES the transcripts are host work."""
        return self._bucket(n) < MERLIN_DEVICE_LANES

    def _operand(self, pubkeys, msgs, sigs, bucket, packed):
        """(64, bucket) rows of the wide merlin challenges for one
        message length, under a `merlin_challenges` span saying which
        side made them (`form`) and for how many rows (`n`). At
        MERLIN_DEVICE_LANES and wider, one launch of the merlin program
        (the span holds its rows' join and the launch), whose
        challenges stay on the device; narrower, the host's batched
        transcripts (crypto/sr25519.py challenge_wides, one native
        keccakf_n call a transcript step)."""
        n = len(msgs)
        if bucket >= MERLIN_DEVICE_LANES:
            with trace.span("merlin_challenges", form="device", n=n):
                rows = _merlin_rows(pubkeys, msgs, sigs, bucket - n)
                return self._launch(_MERLIN, ROWS, bucket, rows)
        from ..crypto.sr25519 import challenge_wides

        wide = np.zeros((64, bucket), dtype=np.uint8)
        with trace.span("merlin_challenges", form="host", n=n):
            rs = [sig[:32] for sig in sigs]
            wide[:, :n] = challenge_wides(pubkeys, msgs, rs).T
        return wide


_DEFAULT: Optional[Sr25519Verifier] = None
_DEFAULT_LOCK = threading.Lock()


def default_verifier() -> Sr25519Verifier:
    """The shared module verifier (see ed25519_kernel.default_verifier)."""
    global _DEFAULT
    if _DEFAULT is None:
        # double-checked: the first calls race in from the asyncio loop
        # AND the breaker probe thread (tmrace), and a losing duplicate
        # construction is not just waste — each instance carries its
        # own compiled-program cache, so consensus traffic landing on a
        # discarded instance would recompile every bucket
        with _DEFAULT_LOCK:
            if _DEFAULT is None:
                _DEFAULT = Sr25519Verifier()
    return _DEFAULT


def batch_verify_host(pubkeys, msgs, sigs) -> np.ndarray:
    """Module-level convenience using the shared verifier instance."""
    return default_verifier().verify(pubkeys, msgs, sigs)
