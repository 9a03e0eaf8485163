"""Batched SHA-512 as an XLA program (uint32 half-word lanes).

The device hash behind ed25519's k = SHA512(R || A || M) scalar prep
(reference: the one-call verify boundary crypto/ed25519/ed25519.go:202-237
hides this inside curve25519-voi) — with it, the host side of a batch
verify is byte joins only (ops/ed25519_kernel.py dispatch).

TPUs have no 64-bit integer units, so every 64-bit word is an
(hi, lo) pair of uint32 planes: arrays carry an extra axis of size 2
right before the batch axis ((16, 2, N) blocks, (8, 2, N) states).
Rotations split across the halves at trace time (constant shift
counts); additions ripple one carry from lo to hi. Rounds and schedule
are lax.scan loops over a ~40-op body, matching the sha256 kernel's
compile-size strategy (ops/sha256_kernel.py).

Fixed message lengths compile one program per (length, batch-bucket):
padding is laid out at trace time. Callers group variable-length
batches by length (the ed25519 verifier does).
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["sha512_fixed"]

_K64 = [
    0x428A2F98D728AE22, 0x7137449123EF65CD, 0xB5C0FBCFEC4D3B2F,
    0xE9B5DBA58189DBBC, 0x3956C25BF348B538, 0x59F111F1B605D019,
    0x923F82A4AF194F9B, 0xAB1C5ED5DA6D8118, 0xD807AA98A3030242,
    0x12835B0145706FBE, 0x243185BE4EE4B28C, 0x550C7DC3D5FFB4E2,
    0x72BE5D74F27B896F, 0x80DEB1FE3B1696B1, 0x9BDC06A725C71235,
    0xC19BF174CF692694, 0xE49B69C19EF14AD2, 0xEFBE4786384F25E3,
    0x0FC19DC68B8CD5B5, 0x240CA1CC77AC9C65, 0x2DE92C6F592B0275,
    0x4A7484AA6EA6E483, 0x5CB0A9DCBD41FBD4, 0x76F988DA831153B5,
    0x983E5152EE66DFAB, 0xA831C66D2DB43210, 0xB00327C898FB213F,
    0xBF597FC7BEEF0EE4, 0xC6E00BF33DA88FC2, 0xD5A79147930AA725,
    0x06CA6351E003826F, 0x142929670A0E6E70, 0x27B70A8546D22FFC,
    0x2E1B21385C26C926, 0x4D2C6DFC5AC42AED, 0x53380D139D95B3DF,
    0x650A73548BAF63DE, 0x766A0ABB3C77B2A8, 0x81C2C92E47EDAEE6,
    0x92722C851482353B, 0xA2BFE8A14CF10364, 0xA81A664BBC423001,
    0xC24B8B70D0F89791, 0xC76C51A30654BE30, 0xD192E819D6EF5218,
    0xD69906245565A910, 0xF40E35855771202A, 0x106AA07032BBD1B8,
    0x19A4C116B8D2D0C8, 0x1E376C085141AB53, 0x2748774CDF8EEB99,
    0x34B0BCB5E19B48A8, 0x391C0CB3C5C95A63, 0x4ED8AA4AE3418ACB,
    0x5B9CCA4F7763E373, 0x682E6FF3D6B2B8A3, 0x748F82EE5DEFB2FC,
    0x78A5636F43172F60, 0x84C87814A1F0AB72, 0x8CC702081A6439EC,
    0x90BEFFFA23631E28, 0xA4506CEBDE82BDE9, 0xBEF9A3F7B2C67915,
    0xC67178F2E372532B, 0xCA273ECEEA26619C, 0xD186B8C721C0C207,
    0xEADA7DD6CDE0EB1E, 0xF57D4F7FEE6ED178, 0x06F067AA72176FBA,
    0x0A637DC5A2C898A6, 0x113F9804BEF90DAE, 0x1B710B35131C471B,
    0x28DB77F523047D84, 0x32CAAB7B40C72493, 0x3C9EBE0A15C9BEBC,
    0x431D67C49C100D4C, 0x4CC5D4BECB3E42B6, 0x597F299CFC657E2A,
    0x5FCB6FAB3AD6FAEC, 0x6C44198C4A475817,
]
# (80, 2) -> hi/lo planes
_K = np.array(
    [[(k >> 32) & 0xFFFFFFFF, k & 0xFFFFFFFF] for k in _K64],
    dtype=np.uint32,
)

_H0_64 = [
    0x6A09E667F3BCC908, 0xBB67AE8584CAA73B, 0x3C6EF372FE94F82B,
    0xA54FF53A5F1D36F1, 0x510E527FADE682D1, 0x9B05688C2B3E6C1F,
    0x1F83D9ABFB41BD6B, 0x5BE0CD19137E2179,
]
_H0 = np.array(
    [[(h >> 32) & 0xFFFFFFFF, h & 0xFFFFFFFF] for h in _H0_64],
    dtype=np.uint32,
)


def _rotr(w: jnp.ndarray, n: int) -> jnp.ndarray:
    """Rotate-right of (..., 2, N) uint32 hi/lo pairs by constant n."""
    hi = w[..., 0, :]
    lo = w[..., 1, :]
    if n == 32:
        return jnp.stack([lo, hi], axis=-2)
    if n > 32:
        hi, lo = lo, hi
        n -= 32
    h = (hi >> np.uint32(n)) | (lo << np.uint32(32 - n))
    l = (lo >> np.uint32(n)) | (hi << np.uint32(32 - n))
    return jnp.stack([h, l], axis=-2)


def _shr(w: jnp.ndarray, n: int) -> jnp.ndarray:
    """Logical right shift of hi/lo pairs by constant n < 32."""
    hi = w[..., 0, :]
    lo = w[..., 1, :]
    h = hi >> np.uint32(n)
    l = (lo >> np.uint32(n)) | (hi << np.uint32(32 - n))
    return jnp.stack([h, l], axis=-2)


def _add(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """64-bit add of hi/lo pairs (uint32 wrap + one carry ripple)."""
    lo = a[..., 1, :] + b[..., 1, :]
    carry = (lo < a[..., 1, :]).astype(jnp.uint32)
    hi = a[..., 0, :] + b[..., 0, :] + carry
    return jnp.stack([hi, lo], axis=-2)


def _compress(state: jnp.ndarray, block: jnp.ndarray) -> jnp.ndarray:
    """One SHA-512 compression: state (8, 2, N), block (16, 2, N).

    Two trace-time forms, chosen by backend:

    - TPU: fully unrolled (Python loops, ~4.5k vector ops). A lax.scan
      body this small serializes 144 tiny device loops XLA cannot fuse
      across — measured at 8192 lanes the scan form cost ~24% of total
      ed25519 verify throughput; unrolled it fuses into a handful of
      kernels and disappears into the noise.
    - CPU: the scan form. The CPU backend compiles the unrolled chain
      in ~2-4 s per (length, bucket) program, which multiplies across
      the test suite's many message lengths; the scan compiles the
      ~40-op body once and CPU throughput is not the target."""
    if jax.default_backend() != "tpu":
        return _compress_scan(state, block)
    w = [block[i] for i in range(16)]
    for t in range(16, 80):
        w15 = w[t - 15]
        w2 = w[t - 2]
        s0 = _rotr(w15, 1) ^ _rotr(w15, 8) ^ _shr(w15, 7)
        s1 = _rotr(w2, 19) ^ _rotr(w2, 61) ^ _shr(w2, 6)
        w.append(_add(_add(w[t - 16], s0), _add(w[t - 7], s1)))

    n = state.shape[-1]
    a, b, c, d, e, f, g, h = (state[i] for i in range(8))
    for t in range(80):
        kt = jnp.broadcast_to(jnp.asarray(_K[t])[:, None], (2, n))
        s1 = _rotr(e, 14) ^ _rotr(e, 18) ^ _rotr(e, 41)
        ch = (e & f) ^ (~e & g)
        t1 = _add(_add(h, s1), _add(ch, _add(kt, w[t])))
        s0 = _rotr(a, 28) ^ _rotr(a, 34) ^ _rotr(a, 39)
        maj = (a & b) ^ (a & c) ^ (b & c)
        h, g, f, e, d, c, b, a = (
            g, f, e, _add(d, t1), c, b, a, _add(t1, _add(s0, maj)),
        )
    out = jnp.stack([a, b, c, d, e, f, g, h], axis=0)
    return jnp.stack(
        [_add(state[i], out[i]) for i in range(8)], axis=0
    )


def _compress_scan(state: jnp.ndarray, block: jnp.ndarray) -> jnp.ndarray:
    """Scan-form compression (see _compress): one ~40-op body, 144
    sequential steps. Compile-cheap; serialization-bound on TPU."""

    def sched_body(last16, _):
        w15 = last16[1]
        w2 = last16[14]
        s0 = _rotr(w15, 1) ^ _rotr(w15, 8) ^ _shr(w15, 7)
        s1 = _rotr(w2, 19) ^ _rotr(w2, 61) ^ _shr(w2, 6)
        wt = _add(_add(last16[0], s0), _add(last16[9], s1))
        return jnp.concatenate([last16[1:], wt[None]], axis=0), wt

    _, w_ext = lax.scan(sched_body, block, None, length=64)
    w_all = jnp.concatenate([block, w_ext], axis=0)  # (80, 2, N)

    n = state.shape[-1]
    k_bcast = jnp.broadcast_to(
        jnp.asarray(_K)[:, :, None], (80, 2, n)
    )

    def round_body(st, xs):
        wt, kt = xs
        a, b, c, d, e, f, g, h = (st[i] for i in range(8))
        s1 = _rotr(e, 14) ^ _rotr(e, 18) ^ _rotr(e, 41)
        ch = (e & f) ^ (~e & g)
        t1 = _add(_add(h, s1), _add(ch, _add(kt, wt)))
        s0 = _rotr(a, 28) ^ _rotr(a, 34) ^ _rotr(a, 39)
        maj = (a & b) ^ (a & c) ^ (b & c)
        return jnp.stack(
            [_add(t1, _add(s0, maj)), a, b, c, _add(d, t1), e, f, g],
            axis=0,
        ), None

    out, _ = lax.scan(round_body, state, (w_all, k_bcast))
    return jnp.stack(
        [_add(state[i], out[i]) for i in range(8)], axis=0
    )


def sha512_fixed(data: jnp.ndarray) -> jnp.ndarray:
    """SHA-512 of N equal-length messages: (L, N) uint8 -> (64, N).

    L is static: merkle-damgard padding (0x80, zeros, 128-bit bit
    length) is laid out at trace time."""
    length, n = data.shape
    bitlen = length * 8
    nblocks = (length + 17 + 127) // 128
    padded_len = nblocks * 128
    pad_rows = [jnp.full((1, n), 0x80, dtype=jnp.uint8)]
    zeros = padded_len - length - 1 - 8
    if zeros:
        # the upper 8 of the 16 length bytes are always zero here
        # (messages < 2^61 bytes), so they fold into the zero run
        pad_rows.append(jnp.zeros((zeros, n), dtype=jnp.uint8))
    len_bytes = np.array(
        [(bitlen >> (8 * (7 - i))) & 0xFF for i in range(8)],
        dtype=np.uint8,
    )
    pad_rows.append(
        jnp.broadcast_to(jnp.asarray(len_bytes)[:, None], (8, n))
    )
    full = jnp.concatenate([data.astype(jnp.uint8)] + pad_rows, axis=0)
    full = full.astype(jnp.uint32)
    # (nblocks, 16, 2, N): big-endian bytes -> hi/lo uint32 planes
    octets = full.reshape(nblocks, 16, 2, 4, n)
    words = (
        (octets[..., 0, :] << np.uint32(24))
        | (octets[..., 1, :] << np.uint32(16))
        | (octets[..., 2, :] << np.uint32(8))
        | octets[..., 3, :]
    )
    state = jnp.broadcast_to(
        jnp.asarray(_H0)[:, :, None], (8, 2, n)
    ).astype(jnp.uint32)
    with jax.named_scope("sha512_blocks"):
        for b in range(nblocks):
            state = _compress(state, words[b])
    # big-endian unpack: (8, 2, N) words -> (64, N) bytes
    shifts = np.array([24, 16, 8, 0], dtype=np.uint32)
    out = (state[:, :, None, :] >> jnp.asarray(shifts)[None, None, :, None]) & np.uint32(0xFF)
    return out.reshape(64, n).astype(jnp.uint8)
