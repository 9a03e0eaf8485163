"""Batched ed25519 verification as a single XLA program.

The device program takes a whole batch of (pubkey, R, S-digits, k-digits)
and returns a validity bitmap — this is the TPU replacement for the
reference's curve25519-voi batch verifier behind crypto.BatchVerifier
(reference: crypto/ed25519/ed25519.go:202-237, crypto/crypto.go:53-61).

Verification equation (ZIP-215, cofactored — matching
crypto/ed25519/ed25519.go:27-29 and the host oracle in
crypto/ed25519_math.py):

    [8]([S]B - [k]A - R) == identity,  k = SHA512(R || A || M) mod L

Device-side strategy (one lax.scan over 64 radix-16 windows, fixed trip
count, no data-dependent control flow):

    acc <- 16*acc + dk_w * (-A) + dS_w * B

i.e. Horner evaluation for the variable-base term using a per-signature
9-entry cached table of -A built on device (digits recoded to signed
[-8, 7]; negative entries are the free cached negation), while the
fixed-base term reuses a constant 9-entry niels table of B at every
window — scaling by 16^w happens for free inside the shared Horner
doublings. Then add -R, triple-double (x8 cofactor), and test the
projective identity.

Layout: all device arrays are batch-minor ((NLIMBS, N) field elements,
(4, NLIMBS, N) points — see field25519's layout note; batch-major
stranded ~85% of the VPU lanes). Table indexing is a 9-way one-hot
select (compare + masked accumulate), not a gather: per-lane dynamic
gathers serialize on TPU, while the one-hot form is pure vector ALU.

Scalar prep (SHA-512 of R||A||M, reduction mod L, nibble decomposition)
also runs on device: digests via ops/sha512_kernel.py per
message-length group (sign-bytes in a Commit share one length, so the
common case is a single fused group with no host round-trip), the rest
inside the verify program. Host work is byte joins only.

Shapes are bucketed (pad to the next configured bucket) so XLA compiles a
handful of programs once and reuses them for every Commit size.
"""

from __future__ import annotations

import threading
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from ..crypto import ed25519_math as em
from . import edwards as E
from . import field25519 as F
from .sha512_kernel import sha512_fixed
from .verifier import (
    DEFAULT_BUCKET_SIZES,
    ROWS,
    BucketedVerifier,
    _join_cols,
    bucket_for,
)

__all__ = [
    "Ed25519Verifier",
    "batch_verify_host",
    "dual_mult_sb_minus_ka",
    "walk_form",
    "DEFAULT_BUCKET_SIZES",
    "bucket_for",
]

_TB0 = None  # lazy (9, 4, NLIMBS, 1) fixed-base niels table (host numpy;
# converted per use so jit tracing never captures a cached tracer)


def _tb0():
    global _TB0
    if _TB0 is None:
        _TB0 = E.niels_table_b()
    return jnp.asarray(_TB0)


def _build_neg_a_table(A: jnp.ndarray) -> jnp.ndarray:
    """(4, L, N) extended -A -> (9, 4, L, N) cached table of j*(-A),
    j = 0..8 — the signed-digit half-table (digits recoded to [-8, 7],
    negative entries produced by the free cached negation in
    _select_signed). 4 doublings + 3 additions vs the 14 point ops of
    the old full [0, 15] table."""
    negA = E.negate(A)
    cached_negA = E.cache_point(negA)
    e = {0: E.identity(A.shape[-1]), 1: negA}
    e[2] = E.point_double(e[1])
    e[3] = E.point_add_cached(e[2], cached_negA)
    e[4] = E.point_double(e[2])
    e[5] = E.point_add_cached(e[4], cached_negA)
    e[6] = E.point_double(e[3])
    e[7] = E.point_add_cached(e[6], cached_negA)
    e[8] = E.point_double(e[4])
    cached = [E.cache_point(e[j]) for j in range(9)]
    return jnp.stack(cached, axis=0)  # (9, 4, L, N)


def _onehot_select(table: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """table (K, 4, L, {N|1}), idx (N,) -> (4, L, N) via K-way masked
    accumulate (no per-lane gather). broadcasted_iota and not arange:
    the fused walk's compiler (Mosaic, ops/fused_walk.py) rejects a
    rank-1 iota."""
    k = table.shape[0]
    js = lax.broadcasted_iota(idx.dtype, (k, idx.shape[0]), 0)
    mask = (idx[None, :] == js).astype(table.dtype)  # (K, N)
    return jnp.sum(table * mask[:, None, None, :], axis=0)


def _recode_signed(d: jnp.ndarray) -> jnp.ndarray:
    """(64, N) radix-16 digits in [0, 15], LE -> same value as signed
    digits in [-8, 7]: e_i = t_i - 16*(t_i >= 8), t_i = d_i + c_i,
    c_{i+1} = (t_i >= 8). The carry recurrence is generate/propagate
    (g = d >= 8, p = d == 7), solved in log2(64) Kogge-Stone steps along
    the digit axis — no sequential 64-chain in the graph.

    A carry out of digit 63 is dropped; that loses 2^256, which only
    happens for S >= 2^256 - 8*16^63 — such S fail the S < L
    canonicality check and are already reported invalid, so the curve
    result is irrelevant (same contract as the rest of the math on
    malformed inputs).

    The generate/propagate lattice is int32 0/1 and not bool, for the
    same reason as _onehot_select's iota: Mosaic cannot concatenate or
    shift i1 vregs ('Invalid vector register cast', found compiling
    for a described v5e)."""
    g = (d >= 8).astype(d.dtype)
    p = (d == 7).astype(d.dtype)
    shift = 1
    while shift < d.shape[0]:
        zeros = jnp.zeros_like(g[:shift])
        g = g | (p & jnp.concatenate([zeros, g[:-shift]], axis=0))
        p = p & jnp.concatenate([zeros, p[:-shift]], axis=0)
        shift *= 2
    c = jnp.concatenate([jnp.zeros_like(g[:1]), g[:-1]], axis=0)
    t = d + c
    return t - 16 * (t >= 8).astype(d.dtype)


def _select_signed(table9: jnp.ndarray, e: jnp.ndarray) -> jnp.ndarray:
    """table9 (9, 4, L, {N|1}) cached-form entries for j*P, j = 0..8;
    e (N,) signed digit in [-8, 8] -> (4, L, N) cached |e|*P, negated
    when e < 0 (cached negation = swap (Y-X, Y+X), negate 2dT — no
    multiplies, edwards.negate_cached's identity applied post-select)."""
    sel = _onehot_select(table9, jnp.abs(e))
    sgn = (e < 0)[None, None, :]
    return jnp.where(sgn, E.negate_cached(sel), sel)


def walk_form() -> str:
    """Which form the 64-window walk of a tile program traced now takes:
    "fused", one Pallas kernel (ops/fused_walk.py), where the backend
    is a TPU; "scan", the lax.scan program below, anywhere else (the
    observable ops/sha512_kernel.py picks its unrolled form from). The
    one place that picks; what a launched program then holds, its
    verifier reads off the program (ops/verifier.py `_launch`)."""
    return "fused" if jax.default_backend() == "tpu" else "scan"


def dual_mult_sb_minus_ka(
    A: jnp.ndarray, dS: jnp.ndarray, dk: jnp.ndarray
) -> jnp.ndarray:
    """[S]B - [k]A as a T-less (3, NLIMBS, N) projective stack.

    A: (4, L, N) extended point; dS/dk: (64, N) int32 radix-16 digits,
    little-endian, in [0, 15] (recoded to signed [-8, 7] on device —
    half-size tables, negatives via the free cached negation). 64
    windows, most significant first, Horner
    `acc <- 16*acc + dk_w*(-A) + dS_w*B` with a per-signature 9-entry
    cached table of -A built on device and a constant niels table of B.
    Shared by the ed25519 program (cofactored compare follows) and the
    sr25519/ristretto program (ristretto equality follows,
    ops/sr25519_kernel.py).

    Two forms of the same arithmetic (walk_form): on a TPU the table,
    the recode and the walk are one Pallas kernel, 128 lanes a grid
    step with the intermediates in VMEM; elsewhere one lax.scan over
    pre-flipped digit rows, which is also the kernel's differential
    oracle.
    Either way the walk's operations carry the `dual_mult` scope the
    profiler's trace names them by."""
    if walk_form() == "fused":
        from .fused_walk import fused_walk

        with jax.named_scope("dual_mult"):
            return fused_walk(A, dS, dk)
    with jax.named_scope("neg_a_table"):
        TA = _build_neg_a_table(A)  # (9, 4, L, N)
    with jax.named_scope("scalar_prep"):
        dS = _recode_signed(dS)
        dk = _recode_signed(dk)
    tb0 = _tb0()  # (9, 4, L, 1)
    with jax.named_scope("dual_mult"):
        acc, _ = lax.scan(
            lambda acc, xs: (_window(acc, TA, tb0, *xs), None),
            _acc0(A),
            (jnp.flip(dS, axis=0), jnp.flip(dk, axis=0)),
        )
        return acc


def _acc0(A: jnp.ndarray) -> jnp.ndarray:
    """The walk's carry at its start: the identity as the T-less
    3-stack (X, Y, Z). Doublings never read T and the final comparison
    is projective, so only the ops feeding an addition materialize T
    (point ops drop the T output mul otherwise — 25% of each output
    multiply)."""
    return E.identity(A.shape[-1])[..., :3, :, :]


def _window(acc, TA, tb0, ds_w, dk_w) -> jnp.ndarray:
    """One window: acc <- 16*acc + dk_w*(-A) + ds_w*B, from the tables
    TA of -A and tb0 of B."""
    acc = lax.fori_loop(
        0, 3, lambda _i, a: E.point_double(a, with_t=False), acc
    )
    acc = E.point_double(acc)  # T feeds the addition below
    acc = E.point_add_cached(acc, _select_signed(TA, dk_w))
    return E.point_add_cached(acc, _select_signed(tb0, ds_w), with_t=False)


def dual_mult_rows(
    A: jnp.ndarray, dS: jnp.ndarray, dk: jnp.ndarray
) -> jnp.ndarray:
    """dual_mult_sb_minus_ka in the forms a TPU kernel's compiler
    (Mosaic) lowers: the body ops/fused_walk.py runs a 128-lane tile
    through. A lax.fori_loop whose window picks its digit row by a
    one-hot masked sum, because Mosaic lowers neither scan's
    dynamic_slice of xs nor jnp.flip's rev (64 more MACs a window are
    noise beside the point ops)."""
    TA = _build_neg_a_table(A)
    dS = _recode_signed(dS)
    dk = _recode_signed(dk)
    tb0 = _tb0()
    rows = lax.broadcasted_iota(dS.dtype, dS.shape, 0)  # (64, N)

    def body(w, acc):
        sel = (rows == 63 - w).astype(dS.dtype)  # most significant first
        return _window(
            acc, TA, tb0, jnp.sum(dS * sel, axis=0), jnp.sum(dk * sel, axis=0)
        )

    return lax.fori_loop(0, 64, body, _acc0(A))


def _scalar_mult_check(yA, signA, yR, signR, dS, dk) -> jnp.ndarray:
    """Core device program. Batch axis minor.

    yA/yR: (L, N) field elements; signA/signR: (N,) int32;
    dS/dk: (64, N) int32 radix-16 digits, little-endian.
    Returns ok: (N,) bool."""
    with jax.named_scope("decode_points"):
        A, okA = E.decompress(yA, signA)
        R, okR = E.decompress(yR, signR)
    acc = dual_mult_sb_minus_ka(A, dS, dk)
    with jax.named_scope("final_check"):
        # ZIP-215 cofactored equation, rearranged so nothing needs T:
        # [8]([S]B - [k]A) == [8]R  <=>  [8]([S]B - [k]A - R) == identity.
        for _ in range(3):  # cofactor 8, both sides
            acc = E.point_double(acc, with_t=False)
            R = E.point_double(R, with_t=False)
        # projective equality: X1 Z2 == X2 Z1 and Y1 Z2 == Y2 Z1
        lhs = jnp.stack([acc[..., 0, :, :], acc[..., 1, :, :]], axis=-3)
        rhs = jnp.stack([R[..., 0, :, :], R[..., 1, :, :]], axis=-3)
        z_acc = jnp.broadcast_to(acc[..., 2:3, :, :], lhs.shape)
        z_r = jnp.broadcast_to(R[..., 2:3, :, :], rhs.shape)
        cross_l = F.mul(lhs, z_r)
        cross_r = F.mul(rhs, z_acc)
        same = jnp.all(F.eq(cross_l, cross_r), axis=-2)
        return same & okA & okR


# -- device-side scalar prep --
#
# Everything between the SHA-512 digests and the curve math runs inside
# the same jitted program: byte -> limb unpacking, the reduction of the
# 512-bit digest mod L, S < L canonicality, and nibble decomposition.
# Host numpy versions of these were memory-bandwidth-bound (~6 us/sig);
# on device they are a rounding error next to the scalar multiplication.

_L_INT = em.L
_DELTA16_INT = 16 * (_L_INT - (1 << 252))  # 16*delta, 129 bits: 2^256 ≡ -16*delta


def _bytes_const(value: int, k: int) -> np.ndarray:
    """(k, 1) int32 radix-2^8 limbs of a constant."""
    return np.array(
        [(value >> (8 * i)) & 0xFF for i in range(k)], dtype=np.int32
    )[:, None]


_C8 = _bytes_const(_DELTA16_INT, 17)
_L8 = _bytes_const(_L_INT, 32)

# (32, 1) AND-mask clearing the sign bit of byte row 31 — the
# mask-select form of `.at[31].set(b & 0x7F)` (history: the whole tile
# was a Mosaic kernel once, at 8b8c9eb, and Mosaic lowered no scatter
# update; today's kernel, ops/fused_walk.py, holds the dual
# multiplication alone)
_TOPCLEAR = np.full((32, 1), 0xFF, dtype=np.int32)
_TOPCLEAR[31, 0] = 0x7F


def _fe_from_bytes_dev(b: jnp.ndarray) -> jnp.ndarray:
    """(32, N) int32 byte rows (bit 7 of row 31 already cleared) ->
    (NLIMBS, N) radix-2^13 limbs. The value (< 2^255) may exceed p —
    fine: field ops accept any normalized-limb representative
    (ZIP-215 accepts non-canonical y encodings)."""
    b = jnp.concatenate(
        [b, jnp.zeros((2, b.shape[1]), dtype=b.dtype)], axis=0
    )
    limbs = []
    for i in range(F.NLIMBS):
        s = F.RADIX * i
        b0 = s >> 3
        v = b[b0] + (b[b0 + 1] << 8) + (b[b0 + 2] << 16)
        limbs.append((v >> (s & 7)) & F.MASK)
    return jnp.stack(limbs, axis=0)


def _norm8(x: jnp.ndarray, passes: int) -> jnp.ndarray:
    """Radix-2^8 carry/borrow propagation, `passes` fixed rounds: lower
    limbs land in [0, 2^8), the top limb keeps the value's sign. A
    ripple can travel one limb per round, so `passes` >= rows for full
    canonicalization; 2 for loose bounding between multiplies."""
    zero = jnp.zeros_like(x[:1])
    for _ in range(passes):
        c = x[:-1] >> 8
        x = jnp.concatenate([x[:-1] - (c << 8), x[-1:]], axis=0)
        x = x + jnp.concatenate([zero, c], axis=0)
    return x


def _mul_c8(a: jnp.ndarray, width: int) -> jnp.ndarray:
    """(ka, N) signed radix-2^8 limbs x 16*delta -> (width, N) raw conv.
    Partial sums <= 17 * 2^9 * 2^8 < 2^22: safely int32."""
    ka = a.shape[0]
    acc = None
    for i in range(_C8.shape[0]):
        t = jnp.pad(a * _C8[i], ((i, width - i - ka), (0, 0)))
        acc = t if acc is None else acc + t
    return acc


def _mod_l_dev(d: jnp.ndarray) -> jnp.ndarray:
    """(64, N) int32 digest byte rows (LE) -> (32, N) canonical byte
    rows of the value mod L.

    Three folds of the high half with 2^256 ≡ -16*delta, then an
    approximate quotient by the top 4 bits and conditional +L fixes:
      fold1: < 2^512          -> |x| < 2^385  (50 rows)
      fold2: |hi| < 2^129     -> |x| < 2^259  (35 rows)
      (full normalize so lo is canonical)
      fold3: |hi| < 2^3       -> x in (-2^132, 2^256)  (33 rows)
      +L if negative; q = x >> 252 in [0,15]; x -= q*L -> (-16d, 2^252)
      +L if negative -> [0, L)."""
    x = d
    for split, width in ((32, 50), (32, 35)):
        lo = jnp.pad(
            x[:split], ((0, width - split), (0, 0))
        )
        x = _norm8(lo - _mul_c8(x[split:], width), 2)
    x = _norm8(x, 36)  # canonical lower limbs, signed top
    lo = jnp.pad(x[:32], ((0, 1), (0, 0)))
    x = _norm8(lo - _mul_c8(x[32:], 33), 34)
    l8_33 = jnp.asarray(np.pad(_L8, ((0, 1), (0, 0))))
    # x[32], not x[-1]: jnp lowers negative indices via dynamic_slice
    # (history, as _TOPCLEAR: Mosaic could not lower it)
    neg = (x[32] < 0).astype(jnp.int32)
    x = x + neg[None, :] * l8_33
    x = _norm8(x, 34)
    # value < 2^257: bits 252..255 in row 31, bit 256 in row 32
    q = (x[31] >> 4) + (x[32] << 4)
    x = x - q[None, :] * l8_33
    x = _norm8(x, 34)
    neg = (x[32] < 0).astype(jnp.int32)
    x = x + neg[None, :] * l8_33
    return _norm8(x, 34)[:32]


def _lt_const_dev(rows: jnp.ndarray, const8: np.ndarray) -> jnp.ndarray:
    """(32, N) canonical byte rows (LE) -> (N,) bool: value < const.
    Most-significant-byte-first scan; shared by the S < L check here
    and the ristretto s < p canonicity check (ops/sr25519_kernel.py).

    The decided/lt lattice is int32 0/1 and not bool (history, as
    _TOPCLEAR: a scalar-True jnp.where operand became an i8
    constant that Mosaic had to truncate to i1, 'Unsupported target
    bitwidth for truncation')."""
    cb = np.asarray(const8)[:, 0]
    lt = jnp.zeros(rows.shape[1], dtype=jnp.int32)
    decided = jnp.zeros(rows.shape[1], dtype=jnp.int32)
    for i in range(31, -1, -1):
        lo = (rows[i] < int(cb[i])).astype(jnp.int32)
        hi = (rows[i] > int(cb[i])).astype(jnp.int32)
        lt = lt | ((1 - decided) & lo)
        decided = decided | lo | hi
    return lt != 0


def _s_lt_l_dev(s: jnp.ndarray) -> jnp.ndarray:
    """(32, N) int32 byte rows of S (LE) -> (N,) bool: S < L
    (ZIP-215 rule 2: S must be canonical)."""
    return _lt_const_dev(s, _L8)


def _nibbles_dev(b: jnp.ndarray) -> jnp.ndarray:
    """(32, N) canonical byte rows -> (64, N) radix-16 digits, LE."""
    lo = b & 0x0F
    hi = b >> 4
    return jnp.stack([lo, hi], axis=1).reshape(64, b.shape[1])


def _verify_tile(pk_b, sig_b, dig_b) -> jnp.ndarray:
    """The full device program: byte rows in, validity bitmap out.

    pk_b (32, N), sig_b (64, N) uint8/int32 byte rows; dig_b (64, N)
    SHA-512(R||A||M) byte rows. Returns (N,) bool."""
    pk = pk_b.astype(jnp.int32)
    sig = sig_b.astype(jnp.int32)
    dig = dig_b.astype(jnp.int32)
    signA = pk[31] >> 7
    pk = pk & _TOPCLEAR
    r = sig[:32]
    signR = r[31] >> 7
    r = r & _TOPCLEAR
    s = sig[32:]
    # the stage names are one vocabulary with _verify_tile_sr
    # (ops/sr25519_kernel.py): decode_points / ristretto_decode,
    # scalar_prep, neg_a_table, dual_mult, final_check
    with jax.named_scope("decode_points"):
        yA = _fe_from_bytes_dev(pk)
        yR = _fe_from_bytes_dev(r)
    with jax.named_scope("scalar_prep"):
        s_ok = _s_lt_l_dev(s)
        dS = _nibbles_dev(s)
        dk = _nibbles_dev(_mod_l_dev(dig))
    return _scalar_mult_check(yA, signA, yR, signR, dS, dk) & s_ok


# -- host side: only byte joins remain; the batch, bucket and launch
# logic is the shared BucketedVerifier (ops/verifier.py) --

_SHA512 = jax.jit(sha512_fixed)


class Ed25519Verifier(BucketedVerifier):
    """Bucketed ed25519 batch verifier: the shared body with
    `_verify_tile` as its program and SHA512(R || A || M), hashed on
    the device, as the third operand."""

    _TILE = staticmethod(jax.jit(_verify_tile))

    def _pack_operand(self, pubkeys, msgs, sigs, bucket):
        """(64 + len, bucket) rows of R || A || M when every message
        has one length — every sign-bytes in a Commit has the same
        shape — so that the digests stay on device, feeding the verify
        program without a host round-trip; None for mixed lengths."""
        if len(set(map(len, msgs))) != 1:
            return None
        return _join_cols(
            [
                sig[:32] + pk + msg
                for pk, msg, sig in zip(pubkeys, msgs, sigs)
            ],
            64 + len(msgs[0]),
            bucket - len(pubkeys),
        )

    def _operand(self, pubkeys, msgs, sigs, bucket, packed):
        """(64, bucket) rows of SHA512(R || A || M) for one message
        length: one launch (ops/sha512_kernel.py compiles one program
        per length) whose digests stay on the device. `packed` is the
        pre-image `pack_rows` joined; a length group joins its own."""
        if packed is None:
            packed = self._pack_operand(pubkeys, msgs, sigs, bucket)
        return self._launch(_SHA512, ROWS, bucket, packed)


_DEFAULT: Optional[Ed25519Verifier] = None
_DEFAULT_LOCK = threading.Lock()


def default_verifier() -> Ed25519Verifier:
    """The shared module verifier (compiled programs cached across the
    process; also the dispatch/gather handle source for the streaming
    batch seam, crypto/tpu_verifier.py)."""
    global _DEFAULT
    if _DEFAULT is None:
        # double-checked: the first calls race in from the asyncio loop
        # AND the breaker probe thread (tmrace), and a losing duplicate
        # construction is not just waste — each instance carries its
        # own compiled-program cache, so consensus traffic landing on a
        # discarded instance would recompile every bucket
        with _DEFAULT_LOCK:
            if _DEFAULT is None:
                _DEFAULT = Ed25519Verifier()
    return _DEFAULT


def batch_verify_host(pubkeys, msgs, sigs) -> np.ndarray:
    """Module-level convenience using the shared verifier instance."""
    return default_verifier().verify(pubkeys, msgs, sigs)
