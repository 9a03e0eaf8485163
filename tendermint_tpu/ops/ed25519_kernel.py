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

import hashlib
import threading
from typing import Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from ..crypto import ed25519_math as em
from ..libs import trace
from . import edwards as E
from . import field25519 as F

__all__ = [
    "Ed25519Verifier",
    "batch_verify_host",
    "dual_mult_sb_minus_ka",
    "DEFAULT_BUCKET_SIZES",
    "bucket_for",
]

# shared by the ed25519 and sr25519 verifiers (ops/sr25519_kernel.py)
# and the [tpu] config section: tune once, everything follows
from ..config import DEFAULT_BUCKET_SIZES  # noqa: E402


def bucket_for(n: int, sizes: Sequence[int]) -> int:
    """Smallest configured bucket >= n, or n itself when oversized."""
    for b in sizes:
        if n <= b:
            return b
    return n

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
    accumulate (no per-lane gather). broadcasted_iota (not arange):
    Mosaic rejects rank-1 iota."""
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

    The generate/propagate lattice is kept in int32 0/1, not bool:
    Mosaic cannot concatenate/shift i1 vregs (it bitcasts them to i32,
    which fails with 'Invalid vector register cast' — found via local
    AOT compile against a v5e topology)."""
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


def _select_signed(
    table9: jnp.ndarray, e: jnp.ndarray, mxu: bool = False
) -> jnp.ndarray:
    """table9 (9, 4, L, {N|1}) cached-form entries for j*P, j = 0..8;
    e (N,) signed digit in [-8, 8] -> (4, L, N) cached |e|*P, negated
    when e < 0 (cached negation = swap (Y-X, Y+X), negate 2dT — no
    multiplies, edwards.negate_cached's identity applied post-select).

    mxu=True (lane-shared tables only, i.e. the fixed-base B table):
    the select is a real (9, 4L) x (9, N) contraction, so ride the MXU
    in f32 instead of spending VPU MACs — exact because limbs < 2^24
    and the mask is one-hot (Precision.HIGHEST carries the full f32
    mantissa through the bf16 passes)."""
    idx = jnp.abs(e)
    if mxu and table9.shape[-1] == 1:
        k = table9.shape[0]
        js = lax.broadcasted_iota(idx.dtype, (k, idx.shape[0]), 0)
        mask = (idx[None, :] == js).astype(jnp.float32)  # (9, N)
        tbl = table9[..., 0].reshape(k, -1).astype(jnp.float32)  # (9, 4L)
        sel = jnp.einsum(
            "kc,kn->cn", tbl, mask, precision=lax.Precision.HIGHEST
        )
        sel = sel.reshape(
            table9.shape[1], table9.shape[2], idx.shape[0]
        ).astype(jnp.int32)
    else:
        sel = _onehot_select(table9, idx)
    sgn = (e < 0)[None, None, :]
    return jnp.where(sgn, E.negate_cached(sel), sel)


def dual_mult_sb_minus_ka(
    A: jnp.ndarray,
    dS: jnp.ndarray,
    dk: jnp.ndarray,
    mosaic: bool = False,
    mxu: Optional[bool] = None,
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

    Two window-walk forms, same math:
    - mosaic=False (XLA default): lax.scan over pre-flipped digit rows.
    - mosaic=True (the Pallas tile): lax.fori_loop; the window's digit
      row is picked by a one-hot masked sum because Mosaic lowers
      neither scan's xs dynamic_slice nor jnp.flip's rev. 64 extra
      MACs/window are noise next to the point ops.

    `mxu` overrides the fixed-base select engine (default: MXU einsum
    on the XLA path, VPU one-hot in the mosaic/Pallas path) — the
    override exists for device A/B attribution (scripts/probe_r3.py)."""
    if mxu is None:
        mxu = not mosaic
    with jax.named_scope("neg_a_table"):
        TA = _build_neg_a_table(A)  # (9, 4, L, N)

    tb0 = _tb0()  # (9, 4, L, 1)

    with jax.named_scope("scalar_prep"):
        dS = _recode_signed(dS)
        dk = _recode_signed(dk)

    # The carry is the T-less 3-stack (X, Y, Z): doublings never
    # read T and the final comparison is projective, so only the ops
    # feeding an addition materialize T (point ops drop the T output
    # mul otherwise — 25% of each output multiply).
    acc0 = E.identity(A.shape[-1])[..., :3, :, :]

    def step(acc, ds_w, dk_w):
        acc = lax.fori_loop(
            0, 3, lambda _i, a: E.point_double(a, with_t=False), acc
        )
        acc = E.point_double(acc)  # T feeds the addition below
        acc = E.point_add_cached(acc, _select_signed(TA, dk_w))
        acc = E.point_add_cached(
            acc, _select_signed(tb0, ds_w, mxu=mxu), with_t=False
        )
        return acc

    # the 64-window walk: the profiler's trace names its operations by
    # this scope
    with jax.named_scope("dual_mult"):
        if mosaic:
            rows = lax.broadcasted_iota(dS.dtype, dS.shape, 0)  # (64, N)

            def body(w, acc):
                sel = (rows == 63 - w).astype(dS.dtype)  # MSB-first walk
                return step(
                    acc,
                    jnp.sum(dS * sel, axis=0),
                    jnp.sum(dk * sel, axis=0),
                )

            return lax.fori_loop(0, 64, body, acc0)

        def scan_body(acc, xs):
            ds_w, dk_w = xs
            return step(acc, ds_w, dk_w), None

        acc, _ = lax.scan(
            scan_body, acc0, (jnp.flip(dS, axis=0), jnp.flip(dk, axis=0))
        )
        return acc


def _scalar_mult_check(
    yA, signA, yR, signR, dS, dk, mosaic=False, dual_fn=None
) -> jnp.ndarray:
    """Core device program. Batch axis minor.

    yA/yR: (L, N) field elements; signA/signR: (N,) int32;
    dS/dk: (64, N) int32 radix-16 digits, little-endian.
    Returns ok: (N,) bool. `dual_fn` overrides the dual scalar-mult
    (the segmented Pallas kernel plugs in here; everything around it —
    decompression, cofactor clearing, the projective compare — stays
    XLA, which fuses those fine)."""
    with jax.named_scope("decode_points"):
        A, okA = E.decompress(yA, signA)
        R, okR = E.decompress(yR, signR)
    if dual_fn is None:
        acc = dual_mult_sb_minus_ka(A, dS, dk, mosaic=mosaic)
    else:
        acc = dual_fn(A, dS, dk)
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
# mask-select form of `.at[31].set(b & 0x7F)`; jnp scatter updates
# have no Pallas TPU lowering (Mosaic: "Unimplemented ... scatter")
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
    # x[32], not x[-1]: jnp lowers negative indices via dynamic_slice,
    # which Mosaic (Pallas TPU) cannot lower
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

    The decided/lt lattice is int32 0/1, not bool: a scalar-True
    jnp.where operand materializes as an i8 constant that Mosaic must
    trunci to i1 — 'Unsupported target bitwidth for truncation'
    (found via scripts/aot_bisect.py against the local v5e topology)."""
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


def _verify_tile(pk_b, sig_b, dig_b, mosaic: bool = False, dual_fn=None) -> jnp.ndarray:
    """The full device program: byte rows in, validity bitmap out.

    pk_b (32, N), sig_b (64, N) uint8/int32 byte rows; dig_b (64, N)
    SHA-512(R||A||M) byte rows. Returns (N,) bool.

    Pure jnp on values — the same body runs as a jitted XLA program
    (CPU and fallback) and, with mosaic=True (Mosaic-lowerable window
    walk, see dual_mult_sb_minus_ka), as the per-tile body of the
    fused Pallas kernel (ops/ed25519_pallas.py). `dual_fn` swaps in the
    segmented Pallas dual-mult while the rest stays XLA."""
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
    ok = _scalar_mult_check(
        yA, signA, yR, signR, dS, dk, mosaic=mosaic, dual_fn=dual_fn
    )
    return ok & s_ok


# -- host packing (only SHA-512 and byte joins remain on host) --


def _join_cols(items: Sequence[bytes], width: int, pad: int) -> np.ndarray:
    """Join n equal-length byte strings into a (width, n+pad) uint8
    array, batch-minor, zero-padded on the right."""
    arr = np.frombuffer(b"".join(items), dtype=np.uint8).reshape(-1, width)
    out = arr.T
    if pad:
        return np.pad(out, ((0, 0), (0, pad)))
    return np.ascontiguousarray(out)


def pallas_bucket(b: int) -> int:
    """Round a bucket up to full Pallas tiles. Rounding small buckets
    up costs nothing: the VPU lane tile is 128 wide, so an 8-lane XLA
    program wastes 94% of every vector register anyway."""
    from .ed25519_pallas import TILE

    return max(TILE, -(-b // TILE) * TILE)


def run_with_pallas_fallback(
    prog, args, *, is_pallas, bucket, proven, compiled, xla_factory, label
):
    """Shared dispatch policy for programs that may contain a Pallas
    kernel (the ed25519 tile/hybrid and the sr25519 hybrid).

    Runs `prog(*args)`. JAX dispatch is asynchronous, so a Mosaic
    *runtime* failure would surface later at gather()'s np.asarray —
    past any fallback; block on the first call of each Pallas bucket so
    device-side kernel failures downgrade HERE. On failure (lowering or
    first-call runtime), log, permanently swap the bucket's entry in
    `compiled` to `xla_factory()` (same math, same semantics), count
    it (tpu_pallas_fallbacks_total — a run that asked for Pallas and
    got XLA must be able to tell), and re-run. A non-Pallas program
    failing is a real error and re-raises."""
    try:
        ok = prog(*args)
        if is_pallas and bucket not in proven:
            jax.block_until_ready(ok)
            proven.add(bucket)
        return ok
    except Exception as e:
        if not is_pallas:
            raise
        import logging

        logging.getLogger("tendermint_tpu.ops").warning(
            "pallas %s kernel failed for bucket %d; "
            "falling back to the XLA program: %s",
            label,
            bucket,
            e,
        )
        from ..crypto.tpu_verifier import note_pallas_fallback

        note_pallas_fallback()
        fn = xla_factory()
        compiled[bucket] = fn
        return fn(*args)


class Ed25519Verifier:
    """Compiled, bucketed batch verifier.

    One instance caches jitted programs per bucket size. Thread-compatible
    for the asyncio runtime (verification calls are synchronous device
    invocations)."""

    def __init__(self, bucket_sizes: Optional[Sequence[int]] = None) -> None:
        self.bucket_sizes = sorted(bucket_sizes or DEFAULT_BUCKET_SIZES)
        self._compiled = {}
        # buckets whose Pallas program has completed on device at least
        # once (first calls block, see dispatch())
        self._pallas_proven = set()

    @staticmethod
    def _is_pallas(prog) -> bool:
        import sys

        # only consult the pallas module if something already imported
        # it (i.e. a pallas program could possibly be in `prog`) — the
        # default XLA path must never pay for, or fail on, this import
        mod = sys.modules.get(__package__ + ".ed25519_pallas")
        return mod is not None and (
            prog is mod.verify_pallas or prog is mod.verify_hybrid
        )

    def _bucket(self, n: int) -> int:
        b = bucket_for(n, self.bucket_sizes)
        if self._pallas_wanted():
            b = pallas_bucket(b)
        return b

    @staticmethod
    def _pallas_wanted() -> Optional[str]:
        """Fused Pallas kernel gate. Opt-in: the kernels are
        differential-verified in interpret mode (tests/test_ops_pallas.py)
        and compile for a v5e ahead of time (scripts/aot_check.py), but
        none has been timed against the XLA program on a chip. The XLA
        program remains the default until one has.

        TM_TPU_PALLAS=1|hybrid -> the segmented kernel (Pallas
        dual-mult inside an XLA program — ~6x smaller Mosaic module);
        TM_TPU_PALLAS=full -> the monolithic whole-tile kernel."""
        import os

        if os.environ.get("TM_TPU_NO_PALLAS"):
            return None
        if jax.default_backend() != "tpu":
            return None
        v = os.environ.get("TM_TPU_PALLAS")
        if v in ("1", "hybrid"):
            return "hybrid"
        if v == "full":
            return "full"
        return None

    def _program(self, size: int):
        """The compiled program for a bucket. One shape-polymorphic
        jitted function serves every bucket (jit caches per shape
        internally); the per-size dict exists for overrides — the
        Pallas fallback swap in dispatch() and ShardedEd25519Verifier's
        per-bucket sharded programs."""
        fn = self._compiled.get(size)
        if fn is None:
            kind = self._pallas_wanted()
            if kind == "hybrid":
                from .ed25519_pallas import verify_hybrid

                fn = verify_hybrid
            elif kind == "full":
                from .ed25519_pallas import verify_pallas

                fn = verify_pallas
            else:
                fn = _jit_verify_tile()
            self._compiled[size] = fn
        return fn

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
            # host work is byte joins only; hashing (SHA-512 of
            # R||A||M), limb unpacking, mod-L, S-canonicality, digits,
            # and the curve math all run on device
            pk_b = _join_cols(pubkeys, 32, pad)
            sig_b = _join_cols(sigs, 64, pad)
            pre = self._preimage_rows(pubkeys, msgs, sigs, bucket)
        dig_b = self._digest_rows(pubkeys, msgs, sigs, bucket, pre)
        prog = self._program(bucket)
        with trace.span(
            "device_launch", program=_program_name(prog), bucket=bucket
        ):
            ok = run_with_pallas_fallback(
                prog,
                (
                    self._place(pk_b),
                    self._place(sig_b),
                    self._place(dig_b),
                ),
                is_pallas=self._is_pallas(prog),
                bucket=bucket,
                proven=self._pallas_proven,
                compiled=self._compiled,
                xla_factory=_jit_verify_tile,
                label="ed25519",
            )
        return (ok, n, size_ok)

    def _place(self, rows):
        """Byte rows (host or already on device) -> the device array
        the programs take. The mesh verifiers override this to shard
        the batch axis from the host (parallel/sharding.py)."""
        return jnp.asarray(rows)

    def _sha512_program(self):
        """The jitted SHA-512 the digests run through (the mesh
        verifiers partition it like the tile)."""
        return _jit_sha512()

    def _preimage_rows(self, pubkeys, msgs, sigs, bucket):
        """(64 + len, bucket) rows of R || A || M when every message
        has one length — every sign-bytes in a Commit has the same
        shape — so that the digests stay on device, feeding the verify
        program without a host round-trip; None otherwise (mixed
        lengths, or TM_TPU_HOST_SHA512=1)."""
        import os

        if os.environ.get("TM_TPU_HOST_SHA512"):
            return None
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

    def _digest_rows(self, pubkeys, msgs, sigs, bucket, pre=None):
        """(64, bucket) rows of SHA512(R || A || M).

        Device-hashed per message-length group (ops/sha512_kernel.py
        compiles one program per length); `pre` is the single-length
        case's pre-image (_preimage_rows), joined here when the caller
        has not. TM_TPU_HOST_SHA512=1 restores hashlib (bench
        comparisons)."""
        import os

        n = len(pubkeys)
        if pre is None:
            pre = self._preimage_rows(pubkeys, msgs, sigs, bucket)
        if pre is not None:
            prog = self._sha512_program()
            with trace.span(
                "device_launch", program=_program_name(prog), bucket=bucket
            ):
                return prog(self._place(pre))
        if os.environ.get("TM_TPU_HOST_SHA512"):
            return _join_cols(
                [
                    hashlib.sha512(sig[:32] + pk + msg).digest()
                    for pk, msg, sig in zip(pubkeys, msgs, sigs)
                ],
                64,
                bucket - n,
            )
        groups: dict = {}
        for i, m in enumerate(msgs):
            groups.setdefault(len(m), []).append(i)
        dig = np.zeros((64, bucket), dtype=np.uint8)
        for mlen, idxs in groups.items():
            g = len(idxs)
            gb = bucket_for(g, self.bucket_sizes)
            pre = _join_cols(
                [
                    sigs[i][:32] + pubkeys[i] + msgs[i]
                    for i in idxs
                ],
                64 + mlen,
                gb - g,
            )
            prog = self._sha512_program()
            with trace.span(
                "device_launch", program=_program_name(prog), bucket=gb
            ):
                launched = prog(self._place(pre))
            out = np.asarray(launched)
            dig[:, idxs] = out[:, :g]
        return dig

    def gather(self, handle) -> np.ndarray:
        """Block on a dispatch() handle and return the bitmap."""
        ok, n, size_ok = handle
        if ok is None:
            return size_ok
        return np.asarray(ok)[:n] & size_ok


def _program_name(prog) -> str:
    """The traced function's own name (`_verify_tile`, `sha512_fixed`,
    a Pallas variant's): what the profiler calls the program's
    executions, less its `jit_` prefix."""
    return getattr(prog, "__name__", type(prog).__name__)


_JIT_VERIFY = None
_JIT_SHA512 = None


def _jit_sha512():
    """Shared jitted sha512_fixed (one compile per message length +
    bucket shape inside jax's cache)."""
    global _JIT_SHA512
    if _JIT_SHA512 is None:
        from .sha512_kernel import sha512_fixed

        _JIT_SHA512 = jax.jit(sha512_fixed)
    return _JIT_SHA512


def _jit_verify_tile():
    """Shared jitted XLA program (shape-polymorphic; compiles once per
    bucket shape inside jax's own cache)."""
    global _JIT_VERIFY
    if _JIT_VERIFY is None:
        _JIT_VERIFY = jax.jit(_verify_tile)
    return _JIT_VERIFY


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
