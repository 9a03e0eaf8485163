"""The int32 work one signature verification NEEDS, from sizes alone.

`kernel_roofline` style metrics divide the least time the chip could
take for this work by the time the device spent. The work is counted
from a plain algorithm written down here, never from a kernel's code or
its jaxpr, so it reads the same whatever implements the kernel.

Unit: one int32 multiply-add (a 32-bit multiply and the add that
accumulates it), the operation chipbench/calibrate.py measures the
ceiling of. Work that is not multiplication (SHA-512, keccak) is
counted one unit an int32 ALU operation.

The field is GF(2^255 - 19). A limb product must fit an int32
accumulator: 13-bit limbs give 26-bit products and twenty of them sum
under 2^31, so an element is 20 limbs (260 bits), and
  M = one field multiplication = 20 * 20          = 400 multiply-adds
  S = one field squaring       = 20 * 21 / 2      = 210 multiply-adds
(carries and the fold of the high half by 19 are shifts and adds on 40
limbs, under a tenth of M; left out, which only lowers the share).

ed25519 (RFC 8032, cofactorless): accept iff
    encode([s]B - [k]A) == R,  k = SHA-512(R || A || M) mod L.
  1. decompress A: x = sqrt((y^2 - 1) / (d y^2 + 1)) is one
     exponentiation to (p - 5) / 8 — 250 S + 11 M by the usual
     addition chain — and 3 S + 8 M around it      -> 253 S + 19 M
  2. table of 1A..8A in extended coordinates: one doubling
     (4 S + 4 M), six additions (8 M each), eight 2d*T -> 4 S + 60 M
  3. [s]B - [k]A with signed radix-16 digits (64 digits a scalar):
     252 doublings (4 S + 4 M each), 64 additions of a table entry of
     A (8 M), 64 mixed additions of a constant multiple of B (7 M;
     B's table is a constant)                   -> 1008 S + 1968 M
  4. encode the result: one inversion (254 S + 11 M), two
     multiplications                               -> 254 S + 13 M
  total 1519 S + 2060 M = 1519 * 210 + 2060 * 400 = 1,142,990
  5. SHA-512 over 64 + len(M) bytes: ceil((64 + len + 17) / 128)
     blocks of 80 rounds; a round is ~60 64-bit operations (two big
     sigmas, Ch, Maj, seven additions, the schedule's two small sigmas
     and three additions) = 120 on int32     -> 9,600 a block
  (k mod L and the digit recoding are a few hundred operations.)

sr25519 (schnorrkel over ristretto255): accept iff
    encode([s]B - [k]A) == R,  k = merlin challenge of (ctx, M, A, R).
  Ristretto decoding of A and encoding of the result are one inverse
  square root each, the same exponentiation as above, so the curve
  work is ed25519's: 1,142,990. The transcript is STROBE-128 over
  keccak-f[1600] (rate 166 bytes): ~40 bytes of framing and labels,
  the context, 32 + 32 bytes of keys, the message, then one
  permutation more for the 64-byte challenge. A permutation is 24
  rounds of ~150 64-bit operations (theta 55, rho-pi 24, chi 75,
  iota 1) = 300 on int32                -> 7,200 a permutation

Bytes a verification moves over HBM at the least: the key (32), the
signature (64) and the message in, one verdict byte out.
"""

from __future__ import annotations

M = 20 * 20
S = 20 * 21 // 2

CURVE_S = 253 + 4 + 1008 + 254
CURVE_M = 19 + 60 + 1968 + 13
CURVE_MADDS = CURVE_S * S + CURVE_M * M

SHA512_BLOCK_OPS = 80 * 60 * 2
KECCAK_PERM_OPS = 24 * 150 * 2
STROBE_RATE = 166
SR_CONTEXT_LEN = 0  # tendermint signs under the empty context (privkey.go:16)
SR_FRAMING = 40


def _ed25519(msg_len: int) -> int:
    blocks = -(-(64 + msg_len + 17) // 128)
    return CURVE_MADDS + blocks * SHA512_BLOCK_OPS


def _sr25519(msg_len: int) -> int:
    absorbed = SR_FRAMING + SR_CONTEXT_LEN + 64 + msg_len
    perms = -(-absorbed // STROBE_RATE) + 1
    return CURVE_MADDS + perms * KECCAK_PERM_OPS


_KINDS = {"ed25519": _ed25519, "sr25519": _sr25519}


def per_signature(kind: str, msg_len: int) -> dict:
    """{"madds", "bytes"} one verification of this key class needs."""
    return {"madds": _KINDS[kind](msg_len), "bytes": 32 + 64 + msg_len + 1}


def least_seconds(madds: int, nbytes: int, peaks: dict) -> tuple:
    """(seconds, "compute" | "memory"): the roofline's least time."""
    compute = madds / peaks["int32_madd_per_s"]["value"]
    memory = nbytes / peaks["hbm_bytes_per_s"]["value"]
    return (compute, "compute") if compute >= memory else (memory, "memory")
