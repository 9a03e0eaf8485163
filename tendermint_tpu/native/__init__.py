"""Native (C) components, compiled on demand with the system compiler.

The reference's runtime leans on native code through curve25519-voi's
Go+assembly crypto (go.mod:23) and optional cgo DB backends
(config/config.go:182-194). Here the TPU handles the curve math, but a
few host-side primitives still need native speed — first among them
Keccak-f[1600] for merlin/STROBE transcripts (crypto/merlin.py), where
pure Python costs ~1 ms per permutation.

Design: tiny dependency-free C files next to this module, compiled
lazily to ``~/.cache/tendermint_tpu/`` (keyed by source hash, so edits
recompile and concurrent processes converge on the same artifact) and
loaded with ctypes. Every consumer keeps a pure-Python fallback; a
missing or broken toolchain degrades performance, never correctness.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import tempfile
from typing import Optional

__all__ = [
    "load",
    "keccakf_lib",
    "signbytes_lib",
    "ed25519_batch_lib",
    "commit_scan_lib",
    "commit_scan",
]

_SRC_DIR = os.path.dirname(os.path.abspath(__file__))
_LIBS: dict = {}


def _cache_dir() -> str:
    base = os.environ.get("XDG_CACHE_HOME") or os.path.join(
        os.path.expanduser("~"), ".cache"
    )
    d = os.path.join(base, "tendermint_tpu")
    os.makedirs(d, exist_ok=True)
    return d


def load(name: str) -> Optional[ctypes.CDLL]:
    """Compile-and-load ``<name>.c`` from this directory; returns the
    CDLL, or None when disabled (TM_TPU_NO_NATIVE=1), the compiler is
    missing, or compilation fails. Results (including failure) are
    cached per process."""
    if name in _LIBS:
        return _LIBS[name]
    lib = None
    if not os.environ.get("TM_TPU_NO_NATIVE"):
        try:
            lib = _build(name)
        except Exception:
            lib = None
    # tmlive: bounded=keyed by native library name — a fixed in-tree
    # set (one .c source per kernel); one CDLL handle per name
    _LIBS[name] = lib
    return lib


def _build(name: str) -> Optional[ctypes.CDLL]:
    src = os.path.join(_SRC_DIR, f"{name}.c")
    with open(src, "rb") as f:
        code = f.read()
    cc = os.environ.get("CC", "cc")
    flags = [cc, "-O3", "-funroll-loops", "-shared", "-fPIC"]
    # the cache key covers compiler, flags, AND every local header
    # (keccakf_core.h is #included by two units), so neither a flag
    # nor a header change can silently reuse a stale artifact
    hdr = b""
    for h in sorted(os.listdir(_SRC_DIR)):
        if h.endswith(".h"):
            with open(os.path.join(_SRC_DIR, h), "rb") as f:
                hdr += f.read()
    tag = hashlib.sha256(
        code + b"|" + hdr + b"|" + " ".join(flags).encode()
    ).hexdigest()[:16]
    out = os.path.join(_cache_dir(), f"{name}-{tag}.so")
    if not os.path.exists(out):
        # compile to a temp name then atomically rename, so concurrent
        # processes never load a half-written .so
        fd, tmp = tempfile.mkstemp(
            suffix=".so", dir=os.path.dirname(out)
        )
        os.close(fd)
        try:
            subprocess.run(
                flags + ["-o", tmp, src],
                check=True,
                capture_output=True,
                timeout=120,
            )
            os.replace(tmp, out)
        finally:
            if os.path.exists(tmp):
                os.unlink(tmp)
    return ctypes.CDLL(out)


def signbytes_lib():
    """The sign-bytes assembler with argtypes set, or None. Exposes
    ``tm_vote_sign_bytes_batch`` (see signbytes.c for the contract)."""
    lib = load("signbytes")
    if lib is None:
        return None
    if not getattr(lib, "_tm_configured", False):
        lib.tm_vote_sign_bytes_batch.argtypes = [
            ctypes.c_char_p,
            ctypes.c_long,
            ctypes.c_char_p,
            ctypes.c_long,
            ctypes.c_uint8,
            ctypes.c_void_p,
            ctypes.c_long,
            ctypes.c_void_p,
            ctypes.c_long,
            ctypes.c_void_p,
        ]
        lib.tm_vote_sign_bytes_batch.restype = ctypes.c_long
        lib._tm_configured = True
    return lib


def commit_scan_lib():
    """The commit signature scanner with argtypes set, or None. Exposes
    ``tm_commit_scan`` (see commit_scan.c for the contract)."""
    lib = load("commit_scan")
    if lib is None:
        return None
    if not getattr(lib, "_tm_configured", False):
        lib.tm_commit_scan.argtypes = [
            ctypes.c_char_p,
            ctypes.c_long,
            ctypes.c_void_p,
            ctypes.c_long,
            ctypes.POINTER(ctypes.c_long),
        ]
        lib.tm_commit_scan.restype = ctypes.c_long
        lib._tm_configured = True
    return lib


def commit_scan(data) -> Optional[tuple]:
    """Scan an encoded Commit's `signatures` entries in one native
    pass. Returns ``(head_end, columns)`` — the offset where the
    entries start, and six equal-length lists (flag, address start,
    address end, timestamp ns, signature start, signature end; the
    ranges index `data`) — or None when the bytes are not the
    canonical layout commit_scan.c accepts, `data` is not `bytes`, or
    native is unavailable: the caller then decodes generically."""
    if type(data) is not bytes:
        return None
    lib = commit_scan_lib()
    if lib is None:
        return None
    import numpy as np

    # an entry is at least two bytes (tag, length): the columns are
    # sized from the input's length alone, and left uninitialised —
    # only the rows the scan wrote are read back
    cap = len(data) // 2
    cols = np.empty((6, cap), dtype=np.int64)
    head_end = ctypes.c_long()
    n = lib.tm_commit_scan(
        data, len(data), cols.ctypes.data, cap, ctypes.byref(head_end)
    )
    if n < 0:
        return None
    return head_end.value, cols[:, :n].tolist()


def keccakf_lib():
    """The keccakf library with argtypes set, or None. Exposes
    ``tm_keccakf(uint64_t st[25])`` and ``tm_keccakf_n(uint64_t*, long)``
    over the 200-byte STROBE state (little-endian u64 lanes)."""
    lib = load("keccakf")
    if lib is None:
        return None
    if not getattr(lib, "_tm_configured", False):
        lib.tm_keccakf.argtypes = [ctypes.c_void_p]
        lib.tm_keccakf.restype = None
        lib.tm_keccakf_n.argtypes = [ctypes.c_void_p, ctypes.c_long]
        lib.tm_keccakf_n.restype = None
        lib._tm_configured = True
    return lib


def ed25519_batch_lib():
    """The ed25519 batch-equation library with argtypes set, or None.
    Exposes ``tm_ed25519_batch_verify(pk_bytes, r_bytes, zb, a_scalars,
    z_scalars, n) -> int`` (1 accept / 0 equation-reject / -1 decode
    failure) — see native/ed25519_batch.c for the contract."""
    lib = load("ed25519_batch")
    if lib is None:
        return None
    if not getattr(lib, "_tm_configured", False):
        argtypes = [ctypes.c_char_p] * 5 + [ctypes.c_uint64]
        lib.tm_ed25519_batch_verify.argtypes = argtypes
        lib.tm_ed25519_batch_verify.restype = ctypes.c_int
        # same equation over ristretto255 decoding (sr25519/schnorrkel)
        lib.tm_sr25519_batch_verify.argtypes = argtypes
        lib.tm_sr25519_batch_verify.restype = ctypes.c_int
        # whole-batch entry: SHA-512 challenges + mod-L scalar products
        # + the equation in one native call (no per-signature Python)
        lib.tm_ed25519_verify_full.argtypes = [
            ctypes.c_char_p,                  # pks n*32
            ctypes.c_char_p,                  # sigs n*64
            ctypes.c_char_p,                  # msgs blob
            ctypes.POINTER(ctypes.c_uint64),  # n+1 offsets
            ctypes.c_char_p,                  # rand n*16
            ctypes.c_uint64,
        ]
        lib.tm_ed25519_verify_full.restype = ctypes.c_int
        # the sr25519 analog: schnorrkel parsing + merlin challenges
        # (STROBE-128 in C) + RLC products + the ristretto equation
        lib.tm_sr25519_verify_full.argtypes = (
            lib.tm_ed25519_verify_full.argtypes
        )
        lib.tm_sr25519_verify_full.restype = ctypes.c_int
        # differential hook: C merlin challenge vs crypto/sr25519.py
        lib.tm_sr25519_challenge_test.argtypes = [
            ctypes.c_char_p,
            ctypes.c_char_p,
            ctypes.c_char_p,
            ctypes.c_uint64,
            ctypes.c_char_p,
        ]
        lib.tm_sr25519_challenge_test.restype = None
        # production sign-path challenge (same computation; the _test
        # name is the historical differential hook)
        lib.tm_sr25519_challenge.argtypes = (
            lib.tm_sr25519_challenge_test.argtypes
        )
        lib.tm_sr25519_challenge.restype = None
        # decoded-point cache observability (hits/misses/inserts/
        # evictions) + reset — the repeated-validator-set optimization
        # (reference: crypto/ed25519/ed25519.go:50-56 cacheSize 4096)
        lib.tm_pk_cache_stats.argtypes = [
            ctypes.POINTER(ctypes.c_uint64)
        ]
        lib.tm_pk_cache_stats.restype = None
        lib.tm_pk_cache_clear.argtypes = []
        lib.tm_pk_cache_clear.restype = None
        # fixed-base multiply + ristretto encode (sr25519 sign/keygen)
        lib.tm_ristretto_basemul.argtypes = [
            ctypes.c_char_p,
            ctypes.c_char_p,
        ]
        lib.tm_ristretto_basemul.restype = ctypes.c_int
        lib._tm_configured = True
    return lib


def ristretto_basemul(scalar_le32: bytes) -> Optional[bytes]:
    """encode(scalar*B) through the native library, or None when
    native is unavailable. scalar: 32-byte little-endian, < L."""
    # the C side unconditionally reads 32 bytes — a shorter buffer
    # from a future caller would be an out-of-bounds read (ADVICE r5)
    if len(scalar_le32) != 32:
        raise ValueError(
            f"scalar must be exactly 32 bytes, got {len(scalar_le32)}"
        )
    lib = ed25519_batch_lib()
    if lib is None:
        return None
    out = ctypes.create_string_buffer(32)
    # tmct: ct-ok — FFI status code only: the native basemul is a
    # fixed-window constant-structure ladder, and rc reflects library
    # availability/buffer validity, never scalar bits
    if lib.tm_ristretto_basemul(scalar_le32, out) != 0:
        return None
    return out.raw


def sr25519_challenge(pub: bytes, r: bytes, msg: bytes) -> Optional[bytes]:
    """The merlin signing-context challenge k for (pub, R, msg) as 32
    little-endian bytes (reduced mod L), or None when native is
    unavailable — the sign-path twin of ristretto_basemul."""
    # C reads exactly 32 bytes of pub and R (msg carries its length)
    if len(pub) != 32:
        raise ValueError(f"pub must be exactly 32 bytes, got {len(pub)}")
    if len(r) != 32:
        raise ValueError(f"R must be exactly 32 bytes, got {len(r)}")
    lib = ed25519_batch_lib()
    if lib is None:
        return None
    out = ctypes.create_string_buffer(32)
    lib.tm_sr25519_challenge(pub, r, msg, len(msg), out)
    return out.raw


def pk_cache_stats() -> Optional[dict]:
    """Decoded-point cache counters from the native batch library, or
    None when native is unavailable."""
    lib = ed25519_batch_lib()
    if lib is None:
        return None
    out = (ctypes.c_uint64 * 4)()
    lib.tm_pk_cache_stats(out)
    return {
        "hits": out[0],
        "misses": out[1],
        "inserts": out[2],
        "evictions": out[3],
    }
