"""AddressSanitizer sweep of the native batch-equation kernel and the
commit signature scanner.

Builds an ASAN variant of native/ed25519_batch.c and drives every
exported entry point through all three MSM paths (Straus < 1024 terms,
Pippenger w8, Pippenger w11), multi-block SHA-512 message shapes, the
scalar/hash test hooks, and the sr25519 ristretto path — valid and
corrupted batches. Then an ASAN variant of native/commit_scan.c over a
golden commit, every truncation of it and a few thousand seeded
mutations, input and columns in exact-size sanitizer allocations. Run
after ANY change to a C unit:

    python scripts/asan_check.py

Exits nonzero on an ASAN report or a wrong verification result.
(The suite's differential tests check semantics; this checks memory.)
"""

from __future__ import annotations

import ctypes
import os
import random
import subprocess
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NATIVE = os.path.join(REPO, "tendermint_tpu", "native")
UNITS = ("ed25519_batch", "commit_scan")


def main() -> int:
    cc = os.environ.get("CC", "cc")
    out = tempfile.mkdtemp()
    sos = [os.path.join(out, f"{unit}_asan.so") for unit in UNITS]
    for unit, so in zip(UNITS, sos):
        subprocess.run(
            [cc, "-O1", "-g", "-fsanitize=address", "-shared", "-fPIC",
             "-o", so, os.path.join(NATIVE, f"{unit}.c")],
            check=True,
        )
    asan = subprocess.run(
        [cc, "-print-file-name=libasan.so"],
        capture_output=True, text=True, check=True,
    ).stdout.strip()
    # re-exec under LD_PRELOAD so ASAN is initialized before python
    if not os.environ.get("TM_ASAN_CHILD"):
        env = dict(os.environ)
        env["TM_ASAN_CHILD"] = os.pathsep.join(sos)
        env["LD_PRELOAD"] = asan
        env.setdefault("ASAN_OPTIONS", "detect_leaks=0")
        env["JAX_PLATFORMS"] = "cpu"
        env["PYTHONPATH"] = ""
        return subprocess.run(
            [sys.executable, os.path.abspath(__file__)], env=env
        ).returncode
    batch_so, scan_so = os.environ["TM_ASAN_CHILD"].split(os.pathsep)
    return run_checks(batch_so) or run_commit_scan_checks(scan_so)


def _ed25519_keygen():
    """(make_signer(seed) -> obj with .sign(msg), pub_bytes(signer))
    for the sweep's test signatures.

    Prefers the OpenSSL-backed `cryptography` wheel; a container
    without the wheel (this box — PR 1 gated the dependency) falls
    back to the repo's pure-Python RFC-8032 signer. The fallback is
    a TOOLCHAIN substitution, not a weakening: both paths produce the
    identical deterministic RFC-8032 signatures, and the fallback is
    pinned against RFC 8032 test vector 1 here before anything trusts
    it — a broken signer would otherwise launder wrong-signature
    results into the memory sweep."""
    try:
        from cryptography.hazmat.primitives.asymmetric.ed25519 import (
            Ed25519PrivateKey,
        )
        from cryptography.hazmat.primitives.serialization import (
            Encoding,
            PublicFormat,
        )

        def make(seed: bytes):
            return Ed25519PrivateKey.from_private_bytes(seed)

        def pub(sk) -> bytes:
            return sk.public_key().public_bytes(
                Encoding.Raw, PublicFormat.Raw
            )

        return make, pub
    except ImportError:
        from tendermint_tpu.crypto.ed25519 import PrivKeyEd25519

        def make(seed: bytes):
            return PrivKeyEd25519(seed)

        def pub(sk) -> bytes:
            return sk.pub_key().bytes()

        # RFC 8032 §7.1 TEST 1: seed -> pub key and empty-message
        # signature must match bit-for-bit before the sweep runs.
        # Explicit raises, not asserts: `python -O` must not compile
        # the guard away
        vec = make(bytes.fromhex(
            "9d61b19deffd5a60ba844af492ec2cc4"
            "4449c5697b326919703bac031cae7f60"
        ))
        if pub(vec) != bytes.fromhex(
            "d75a980182b10ab7d54bfed3c964073a"
            "0ee172f3daa62325af021a68f707511a"
        ):
            raise RuntimeError(
                "fallback ed25519 keygen diverges from RFC 8032"
            )
        if vec.sign(b"") != bytes.fromhex(
            "e5564300c360ac729086e2cc806e828a"
            "84877f1eb8e5d974d873e06522490155"
            "5fb8821590a33bacc61e39701cf9b46b"
            "d25bf5f0595bbe24655141438e7a100b"
        ):
            raise RuntimeError(
                "fallback ed25519 signer diverges from RFC 8032"
            )
        return make, pub


def run_checks(so: str) -> int:
    sys.path.insert(0, REPO)
    lib = ctypes.CDLL(so)
    argtypes = [ctypes.c_char_p] * 5 + [ctypes.c_uint64]
    lib.tm_ed25519_batch_verify.argtypes = argtypes
    lib.tm_ed25519_batch_verify.restype = ctypes.c_int
    lib.tm_sr25519_batch_verify.argtypes = argtypes
    lib.tm_sr25519_batch_verify.restype = ctypes.c_int
    lib.tm_ed25519_verify_full.argtypes = [ctypes.c_char_p] * 3 + [
        ctypes.POINTER(ctypes.c_uint64), ctypes.c_char_p, ctypes.c_uint64
    ]
    lib.tm_ed25519_verify_full.restype = ctypes.c_int
    lib.tm_sc_mod_l_test.argtypes = [ctypes.c_char_p, ctypes.c_char_p]
    lib.tm_sha512_test.argtypes = [
        ctypes.c_char_p, ctypes.c_uint64, ctypes.c_char_p
    ]

    random.seed(5)
    out32 = ctypes.create_string_buffer(32)
    for _ in range(200):
        lib.tm_sc_mod_l_test(random.randbytes(64), out32)
    out64 = ctypes.create_string_buffer(64)
    for ln in (0, 1, 111, 112, 113, 127, 128, 129, 600):
        lib.tm_sha512_test(random.randbytes(ln), ln, out64)

    make_signer, pub_bytes = _ed25519_keygen()
    keys = []
    for i in range(8):
        sk = make_signer(bytes([i + 1]) * 32)
        keys.append((sk, pub_bytes(sk)))
    # sizes hitting Straus (<512 sigs), Pippenger w8, and w11 (>1700)
    for n in (1, 2, 7, 48, 600, 2048):
        pks, sigs, blob = bytearray(), bytearray(), bytearray()
        offs = (ctypes.c_uint64 * (n + 1))()
        pos = 0
        for i in range(n):
            sk, pk = keys[i % 8]
            m = b"asan-%d-" % i + b"y" * ((i * 53) % 500)
            pks += pk
            sigs += sk.sign(m)
            offs[i] = pos
            blob += m
            pos += len(m)
        offs[n] = pos
        rc = lib.tm_ed25519_verify_full(
            bytes(pks), bytes(sigs), bytes(blob), offs,
            random.randbytes(16 * n), n,
        )
        assert rc == 1, (n, rc)
        bad = bytearray(sigs)
        bad[32] ^= 1
        rc = lib.tm_ed25519_verify_full(
            bytes(pks), bytes(bad), bytes(blob), offs,
            random.randbytes(16 * n), n,
        )
        assert rc in (0, -1), (n, rc)

    import jax

    jax.config.update("jax_platforms", "cpu")
    from tendermint_tpu.crypto.ed25519 import _rlc_scalars
    from tendermint_tpu.crypto.sr25519 import (
        PrivKeySr25519,
        _parse_signature,
        challenge_batch,
    )

    privs = [PrivKeySr25519.from_seed(bytes([i + 3]) * 32) for i in range(4)]
    n = 40
    pks_l, msgs, sigs_l = [], [], []
    for i in range(n):
        p = privs[i % 4]
        m = b"sr-asan-%d" % i
        pks_l.append(p.pub_key().bytes())
        msgs.append(m)
        sigs_l.append(p.sign(m))
    parsed = [_parse_signature(s) for s in sigs_l]
    ks = challenge_batch(pks_l, msgs, [r for r, _ in parsed])
    zb, a_sc, z_sc = _rlc_scalars([s for _, s in parsed], ks)
    rc = lib.tm_sr25519_batch_verify(
        b"".join(pks_l), b"".join(r for r, _ in parsed), zb, a_sc, z_sc, n
    )
    assert rc == 1, rc

    # whole-batch sr25519 entry (merlin/STROBE in C) across STROBE
    # rate boundaries, valid + marker-stripped + corrupted-s batches
    lib.tm_sr25519_verify_full.argtypes = lib.tm_ed25519_verify_full.argtypes
    lib.tm_sr25519_verify_full.restype = ctypes.c_int
    lib.tm_sr25519_challenge_test.argtypes = [
        ctypes.c_char_p, ctypes.c_char_p, ctypes.c_char_p,
        ctypes.c_uint64, ctypes.c_char_p,
    ]
    for mlen in (0, 1, 165, 166, 167, 400):
        lib.tm_sr25519_challenge_test(
            random.randbytes(32), random.randbytes(32),
            random.randbytes(mlen), mlen, out32,
        )
    for n in (1, 2, 40, 600):
        pks_b, sigs_b, blob = bytearray(), bytearray(), bytearray()
        offs = (ctypes.c_uint64 * (n + 1))()
        pos = 0
        for i in range(n):
            p = privs[i % 4]
            m = b"srfull-%d-" % i + b"z" * ((i * 71) % 400)
            pks_b += p.pub_key().bytes()
            sigs_b += p.sign(m)
            offs[i] = pos
            blob += m
            pos += len(m)
        offs[n] = pos
        rc = lib.tm_sr25519_verify_full(
            bytes(pks_b), bytes(sigs_b), bytes(blob), offs,
            random.randbytes(16 * n), n,
        )
        assert rc == 1, (n, rc)
        bad = bytearray(sigs_b)
        bad[63] &= 0x7F  # strip the v1 marker on sig 0
        rc = lib.tm_sr25519_verify_full(
            bytes(pks_b), bytes(bad), bytes(blob), offs,
            random.randbytes(16 * n), n,
        )
        assert rc == 0, (n, rc)

    # decoded-point cache hooks: stats/clear under mixed-curve traffic
    lib.tm_pk_cache_stats.argtypes = [ctypes.POINTER(ctypes.c_uint64)]
    lib.tm_pk_cache_clear.argtypes = []
    stats = (ctypes.c_uint64 * 4)()
    lib.tm_pk_cache_stats(stats)
    lib.tm_pk_cache_clear()
    lib.tm_pk_cache_stats(stats)
    assert list(stats) == [0, 0, 0, 0]

    # fixed-base multiply + ristretto encode (sign/keygen path):
    # edge scalars (0, 1, L-1) and random ones
    lib.tm_ristretto_basemul.argtypes = [ctypes.c_char_p, ctypes.c_char_p]
    lib.tm_ristretto_basemul.restype = ctypes.c_int
    L = 2**252 + 27742317777372353535851937790883648493
    out32 = ctypes.create_string_buffer(32)
    for k in [0, 1, 2, L - 1] + [
        random.randrange(L) for _ in range(32)
    ]:
        rc = lib.tm_ristretto_basemul(
            int(k).to_bytes(32, "little"), out32
        )
        assert rc == 0, k

    print("ASAN PASS: all entry points, all MSM paths, no reports")
    return 0


def run_commit_scan_checks(so: str) -> int:
    """tm_commit_scan over hostile bytes. A Python bytes object keeps a
    NUL after its last byte inside an allocation the sanitizer does not
    see, so each input is copied into a malloc of its exact size (the
    preloaded runtime's, with redzones), and the columns likewise: a
    read or write one past either end is a report. What the scan
    accepts must hold the generic decoder's entry count."""
    sys.path.insert(0, REPO)
    os.environ["TM_TPU_NO_NATIVE"] = "1"  # the oracle below is generic
    from tendermint_tpu.types.block_id import BlockID, PartSetHeader
    from tendermint_tpu.types.commit import Commit, CommitSig

    lib = ctypes.CDLL(so)
    lib.tm_commit_scan.argtypes = [
        ctypes.c_void_p, ctypes.c_long, ctypes.c_void_p, ctypes.c_long,
        ctypes.POINTER(ctypes.c_long),
    ]
    lib.tm_commit_scan.restype = ctypes.c_long
    libc = ctypes.CDLL(None)
    libc.malloc.argtypes = [ctypes.c_size_t]
    libc.malloc.restype = ctypes.c_void_p
    libc.free.argtypes = [ctypes.c_void_p]

    def scan(data: bytes) -> int:
        cap = len(data) // 2
        buf = libc.malloc(len(data))
        cols = libc.malloc(6 * 8 * cap)
        ctypes.memmove(buf, data, len(data))
        head_end = ctypes.c_long()
        try:
            return lib.tm_commit_scan(
                buf, len(data), cols, cap, ctypes.byref(head_end)
            )
        finally:
            libc.free(buf)
            libc.free(cols)

    rng = random.Random(27)
    sigs = []
    for i in range(12):
        ts = (i - 3) * 1_000_000_007 * 10 ** (i % 4)
        if i % 5 == 2:
            sigs.append(CommitSig.absent())
        elif i % 5 == 4:
            sigs.append(CommitSig.for_nil(rng.randbytes(64), rng.randbytes(20), ts))
        else:
            sigs.append(CommitSig.for_block(rng.randbytes(64), rng.randbytes(20), ts))
    golden = Commit(
        height=9, round=2, signatures=sigs,
        block_id=BlockID(
            hash=b"\x03" * 32,
            part_set_header=PartSetHeader(total=2, hash=b"\x04" * 32),
        ),
    ).to_proto()
    assert scan(golden) == len(sigs), scan(golden)
    for cut in range(len(golden) + 1):
        scan(golden[:cut])
    accepted = 0
    for _ in range(4000):
        b = bytearray(golden)
        for _ in range(rng.randrange(1, 5)):
            at = rng.randrange(len(b))
            op = rng.randrange(4)
            if op == 0:
                b[at] = rng.randrange(256)
            elif op == 1:
                b[at] |= 0x80  # stretch a varint over what follows
            elif op == 2:
                del b[at : at + rng.randrange(1, 8)]
            else:
                b[at:at] = rng.randbytes(rng.randrange(1, 8))
            if not b:
                b = bytearray(b"\x22")
        n = scan(bytes(b))
        if n >= 0:
            accepted += 1
            try:
                want = len(Commit.from_proto(bytes(b)).signatures)
            except ValueError:
                continue  # the head, which the scan only skips
            assert n == want, bytes(b).hex()
    # degenerate shapes: nothing, a lone tag, the most entries an input
    # can hold, a length that claims 2**62 bytes
    for data in (b"", b"\x22", b"\x22\x00" * 4096,
                 b"\x22\xff\xff\xff\xff\xff\xff\xff\xff\x3f\x08"):
        scan(data)
    assert 0 < accepted < 4000, accepted
    print(f"ASAN PASS: commit_scan, {len(golden) + 1} truncations, "
          f"4000 mutations ({accepted} still canonical), no reports")
    return 0


if __name__ == "__main__":
    sys.exit(main())
