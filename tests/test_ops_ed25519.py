"""Differential tests for the full device ed25519 batch verifier against
the CPU implementations (OpenSSL fast path + pure-Python ZIP-215 oracle).
This mirrors the reference's own batch-vs-single equivalence strategy
(reference: types/validation_test.go, crypto/ed25519/ed25519_test.go)."""

import hashlib

import numpy as np
import pytest

from tendermint_tpu.crypto import ed25519_math as em
from tendermint_tpu.crypto.ed25519 import PrivKeyEd25519
from tendermint_tpu.ops.ed25519_kernel import Ed25519Verifier


@pytest.fixture(scope="module")
def verifier():
    return Ed25519Verifier(bucket_sizes=[8])


def _sign_set(n, tag=b""):
    keys = [
        PrivKeyEd25519.from_seed(hashlib.sha256(tag + bytes([i])).digest())
        for i in range(n)
    ]
    msgs = [b"msg-" + tag + bytes([i]) for i in range(n)]
    sigs = [k.sign(m) for k, m in zip(keys, msgs)]
    return [k.pub_key().bytes() for k in keys], msgs, sigs


def test_valid_batch(verifier):
    pks, msgs, sigs = _sign_set(6)
    ok = verifier.verify(pks, msgs, sigs)
    assert ok.tolist() == [True] * 6


def test_mixed_batch_bitmap(verifier):
    pks, msgs, sigs = _sign_set(6, b"x")
    # corrupt sig at 1, message at 3, pubkey at 5
    sigs[1] = sigs[1][:32] + (
        (int.from_bytes(sigs[1][32:], "little") ^ 1).to_bytes(32, "little")
    )
    msgs[3] = b"tampered"
    pks[5] = hashlib.sha256(b"not a point seed").digest()  # likely invalid/other key
    ok = verifier.verify(pks, msgs, sigs)
    # cross-check every index against the ZIP-215 oracle
    expect = [em.zip215_verify(p, m, s) for p, m, s in zip(pks, msgs, sigs)]
    assert ok.tolist() == expect
    assert not ok[1] and not ok[3] and not ok[5]


def test_high_s_rejected(verifier):
    pks, msgs, sigs = _sign_set(2, b"s")
    s = int.from_bytes(sigs[0][32:], "little")
    sigs[0] = sigs[0][:32] + (s + em.L).to_bytes(32, "little")
    ok = verifier.verify(pks, msgs, sigs)
    assert ok.tolist() == [False, True]


def test_malformed_sizes(verifier):
    pks, msgs, sigs = _sign_set(3, b"z")
    sigs[0] = sigs[0][:40]
    pks[1] = pks[1][:10]
    ok = verifier.verify(pks, msgs, sigs)
    assert ok.tolist() == [False, False, True]


def test_noncanonical_y_zip215_accepted(verifier):
    # Build a signature whose R has a y >= p encoding: R = point with
    # small y where y + p < 2^255. Craft via oracle: take a valid sig and
    # re-encode R non-canonically if possible; else assert oracle parity.
    pks, msgs, sigs = _sign_set(1, b"nc")
    r_int = int.from_bytes(sigs[0][:32], "little")
    y = r_int & ((1 << 255) - 1)
    if y + em.P < (1 << 255):  # rarely true for random points
        nc = (y + em.P) | (r_int & (1 << 255))
        sigs[0] = nc.to_bytes(32, "little") + sigs[0][32:]
    ok = verifier.verify(pks, msgs, sigs)
    expect = [em.zip215_verify(pks[0], msgs[0], sigs[0])]
    assert ok.tolist() == expect


def test_empty_batch(verifier):
    assert verifier.verify([], [], []).tolist() == []


def test_small_order_points_match_oracle(verifier):
    """Cofactor-sensitive edge class: small-order encodings for A and R
    (identity, y=-1 order 2, y=0 order 4). ZIP-215's cofactored
    equation accepts combinations a cofactorless verifier rejects; the
    kernel must agree with the pure-Python oracle bit for bit
    (reference semantics: crypto/ed25519/ed25519.go:27-29)."""
    ident = bytes([1]) + bytes(31)                    # y=1, order 1
    y_minus1 = int(em.P - 1).to_bytes(32, "little")   # y=-1, order 2
    y0_a = bytes(32)                                  # y=0, order 4
    y0_b = bytes(31) + bytes([0x80])                  # y=0, other root
    small = [ident, y_minus1, y0_a, y0_b]
    # order-8 torsion, derived not hard-coded: [L]P of an arbitrary
    # curve point lands in the 8-torsion; keep the order-8 ones.
    # Without these, [4]P == identity for every case and an off-by-one
    # in the kernel's cofactor-doubling loop would go unnoticed.
    for y in range(2, 200):
        pt = em.decompress(int(y).to_bytes(32, "little"))
        if pt is None:
            continue
        t = em.scalar_mult(em.L, pt)
        if (
            em.compress(em.scalar_mult(4, t)) != ident
            and em.compress(em.scalar_mult(8, t)) == ident
        ):
            enc = em.compress(t)
            small.append(enc)  # order-8 point
            small.append(enc[:31] + bytes([enc[31] ^ 0x80]))  # its negation
            break
    assert len(small) == 6, "order-8 torsion point not found"

    msg = b"small-order"
    cases = []
    # small-order A with R = small-order and S in {0, 1}
    for a in small:
        for r in small:
            for s_int in (0, 1):
                sig = r + int(s_int).to_bytes(32, "little")
                cases.append((a, msg, sig))
    # valid honest signature but R replaced by a small-order point
    priv = PrivKeyEd25519.from_seed(b"\x77" * 32)
    pk = priv.pub_key().bytes()
    honest = priv.sign(msg)
    for r in small:
        cases.append((pk, msg, r + honest[32:]))

    pks = [c[0] for c in cases]
    msgs = [c[1] for c in cases]
    sigs = [c[2] for c in cases]
    got = verifier.verify(pks, msgs, sigs)
    expect = [em.zip215_verify(p, m, s) for p, m, s in cases]
    assert list(got) == expect, list(zip(got, expect))
    # sanity: at least one cofactored acceptance exists in this set
    assert any(expect), "expected some small-order case to verify"


def test_sha512_kernel_matches_hashlib():
    """Device SHA-512 (ops/sha512_kernel.py) vs hashlib across block
    boundaries (111/112 bytes is the one/two-block edge)."""
    import jax
    import jax.numpy as jnp

    from tendermint_tpu.ops.sha512_kernel import sha512_fixed

    rng = np.random.default_rng(11)
    for length in (0, 1, 111, 112, 127, 128, 250):
        msgs = [
            bytes(rng.integers(0, 256, length, dtype=np.uint8))
            for _ in range(4)
        ]
        if length:
            rows = (
                np.frombuffer(b"".join(msgs), dtype=np.uint8)
                .reshape(4, length)
                .T
            )
        else:
            rows = np.zeros((0, 4), dtype=np.uint8)
        got = np.asarray(jax.jit(sha512_fixed)(jnp.asarray(rows)))
        for i, m in enumerate(msgs):
            assert got[:, i].tobytes() == hashlib.sha512(m).digest()


def test_sha512_unrolled_compress_matches_scan_form():
    """The TPU trace-time compression (_compress unrolled branch) vs
    the scan form the CPU backend traces — the unrolled branch never
    runs under JAX_PLATFORMS=cpu, so its math is covered directly."""
    import jax.numpy as jnp

    from tendermint_tpu.ops import sha512_kernel as SK

    import unittest.mock as mock

    rng = np.random.default_rng(13)
    state = jnp.asarray(rng.integers(0, 2**32, (8, 2, 5), dtype=np.uint32))
    block = jnp.asarray(rng.integers(0, 2**32, (16, 2, 5), dtype=np.uint32))
    # trace the unrolled branch by bypassing the backend gate
    with mock.patch("jax.default_backend", return_value="tpu"):
        got = np.asarray(SK._compress(state, block))
    want = np.asarray(SK._compress_scan(state, block))
    assert (got == want).all()


def test_mixed_message_lengths_device_digests(verifier):
    """dispatch groups by message length for the device SHA-512 and
    reassembles digests in batch order."""
    pks, msgs, sigs = _sign_set(6, b"len")
    keys = [
        PrivKeyEd25519.from_seed(hashlib.sha256(b"len" + bytes([i])).digest())
        for i in range(6)
    ]
    msgs = [b"x" * (10 + 7 * (i % 3)) for i in range(6)]
    sigs = [k.sign(m) for k, m in zip(keys, msgs)]
    sigs[2] = sigs[2][:10] + bytes([sigs[2][10] ^ 1]) + sigs[2][11:]
    ok = verifier.verify(pks, msgs, sigs)
    assert ok.tolist() == [True, True, False, True, True, True]


def test_recode_signed_value_preserving():
    """_recode_signed must re-express the radix-16 value exactly with
    digits in [-8, 7] — including maximal carry-propagation runs (all
    7s, all 8s, all 15s) where the Kogge-Stone lattice is stressed."""
    import jax
    import jax.numpy as jnp

    from tendermint_tpu.ops import ed25519_kernel as K

    rng = np.random.default_rng(5)
    cols = [
        rng.integers(0, 16, 64) for _ in range(12)
    ] + [
        np.full(64, 7), np.full(64, 8), np.full(64, 15), np.zeros(64),
        np.array([15] * 63 + [0]),  # carry run stopping at the top
    ]
    # keep the top digit small enough that no carry is dropped (the
    # dropped-carry case is gated by s < L upstream — see docstring)
    for c in cols:
        c[-1] = min(int(c[-1]), 6)
    d = np.stack(cols, axis=1).astype(np.int32)  # (64, N)
    e = np.asarray(jax.jit(K._recode_signed)(jnp.asarray(d)))
    assert e.min() >= -8 and e.max() <= 7
    w = 16 ** np.arange(64, dtype=object)
    for j in range(d.shape[1]):
        orig = int(sum(int(x) * int(p) for x, p in zip(d[:, j], w)))
        got = int(sum(int(x) * int(p) for x, p in zip(e[:, j], w)))
        assert got == orig, j
