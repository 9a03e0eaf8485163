"""schnorrkel's merlin challenge as an XLA program (uint32 half-word lanes).

The device form of sr25519's Fiat-Shamir challenge (reference: the
schnorrkel signing transcript that curve25519-voi runs behind
crypto/sr25519/batch.go; host form crypto/sr25519.py challenge_wides):
the 64 wide bytes of `sign:c` after signing_context(b"") appends the
message, proto-name "Schnorr-sig", the key A and the commitment R. The
sr25519 tile reduces them mod L (ops/sr25519_kernel.py), as the ed25519
tile reduces its SHA-512 digests.

STROBE-128's control flow depends only on the lengths of what it
absorbs (crypto/merlin.py `_StrobeBatch`), so for one message length
the transcript is straight-line code: `_schedule` replays the host
transcript at trace time over placeholders for the operand's rows and
records, for each Keccak-f permutation, the constant bytes and the row
ranges absorbed before it (begin-op bytes, labels, lengths, `_run_f`'s
padding, and the signing-context prefix's whole state in the first).
The device then XORs each block into the state and permutes, and reads
the challenge off the last state.

Keccak-f[1600] runs on (hi, lo) uint32 planes, as SHA-512 does
(ops/sha512_kernel.py): a state is (2, 25, N), a plane of high halves
and one of low halves of the 25 64-bit lanes, so that every operand of
the round is a dense (N,) row. The 24 rounds are a lax.scan over a
~900-op body, so the program stays small to compile on every backend.
One program a (message length, batch bucket); callers group a batch by
length.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from ..crypto import merlin

__all__ = ["merlin_challenge"]

_RATE = merlin._R
_RC = np.array(
    [[(rc >> 32) & 0xFFFFFFFF, rc & 0xFFFFFFFF] for rc in merlin._RC],
    dtype=np.uint32,
)  # (24, 2): hi/lo planes of iota's round constants


class _Rows:
    """Stands for `length` operand rows from `start` where the host
    transcript would take message bytes: only its length is known at
    trace time."""

    __slots__ = ("start", "length")

    def __init__(self, start: int, length: int) -> None:
        self.start = start
        self.length = length

    def __len__(self) -> int:
        return self.length


class _Schedule(merlin._Strobe128):
    """The merlin subset of STROBE-128 (crypto/merlin.py, whose
    begin-op and framing logic it inherits) run over lengths alone.
    Instead of permuting, it closes a block: `blocks` holds, a
    permutation each, the (200,) uint8 bytes XORed into the state before
    it and the (position, row, count) runs of operand rows absorbed
    with them. `squeezed` is (position, n) of the challenge's bytes in
    the state after the last permutation."""

    def __init__(self, prefix: "merlin._Strobe128") -> None:
        # the first block XORs into a zero state: it starts as the
        # prefix's whole state
        self.pending = np.frombuffer(bytes(prefix.state), dtype=np.uint8).copy()
        self.runs: list = []
        self.blocks: list = []
        self.squeezed = None
        self.pos = prefix.pos
        self.pos_begin = prefix.pos_begin
        self.cur_flags = prefix.cur_flags

    def _absorb(self, data) -> None:
        rows = isinstance(data, _Rows)
        off, n = 0, len(data)
        while off < n:
            take = min(n - off, _RATE - self.pos)
            if rows:
                self.runs.append((self.pos, data.start + off, take))
            else:
                chunk = np.frombuffer(data[off : off + take], dtype=np.uint8)
                self.pending[self.pos : self.pos + take] ^= chunk
            self.pos += take
            off += take
            if self.pos == _RATE:
                self._run_f()

    def _squeeze(self, n: int) -> None:
        if self.runs or self.pending.any() or self.pos + n > _RATE:
            # the prf's begin-op permutes (its C flag), so the challenge
            # is read from a fresh state and within its rate
            raise ValueError("challenge not at the start of a permuted state")
        self.squeezed = (self.pos, n)

    def _run_f(self) -> None:
        self.pending[self.pos] ^= self.pos_begin
        self.pending[self.pos + 1] ^= 0x04
        self.pending[_RATE + 1] ^= 0x80
        self.blocks.append((self.pending, self.runs))
        self.pending = np.zeros(200, dtype=np.uint8)
        self.runs = []
        self.pos = 0
        self.pos_begin = 0


def _schedule(mlen: int) -> _Schedule:
    """The signing transcript of an `mlen`-byte message, for operand
    rows M || A || R (crypto/sr25519.py's sequence of appends)."""
    from ..crypto import sr25519

    t = object.__new__(merlin.Transcript)
    t._strobe = _Schedule(sr25519._signing_prefix()._strobe)
    t.append_message(b"sign-bytes", _Rows(0, mlen))
    sr25519._challenge_wide(t, _Rows(mlen, 32), _Rows(mlen + 32, 32))
    return t._strobe


def _rotl(h: jnp.ndarray, l: jnp.ndarray, n: int):
    """Rotate-left of 64-bit words held as hi/lo uint32 halves by
    constant n."""
    if n >= 32:
        h, l, n = l, h, n - 32
    if n == 0:
        return h, l
    r = np.uint32(32 - n)
    n = np.uint32(n)
    return (h << n) | (l >> r), (l << n) | (h >> r)


def _round(a: jnp.ndarray, rc: jnp.ndarray) -> jnp.ndarray:
    """One Keccak-f round over a (2, 25, N) state (crypto/merlin.py
    `_keccak_f_py`, lane x + 5y; plane 0 the high halves)."""
    hi = [a[0, i] for i in range(25)]
    lo = [a[1, i] for i in range(25)]
    xor5 = lambda p, x: p[x] ^ p[x + 5] ^ p[x + 10] ^ p[x + 15] ^ p[x + 20]  # noqa: E731
    c = [(xor5(hi, x), xor5(lo, x)) for x in range(5)]
    d = []
    for x in range(5):
        rh, rl = _rotl(*c[(x + 1) % 5], 1)
        d.append((c[(x - 1) % 5][0] ^ rh, c[(x - 1) % 5][1] ^ rl))
    bh, bl = [None] * 25, [None] * 25
    for x in range(5):
        for y in range(5):
            to = y + 5 * ((2 * x + 3 * y) % 5)
            bh[to], bl[to] = _rotl(
                hi[x + 5 * y] ^ d[x][0], lo[x + 5 * y] ^ d[x][1], merlin._ROT[x][y]
            )
    chi = lambda b, x, y: b[x + 5 * y] ^ (~b[(x + 1) % 5 + 5 * y] & b[(x + 2) % 5 + 5 * y])  # noqa: E731
    hi = [chi(bh, x, y) for y in range(5) for x in range(5)]
    lo = [chi(bl, x, y) for y in range(5) for x in range(5)]
    hi[0] = hi[0] ^ rc[0]
    lo[0] = lo[0] ^ rc[1]
    return jnp.stack([jnp.stack(hi), jnp.stack(lo)])


def _keccak_f(state: jnp.ndarray) -> jnp.ndarray:
    out, _ = lax.scan(
        lambda a, rc: (_round(a, rc), None), state, jnp.asarray(_RC)
    )
    return out


def _to_lanes(b: jnp.ndarray) -> jnp.ndarray:
    """(200, N) state bytes -> (2, 25, N) hi/lo planes (lanes LE)."""
    w = b.astype(jnp.uint32).reshape(25, 2, 4, b.shape[-1])
    w = (
        w[:, :, 0]
        | (w[:, :, 1] << np.uint32(8))
        | (w[:, :, 2] << np.uint32(16))
        | (w[:, :, 3] << np.uint32(24))
    )  # (25, 2, N): the low word of a lane first, by byte order
    return jnp.stack([w[:, 1], w[:, 0]])


def _to_bytes(state: jnp.ndarray) -> jnp.ndarray:
    """(2, 25, N) hi/lo planes -> (200, N) state bytes."""
    w = jnp.stack([state[1], state[0]], axis=1)  # (25, 2, N), low word first
    shifts = np.array([0, 8, 16, 24], dtype=np.uint32)
    out = (w[:, :, None, :] >> jnp.asarray(shifts)[None, None, :, None]) & np.uint32(0xFF)
    return out.reshape(200, state.shape[-1]).astype(jnp.uint8)


def _block(rows: jnp.ndarray, const: np.ndarray, runs: list) -> jnp.ndarray:
    """One block's (200, N) bytes: the constant bytes, and XORed over
    them its operand rows, each run at its position."""
    out = jnp.broadcast_to(jnp.asarray(const)[:, None], (200, rows.shape[-1]))
    for pos, start, count in runs:
        run = rows[start : start + count]
        out = out ^ jnp.pad(run, ((pos, 200 - pos - count), (0, 0)))
    return out


def merlin_challenge(rows: jnp.ndarray) -> jnp.ndarray:
    """sr25519 challenges of N equal-length messages: (len + 64, N)
    uint8 rows of M || A || R -> (64, N) wide challenge bytes (LE).

    len is static: the transcript's framing is laid out at trace time
    (`_schedule`)."""
    plan = _schedule(rows.shape[0] - 64)
    rows = rows.astype(jnp.uint8)
    with jax.named_scope("merlin"):
        state = jnp.zeros((2, 25, rows.shape[-1]), dtype=jnp.uint32)
        for const, runs in plan.blocks:
            state = _keccak_f(state ^ _to_lanes(_block(rows, const, runs)))
        pos, n = plan.squeezed
        return _to_bytes(state)[pos : pos + n]
