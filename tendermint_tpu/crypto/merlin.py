"""Merlin transcripts over STROBE-128 (Keccak-f[1600]).

The Fiat-Shamir transcript construction used by schnorrkel/sr25519
(reference: crypto/sr25519 via the curve25519-voi dependency, which is
schnorrkel-compatible; merlin spec: merlin.cool, STROBE spec:
strobe.sourceforge.io). The host implementation, and the oracle of the
device form of schnorrkel's signing transcript (ops/merlin_kernel.py,
which replays `_Strobe128` over lengths alone to lay the transcript
out at trace time).
"""

from __future__ import annotations

import ctypes
import struct

import numpy as np

__all__ = ["Transcript", "TranscriptBatch"]

# -- Keccak-f[1600] ---------------------------------------------------------

_ROUNDS = 24
_RC = [
    0x0000000000000001, 0x0000000000008082, 0x800000000000808A,
    0x8000000080008000, 0x000000000000808B, 0x0000000080000001,
    0x8000000080008081, 0x8000000000008009, 0x000000000000008A,
    0x0000000000000088, 0x0000000080008009, 0x000000008000000A,
    0x000000008000808B, 0x800000000000008B, 0x8000000000008089,
    0x8000000000008003, 0x8000000000008002, 0x8000000000000080,
    0x000000000000800A, 0x800000008000000A, 0x8000000080008081,
    0x8000000000008080, 0x0000000080000001, 0x8000000080008008,
]
# rotation offsets r[x][y]
_ROT = [
    [0, 36, 3, 41, 18],
    [1, 44, 10, 45, 2],
    [62, 6, 43, 15, 61],
    [28, 55, 25, 21, 56],
    [27, 20, 39, 8, 14],
]
_MASK = (1 << 64) - 1


def _rotl(v: int, n: int) -> int:
    return ((v << n) | (v >> (64 - n))) & _MASK


# Native Keccak-f (tendermint_tpu/native/keccakf.c, ~0.5 µs/permutation
# vs ~1 ms in Python); loaded lazily so importing this module never
# triggers a compile. None = not yet probed, False = unavailable.
_NATIVE = None


def _native_lib():
    global _NATIVE
    if _NATIVE is None:
        from .. import native

        _NATIVE = native.keccakf_lib() or False
    return _NATIVE or None


def _keccak_f(state: bytearray) -> None:
    """In-place permutation of the 200-byte state (lanes LE u64).
    Dispatches to the native library when available; the pure-Python
    body below is the fallback and the differential oracle."""
    lib = _native_lib()
    if lib is not None:
        lib.tm_keccakf(
            ctypes.addressof(ctypes.c_char.from_buffer(state))
        )
        return
    _keccak_f_py(state)


def _keccak_f_py(state: bytearray) -> None:
    lanes = list(struct.unpack("<25Q", state))
    A = [[lanes[x + 5 * y] for y in range(5)] for x in range(5)]
    for rnd in range(_ROUNDS):
        # theta
        C = [A[x][0] ^ A[x][1] ^ A[x][2] ^ A[x][3] ^ A[x][4] for x in range(5)]
        D = [C[(x - 1) % 5] ^ _rotl(C[(x + 1) % 5], 1) for x in range(5)]
        for x in range(5):
            for y in range(5):
                A[x][y] ^= D[x]
        # rho + pi
        B = [[0] * 5 for _ in range(5)]
        for x in range(5):
            for y in range(5):
                B[y][(2 * x + 3 * y) % 5] = _rotl(A[x][y], _ROT[x][y])
        # chi
        for x in range(5):
            for y in range(5):
                A[x][y] = B[x][y] ^ ((~B[(x + 1) % 5][y]) & B[(x + 2) % 5][y])
        # iota
        A[0][0] ^= _RC[rnd]
    out = [A[x % 5][x // 5] for x in range(25)]
    state[:] = struct.pack("<25Q", *[v & _MASK for v in out])


# -- STROBE-128 -------------------------------------------------------------

_R = 166  # rate for 128-bit security: 200 - 32 - 2
_FLAG_I = 1
_FLAG_A = 1 << 1
_FLAG_C = 1 << 2
_FLAG_T = 1 << 3
_FLAG_M = 1 << 4
_FLAG_K = 1 << 5


def _initial_state() -> bytearray:
    st = bytearray(200)
    st[0:6] = bytes([1, _R + 2, 1, 0, 1, 96])
    st[6:18] = b"STROBEv1.0.2"
    _keccak_f(st)
    return st


_INIT = None  # computed once


class _Strobe128:
    """The merlin subset of STROBE-128: meta-AD, AD, PRF, KEY."""

    def __init__(self, protocol_label: bytes) -> None:
        global _INIT
        if _INIT is None:
            _INIT = _initial_state()
        self.state = bytearray(_INIT)
        self.pos = 0
        self.pos_begin = 0
        self.cur_flags = 0
        self.meta_ad(protocol_label, False)

    # operations

    def meta_ad(self, data: bytes, more: bool) -> None:
        self._begin_op(_FLAG_M | _FLAG_A, more)
        self._absorb(data)

    def ad(self, data: bytes, more: bool) -> None:
        self._begin_op(_FLAG_A, more)
        self._absorb(data)

    def prf(self, n: int, more: bool) -> bytes:
        self._begin_op(_FLAG_I | _FLAG_A | _FLAG_C, more)
        return self._squeeze(n)

    def key(self, data: bytes, more: bool) -> None:
        self._begin_op(_FLAG_A | _FLAG_C, more)
        self._overwrite(data)

    # internals

    def _begin_op(self, flags: int, more: bool) -> None:
        if more:
            if flags != self.cur_flags:
                raise ValueError("'more' must continue the same operation")
            return
        old_begin = self.pos_begin
        self.pos_begin = self.pos + 1
        self.cur_flags = flags
        self._absorb(bytes([old_begin, flags]))
        if (flags & (_FLAG_C | _FLAG_K)) and self.pos != 0:
            self._run_f()

    def _absorb(self, data: bytes) -> None:
        # sliced: XOR whole chunks via big-int ops (C speed) instead of
        # a per-byte Python loop; permutation cadence is unchanged
        off = 0
        n = len(data)
        while off < n:
            take = min(n - off, _R - self.pos)
            p = self.pos
            chunk = data[off : off + take]
            cur = self.state[p : p + take]
            self.state[p : p + take] = (
                int.from_bytes(cur, "little")
                ^ int.from_bytes(chunk, "little")
            ).to_bytes(take, "little")
            self.pos += take
            off += take
            if self.pos == _R:
                self._run_f()

    def _overwrite(self, data: bytes) -> None:
        off = 0
        n = len(data)
        while off < n:
            take = min(n - off, _R - self.pos)
            self.state[self.pos : self.pos + take] = data[
                off : off + take
            ]
            self.pos += take
            off += take
            if self.pos == _R:
                self._run_f()

    def _squeeze(self, n: int) -> bytes:
        out = bytearray()
        while len(out) < n:
            take = min(n - len(out), _R - self.pos)
            p = self.pos
            out += self.state[p : p + take]
            self.state[p : p + take] = bytes(take)
            self.pos += take
            if self.pos == _R:
                self._run_f()
        return bytes(out)

    def _run_f(self) -> None:
        self.state[self.pos] ^= self.pos_begin
        self.state[self.pos + 1] ^= 0x04
        self.state[_R + 1] ^= 0x80
        _keccak_f(self.state)
        self.pos = 0
        self.pos_begin = 0


# -- merlin transcript ------------------------------------------------------

_MERLIN_LABEL = b"Merlin v1.0"


class Transcript:
    """merlin.Transcript: labeled append/challenge over STROBE-128."""

    def __init__(self, label: bytes) -> None:
        self._strobe = _Strobe128(_MERLIN_LABEL)
        self.append_message(b"dom-sep", label)

    def clone(self) -> "Transcript":
        t = object.__new__(Transcript)
        t._strobe = object.__new__(_Strobe128)
        t._strobe.state = bytearray(self._strobe.state)
        t._strobe.pos = self._strobe.pos
        t._strobe.pos_begin = self._strobe.pos_begin
        t._strobe.cur_flags = self._strobe.cur_flags
        return t

    def append_message(self, label: bytes, message: bytes) -> None:
        self._strobe.meta_ad(label, False)
        self._strobe.meta_ad(struct.pack("<I", len(message)), True)
        self._strobe.ad(message, False)

    def append_u64(self, label: bytes, value: int) -> None:
        self.append_message(label, struct.pack("<Q", value))

    def challenge_bytes(self, label: bytes, n: int) -> bytes:
        self._strobe.meta_ad(label, False)
        self._strobe.meta_ad(struct.pack("<I", n), True)
        return self._strobe.prf(n, False)


# -- batched transcripts ----------------------------------------------------


class _StrobeBatch:
    """G STROBE-128 states advancing in lockstep.

    The STROBE position/flag state machine depends only on operation
    *lengths*, so G transcripts whose appended messages are
    equal-length per step share one control flow: the 200-byte states
    live in a (G, 200) array, absorbs are vectorized XORs, and the
    permutation runs once per step over the whole group —
    tm_keccakf_n in the native library (one ctypes call), the
    per-state Python permutation as fallback. This is what makes host
    prep for sr25519 device batches scale (one merlin challenge per
    signature; crypto/sr25519.py challenge_batch)."""

    def __init__(self, template: "_Strobe128", g: int) -> None:
        self.states = np.tile(
            np.frombuffer(bytes(template.state), dtype=np.uint8), (g, 1)
        )
        self.pos = template.pos
        self.pos_begin = template.pos_begin
        self.cur_flags = template.cur_flags

    def _run_f(self) -> None:
        self.states[:, self.pos] ^= self.pos_begin
        self.states[:, self.pos + 1] ^= 0x04
        self.states[:, _R + 1] ^= 0x80
        lib = _native_lib()
        if lib is not None:
            st = np.ascontiguousarray(self.states)
            lib.tm_keccakf_n(
                st.ctypes.data_as(ctypes.c_void_p), st.shape[0]
            )
            self.states = st
        else:
            for i in range(self.states.shape[0]):
                row = bytearray(self.states[i].tobytes())
                _keccak_f_py(row)
                self.states[i] = np.frombuffer(row, dtype=np.uint8)
        self.pos = 0
        self.pos_begin = 0

    def _absorb(self, data: np.ndarray) -> None:
        """data: (G, k) uint8 — per-transcript bytes, equal length."""
        off = 0
        k = data.shape[1]
        while off < k:
            take = min(k - off, _R - self.pos)
            self.states[:, self.pos : self.pos + take] ^= data[
                :, off : off + take
            ]
            self.pos += take
            off += take
            if self.pos == _R:
                self._run_f()

    def _absorb_const(self, data: bytes) -> None:
        self._absorb(
            np.tile(
                np.frombuffer(data, dtype=np.uint8),
                (self.states.shape[0], 1),
            )
        )

    def _begin_op(self, flags: int, more: bool) -> None:
        if more:
            if flags != self.cur_flags:
                raise ValueError("'more' must continue the same operation")
            return
        old_begin = self.pos_begin
        self.pos_begin = self.pos + 1
        self.cur_flags = flags
        self._absorb_const(bytes([old_begin, flags]))
        if (flags & (_FLAG_C | _FLAG_K)) and self.pos != 0:
            self._run_f()

    def meta_ad_const(self, data: bytes, more: bool) -> None:
        self._begin_op(_FLAG_M | _FLAG_A, more)
        self._absorb_const(data)

    def ad(self, data: np.ndarray, more: bool) -> None:
        self._begin_op(_FLAG_A, more)
        self._absorb(data)

    def prf(self, n: int, more: bool) -> np.ndarray:
        self._begin_op(_FLAG_I | _FLAG_A | _FLAG_C, more)
        out = np.empty((self.states.shape[0], n), dtype=np.uint8)
        got = 0
        while got < n:
            take = min(n - got, _R - self.pos)
            out[:, got : got + take] = self.states[
                :, self.pos : self.pos + take
            ]
            self.states[:, self.pos : self.pos + take] = 0
            self.pos += take
            got += take
            if self.pos == _R:
                self._run_f()
        return out


class TranscriptBatch:
    """G merlin transcripts advancing in lockstep (see _StrobeBatch).

    Constructed from a prototype Transcript whose state every group
    member shares (e.g. the constant signing-context prefix); appended
    messages must be equal-length across the group at each step —
    callers group their batch by message length."""

    def __init__(self, prototype: Transcript, g: int) -> None:
        self._strobe = _StrobeBatch(prototype._strobe, g)

    def append_message_const(self, label: bytes, message: bytes) -> None:
        self._strobe.meta_ad_const(label, False)
        self._strobe.meta_ad_const(struct.pack("<I", len(message)), True)
        self._strobe.ad(
            np.tile(
                np.frombuffer(message, dtype=np.uint8),
                (self._strobe.states.shape[0], 1),
            ),
            False,
        )

    def append_messages(self, label: bytes, messages: np.ndarray) -> None:
        """messages: (G, k) uint8 — one equal-length message per
        transcript."""
        self._strobe.meta_ad_const(label, False)
        self._strobe.meta_ad_const(
            struct.pack("<I", messages.shape[1]), True
        )
        self._strobe.ad(messages, False)

    def challenge_bytes(self, label: bytes, n: int) -> np.ndarray:
        """(G, n) uint8 challenge bytes."""
        self._strobe.meta_ad_const(label, False)
        self._strobe.meta_ad_const(struct.pack("<I", n), True)
        return self._strobe.prf(n, False)
