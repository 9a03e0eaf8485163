"""sr25519 (schnorrkel over ristretto255) in plain Python and numpy.

Written from the specifications — RFC 9496 (ristretto255), the STROBE
v1.0.2 and Merlin v1.0 descriptions, schnorrkel's `sign.rs` — and
importing nothing of the program under test. The harness uses it twice:
gen.py signs the sr25519 votes of a seeded commit with it, and the
plain reference (commit_verify.py) verifies them with it.

Curve arithmetic is Python integers mod p = 2^255 - 19 on the twisted
Edwards curve -x^2 + y^2 = 1 + d x^2 y^2 in extended coordinates
(X, Y, Z, T). The Merlin transcripts of many signatures have one shape
(same labels, same lengths), so they run side by side: the STROBE
state is a (lanes, 200) byte array and keccak-f[1600] works on 25
columns of uint64, one row a signature.

A signature is valid iff  encode([s]B - [k]A) == R  with k the
transcript's challenge (schnorrkel `verify`: the marker bit 0x80 of
byte 63 must be set, s must be canonical). `verify_many` checks a whole
batch with one random linear combination,
    sum z_i ([s_i]B - [k_i]A_i - R_i) == 0,
by Pippenger's bucket method, and bisects a failing batch down to the
signatures that fail alone — the same verdicts as checking one by one,
about fifty times sooner for 5,000 signatures.
"""

from __future__ import annotations

import hashlib

import numpy as np

P = 2**255 - 19
L = 2**252 + 27742317777372353535851937790883648493
D = -121665 * pow(121666, P - 2, P) % P
SQRT_M1 = pow(2, (P - 1) // 4, P)
IDENTITY = (0, 1, 1, 0)


def _is_neg(x: int) -> bool:
    return x & 1 == 1


def _abs(x: int) -> int:
    return P - x if x & 1 else x


def sqrt_ratio_m1(u: int, v: int) -> tuple:
    """RFC 9496 4.2: (was_square, sqrt(u/v)) or sqrt(i*u/v)."""
    v3 = v * v % P * v % P
    v7 = v3 * v3 % P * v % P
    r = u * v3 % P * pow(u * v7 % P, (P - 5) // 8, P) % P
    check = v * r % P * r % P
    u_neg = (P - u) % P
    correct = check == u % P
    flipped = check == u_neg
    flipped_i = check == u_neg * SQRT_M1 % P
    if flipped or flipped_i:
        r = r * SQRT_M1 % P
    return (correct or flipped), _abs(r)


INVSQRT_A_MINUS_D = sqrt_ratio_m1(1, (-1 - D) % P)[1]


def decode(data: bytes):
    """RFC 9496 4.3.1; None for a string that is no encoding."""
    if len(data) != 32:
        return None
    s = int.from_bytes(data, "little")
    if s >= P or _is_neg(s):
        return None
    ss = s * s % P
    u1 = (1 - ss) % P
    u2 = (1 + ss) % P
    u2_sqr = u2 * u2 % P
    v = (-(D * u1 % P * u1) - u2_sqr) % P
    was_square, invsqrt = sqrt_ratio_m1(1, v * u2_sqr % P)
    den_x = invsqrt * u2 % P
    den_y = invsqrt * den_x % P * v % P
    x = _abs(2 * s * den_x % P)
    y = u1 * den_y % P
    t = x * y % P
    if not was_square or _is_neg(t) or y == 0:
        return None
    return (x, y, 1, t)


def encode(pt) -> bytes:
    """RFC 9496 4.3.2."""
    x0, y0, z0, t0 = pt
    u1 = (z0 + y0) * (z0 - y0) % P
    u2 = x0 * y0 % P
    _, invsqrt = sqrt_ratio_m1(1, u1 * u2 % P * u2 % P)
    den1 = invsqrt * u1 % P
    den2 = invsqrt * u2 % P
    z_inv = den1 * den2 % P * t0 % P
    if _is_neg(t0 * z_inv % P):
        x, y = y0 * SQRT_M1 % P, x0 * SQRT_M1 % P
        den_inv = den1 * INVSQRT_A_MINUS_D % P
    else:
        x, y = x0, y0
        den_inv = den2
    if _is_neg(x * z_inv % P):
        y = (P - y) % P
    return _abs(den_inv * (z0 - y) % P).to_bytes(32, "little")


def add(p1, p2):
    """add-2008-hwcd-3 for a = -1."""
    x1, y1, z1, t1 = p1
    x2, y2, z2, t2 = p2
    a = (y1 - x1) * (y2 - x2) % P
    b = (y1 + x1) * (y2 + x2) % P
    c = 2 * D * t1 % P * t2 % P
    d = 2 * z1 * z2 % P
    e, f, g, h = b - a, d - c, d + c, b + a
    return (e * f % P, g * h % P, f * g % P, e * h % P)


def double(p1):
    """dbl-2008-hwcd for a = -1."""
    x1, y1, z1, _t1 = p1
    a = x1 * x1 % P
    b = y1 * y1 % P
    c = 2 * z1 * z1 % P
    h = a + b
    e = h - (x1 + y1) * (x1 + y1) % P
    g = a - b
    f = c + g
    return (e * f % P, g * h % P, f * g % P, e * h % P)


def neg(p1):
    x, y, z, t = p1
    return ((P - x) % P, y, z, (P - t) % P)


def is_identity(pt) -> bool:
    """In the ristretto quotient: the identity's coset is x*y == 0."""
    x, y, _z, _t = pt
    return x % P == 0 or y % P == 0


def scalar_mult(k: int, pt):
    out = IDENTITY
    for bit in bin(k)[2:]:
        out = double(out)
        if bit == "1":
            out = add(out, pt)
    return out


# the ristretto255 generator is the ed25519 base point
_BY = 4 * pow(5, P - 2, P) % P
_BX = _abs(sqrt_ratio_m1((_BY * _BY - 1) % P, (D * _BY * _BY + 1) % P)[1])
BASE = (_BX, _BY, 1, _BX * _BY % P)

_BASE_TABLE: list = []


def base_mult(k: int):
    """[k]B from a table of j * 16^i * B (64 windows of 15 entries)."""
    if not _BASE_TABLE:
        pt = BASE
        for _ in range(64):
            row, acc = [], pt
            for _j in range(15):
                row.append(acc)
                acc = add(acc, pt)
            _BASE_TABLE.append(row)
            pt = acc  # 16 * pt
    out = IDENTITY
    for i in range(64):
        digit = (k >> (4 * i)) & 15
        if digit:
            out = add(out, _BASE_TABLE[i][digit - 1])
    return out


# -- keccak-f[1600], STROBE-128, Merlin: many lanes side by side -------

_RC = [
    0x0000000000000001, 0x0000000000008082, 0x800000000000808A, 0x8000000080008000,
    0x000000000000808B, 0x0000000080000001, 0x8000000080008081, 0x8000000000008009,
    0x000000000000008A, 0x0000000000000088, 0x0000000080008009, 0x000000008000000A,
    0x000000008000808B, 0x800000000000008B, 0x8000000000008089, 0x8000000000008003,
    0x8000000000008002, 0x8000000000000080, 0x000000000000800A, 0x800000008000000A,
    0x8000000080008081, 0x8000000000008080, 0x0000000080000001, 0x8000000080008008,
]  # fmt: skip
_ROT = [
    [0, 36, 3, 41, 18], [1, 44, 10, 45, 2], [62, 6, 43, 15, 61],
    [28, 55, 25, 21, 56], [27, 20, 39, 8, 14],
]  # fmt: skip  (indexed [x][y])


def _rol(a, n: int):
    return a if n == 0 else (a << np.uint64(n)) | (a >> np.uint64(64 - n))


def keccak_f1600(state: np.ndarray) -> None:
    """In place on a (lanes, 200) uint8 array."""
    words = state.view("<u8")  # (lanes, 25), word x + 5y
    a = [[words[:, x + 5 * y].copy() for y in range(5)] for x in range(5)]
    for rc in _RC:
        c = [a[x][0] ^ a[x][1] ^ a[x][2] ^ a[x][3] ^ a[x][4] for x in range(5)]
        d = [c[(x - 1) % 5] ^ _rol(c[(x + 1) % 5], 1) for x in range(5)]
        a = [[a[x][y] ^ d[x] for y in range(5)] for x in range(5)]
        b = [[None] * 5 for _ in range(5)]
        for x in range(5):
            for y in range(5):
                b[y][(2 * x + 3 * y) % 5] = _rol(a[x][y], _ROT[x][y])
        a = [
            [b[x][y] ^ (~b[(x + 1) % 5][y] & b[(x + 2) % 5][y]) for y in range(5)]
            for x in range(5)
        ]
        a[0][0] = a[0][0] ^ np.uint64(rc)
    for x in range(5):
        for y in range(5):
            words[:, x + 5 * y] = a[x][y]


_R = 166
_FLAG_I, _FLAG_A, _FLAG_C, _FLAG_M, _FLAG_K = 1, 2, 4, 16, 32


class Strobe:
    """STROBE-128/1600 as Merlin uses it, on `lanes` states at once.
    Data is bytes (the same in every lane) or a (lanes, n) uint8 array."""

    def __init__(self, lanes: int, label: bytes) -> None:
        self.state = np.zeros((lanes, 200), dtype=np.uint8)
        self.state[:, :6] = [1, _R + 2, 1, 0, 1, 96]
        self.state[:, 6:18] = np.frombuffer(b"STROBEv1.0.2", dtype=np.uint8)
        keccak_f1600(self.state)
        self.pos = self.pos_begin = self.cur_flags = 0
        self.meta_ad(label, False)

    def widen(self, lanes: int) -> "Strobe":
        """A one-lane state repeated over `lanes`."""
        out = object.__new__(Strobe)
        out.state = np.repeat(self.state, lanes, axis=0)
        out.pos, out.pos_begin, out.cur_flags = self.pos, self.pos_begin, self.cur_flags
        return out

    def _run_f(self) -> None:
        self.state[:, self.pos] ^= self.pos_begin
        self.state[:, self.pos + 1] ^= 0x04
        self.state[:, _R + 1] ^= 0x80
        keccak_f1600(self.state)
        self.pos = self.pos_begin = 0

    def _columns(self, data):
        if isinstance(data, (bytes, bytearray)):
            return np.frombuffer(bytes(data), dtype=np.uint8)[None, :]
        return data

    def _absorb(self, data) -> None:
        cols = self._columns(data)
        at = 0
        while at < cols.shape[1]:
            n = min(_R - self.pos, cols.shape[1] - at)
            self.state[:, self.pos : self.pos + n] ^= cols[:, at : at + n]
            self.pos += n
            at += n
            if self.pos == _R:
                self._run_f()

    def _squeeze(self, n_out: int) -> np.ndarray:
        out = np.zeros((self.state.shape[0], n_out), dtype=np.uint8)
        at = 0
        while at < n_out:
            n = min(_R - self.pos, n_out - at)
            out[:, at : at + n] = self.state[:, self.pos : self.pos + n]
            self.state[:, self.pos : self.pos + n] = 0
            self.pos += n
            at += n
            if self.pos == _R:
                self._run_f()
        return out

    def _begin_op(self, flags: int, more: bool) -> None:
        if more:
            if flags != self.cur_flags:
                raise ValueError("continued a different operation")
            return
        old_begin = self.pos_begin
        self.pos_begin = self.pos + 1
        self.cur_flags = flags
        self._absorb(bytes([old_begin, flags]))
        if flags & (_FLAG_C | _FLAG_K) and self.pos != 0:
            self._run_f()

    def meta_ad(self, data, more: bool) -> None:
        self._begin_op(_FLAG_M | _FLAG_A, more)
        self._absorb(data)

    def ad(self, data, more: bool) -> None:
        self._begin_op(_FLAG_A, more)
        self._absorb(data)

    def prf(self, n_out: int) -> np.ndarray:
        self._begin_op(_FLAG_I | _FLAG_A | _FLAG_C, False)
        return self._squeeze(n_out)


class Transcript:
    """Merlin v1.0 over a Strobe of many lanes."""

    def __init__(self, lanes: int, label: bytes) -> None:
        self.strobe = Strobe(lanes, b"Merlin v1.0")
        self.append(b"dom-sep", label)

    def widen(self, lanes: int) -> "Transcript":
        out = object.__new__(Transcript)
        out.strobe = self.strobe.widen(lanes)
        return out

    def append(self, label: bytes, message) -> None:
        n = len(message) if isinstance(message, (bytes, bytearray)) else message.shape[1]
        self.strobe.meta_ad(label, False)
        self.strobe.meta_ad(n.to_bytes(4, "little"), True)
        self.strobe.ad(message, False)

    def challenge(self, label: bytes, n_out: int) -> np.ndarray:
        self.strobe.meta_ad(label, False)
        self.strobe.meta_ad(n_out.to_bytes(4, "little"), True)
        return self.strobe.prf(n_out)


def _rows(items: list) -> np.ndarray:
    return np.frombuffer(b"".join(items), dtype=np.uint8).reshape(len(items), -1)


def challenges(pubs: list, msgs: list, big_rs: list, context: bytes = b"") -> list:
    """schnorrkel's k for each (public key, message, R): the messages
    of one call must have one length. Scalars mod L."""
    if not pubs:
        return []
    if len({len(m) for m in msgs}) != 1:
        raise ValueError("the messages of one call must have one length")
    prefix = Transcript(1, b"SigningContext")
    prefix.append(b"", context)
    t = prefix.widen(len(pubs))
    t.append(b"sign-bytes", _rows(msgs))
    t.append(b"proto-name", b"Schnorr-sig")
    t.append(b"sign:pk", _rows(pubs))
    t.append(b"sign:R", _rows(big_rs))
    wide = t.challenge(b"sign:c", 64)
    return [int.from_bytes(row.tobytes(), "little") % L for row in wide]


# -- keys, signing, verification --------------------------------------


def secret_scalar(material: bytes) -> int:
    return int.from_bytes(hashlib.sha512(b"sr25519-secret" + material).digest(), "little") % L


def public_key(secret: int) -> bytes:
    return encode(base_mult(secret))


def sign_many(secrets: list, pubs: list, msgs: list) -> list:
    """One signature a (secret, public key, message). The nonce is a
    hash of the secret and the message: any nonce gives a signature
    that verifies."""
    nonces = [
        int.from_bytes(
            hashlib.sha512(b"sr25519-nonce" + a.to_bytes(32, "little") + m).digest(), "little"
        ) % L
        for a, m in zip(secrets, msgs)
    ]  # fmt: skip
    big_rs = [encode(base_mult(r)) for r in nonces]
    ks = challenges(pubs, msgs, big_rs)
    out = []
    for a, r, k, big_r in zip(secrets, nonces, ks, big_rs):
        s = bytearray(((k * a + r) % L).to_bytes(32, "little"))
        s[31] |= 0x80  # schnorrkel's marker
        out.append(big_r + bytes(s))
    return out


def public_keys(secrets: list) -> list:
    """One chunk of chipbench/pool.py's map: a public key a secret."""
    return [public_key(a) for a in secrets]


def sign_jobs(jobs: list) -> list:
    """One chunk of the pool's map: [(secret, public key, message)]."""
    return sign_many([j[0] for j in jobs], [j[1] for j in jobs], [j[2] for j in jobs])


def _parse(sig: bytes):
    """(R bytes, s) or None: 64 bytes, marker set, s canonical."""
    if len(sig) != 64 or not sig[63] & 0x80:
        return None
    s = int.from_bytes(sig[32:63] + bytes([sig[63] & 0x7F]), "little")
    return (sig[:32], s) if s < L else None


def verify_one(pub: bytes, msg: bytes, sig: bytes) -> bool:
    parsed = _parse(sig)
    a_pt = decode(pub)
    if parsed is None or a_pt is None:
        return False
    big_r, s = parsed
    (k,) = challenges([pub], [msg], [big_r])
    return encode(add(base_mult(s), neg(scalar_mult(k, a_pt)))) == big_r


def _multi_scalar(pairs: list):
    """sum k_i * P_i by Pippenger's buckets."""
    if not pairs:
        return IDENTITY
    n = len(pairs)
    c = max(2, min(12, n.bit_length() - 2))
    top = max(k for k, _p in pairs).bit_length()
    out = IDENTITY
    for w in reversed(range(0, top, c)):
        for _ in range(c):
            out = double(out)
        buckets: dict = {}
        for k, pt in pairs:
            digit = (k >> w) & ((1 << c) - 1)
            if digit:
                have = buckets.get(digit)
                buckets[digit] = pt if have is None else add(have, pt)
        running, total = IDENTITY, IDENTITY
        for digit in range((1 << c) - 1, 0, -1):
            have = buckets.get(digit)
            if have is not None:
                running = add(running, have)
            total = add(total, running)
        out = add(out, total)
    return out


def verify_many(triples: list) -> list:
    """[bool] for [(pub, msg, sig)]: as verify_one would say of each."""
    n = len(triples)
    out = [False] * n
    parsed = [_parse(sig) for _p, _m, sig in triples]
    live = [i for i in range(n) if parsed[i] is not None]
    ks = dict(zip(live, challenges(
        [triples[i][0] for i in live], [triples[i][1] for i in live],
        [parsed[i][0] for i in live])))  # fmt: skip
    terms = {}
    for i in live:
        a_pt, r_pt = decode(triples[i][0]), decode(parsed[i][0])
        if a_pt is not None and r_pt is not None:
            terms[i] = (parsed[i][1], ks[i], neg(a_pt), neg(r_pt))
    seed = hashlib.sha512(b"".join(sig for _p, _m, sig in triples)).digest()

    def holds(idxs: list) -> bool:
        """sum z (s B - k A - R) == 0 over idxs, z of 128 bits."""
        b_scalar, pairs = 0, []
        for i in idxs:
            s, k, neg_a, neg_r = terms[i]
            z = int.from_bytes(hashlib.sha256(seed + i.to_bytes(4, "little")).digest()[:16], "little") | 1
            b_scalar = (b_scalar + z * s) % L
            pairs.append((z * k % L, neg_a))
            pairs.append((z, neg_r))
        return is_identity(add(base_mult(b_scalar), _multi_scalar(pairs)))

    def settle(idxs: list) -> None:
        if not idxs:
            return
        if len(idxs) <= 4:
            for i in idxs:
                out[i] = verify_one(*triples[i])
            return
        if holds(idxs):
            for i in idxs:
                out[i] = True
            return
        half = len(idxs) // 2
        settle(idxs[:half])
        settle(idxs[half:])

    settle(sorted(terms))
    return out
