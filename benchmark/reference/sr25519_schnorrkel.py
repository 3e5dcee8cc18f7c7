"""Plain reference: sr25519 (schnorrkel) signature verification.

Straightforward Python over integers, written from the published
descriptions and importing nothing from the program:

- Keccak-f[1600]: FIPS 202, section 3 (theta, rho, pi, chi, iota).
- STROBE-128/1600: the STROBE v1.0.2 specification (strobe.sourceforge.io),
  the operations merlin uses (meta-AD, AD, PRF), rate R = 166.
- merlin transcripts: merlin.cool ("Merlin v1.0": dom-sep, 4-byte
  little-endian lengths as meta-AD, challenge = PRF).
- ristretto255 decoding and equality: RFC 9496, sections 4.3.1 and 4.5.
- the verification equation: schnorrkel's `verify` (w3f/schnorrkel
  src/sign.rs): with k = challenge("sign:c"), accept iff
  [s]B - [k]A equals R as ristretto points.

Choices and departures, each on purpose:
- The signing context is the EMPTY context, as upstream CometBFT's
  crypto/sr25519 uses (`signingCtx = NewSigningContext([]byte{})`): the
  transcript is Transcript("SigningContext"), append_message("", ""),
  append_message("sign-bytes", msg).
- Transcript labels are schnorrkel's: "proto-name" = "Schnorr-sig",
  "sign:pk", "sign:R", and the 64-byte challenge "sign:c" reduced mod L.
- Byte 63 of a signature must carry schnorrkel's marker bit 0x80 (a
  signature without it is an ed25519 signature, and is refused); the
  scalar under it must be canonical (< L).
- schnorrkel compares the COMPRESSED [s]B - [k]A with the signature's R
  bytes. Here R is decoded (a non-canonical or invalid encoding is
  refused, as it can equal no compressed point) and the two points are
  compared with RFC 9496's equality, which needs no encoder.

One signature at a time, no batching, no tables: a verification takes
some 10 ms, so the benchmark asks it about a few dozen lanes.
"""

from __future__ import annotations

P = 2**255 - 19
L = 2**252 + 27742317777372353535851937790883648493
D = (-121665 * pow(121666, P - 2, P)) % P
SQRT_M1 = pow(2, (P - 1) // 4, P)

# the Ed25519 basepoint, which is ristretto255's generator (RFC 9496, 4.1)
_BY = (4 * pow(5, P - 2, P)) % P
_BX = 15112221349535400772501151409588531511454012693041857206046113283949847762202
BASE = (_BX, _BY, 1, (_BX * _BY) % P)
IDENTITY = (0, 1, 1, 0)

# ---------------------------------------------------------------------
# Keccak-f[1600] (FIPS 202) on 25 lanes of 64 bits

_MASK = (1 << 64) - 1
_ROUND_CONSTANTS = []
_ROTATIONS = [[0] * 5 for _ in range(5)]


def _init_keccak() -> None:
    # iota's constants from the degree-8 LFSR, rho's offsets from the
    # (x, y) -> (y, 2x + 3y) walk: FIPS 202, algorithms 5 and 2
    r = 1
    for _ in range(24):
        rc = 0
        for j in range(7):
            r = ((r << 1) ^ ((r >> 7) * 0x71)) % 256
            if r & 2:
                rc ^= 1 << ((1 << j) - 1)
        _ROUND_CONSTANTS.append(rc)
    x, y = 1, 0
    for t in range(24):
        _ROTATIONS[x][y] = ((t + 1) * (t + 2) // 2) % 64
        x, y = y, (2 * x + 3 * y) % 5


_init_keccak()


def _rol(v: int, n: int) -> int:
    return ((v << n) | (v >> (64 - n))) & _MASK if n else v


def keccak_f1600(state: bytearray) -> None:
    a = [[int.from_bytes(state[8 * (x + 5 * y):8 * (x + 5 * y) + 8], "little")
          for y in range(5)] for x in range(5)]
    for rc in _ROUND_CONSTANTS:
        c = [a[x][0] ^ a[x][1] ^ a[x][2] ^ a[x][3] ^ a[x][4] for x in range(5)]
        d = [c[(x - 1) % 5] ^ _rol(c[(x + 1) % 5], 1) for x in range(5)]
        a = [[a[x][y] ^ d[x] for y in range(5)] for x in range(5)]
        b = [[0] * 5 for _ in range(5)]
        for x in range(5):
            for y in range(5):
                b[y][(2 * x + 3 * y) % 5] = _rol(a[x][y], _ROTATIONS[x][y])
        a = [[b[x][y] ^ (~b[(x + 1) % 5][y] & _MASK & b[(x + 2) % 5][y])
              for y in range(5)] for x in range(5)]
        a[0][0] ^= rc
    for x in range(5):
        for y in range(5):
            state[8 * (x + 5 * y):8 * (x + 5 * y) + 8] = \
                a[x][y].to_bytes(8, "little")


# ---------------------------------------------------------------------
# STROBE-128/1600, the subset merlin uses

_R = 166
_FLAG_I, _FLAG_A, _FLAG_C, _FLAG_T, _FLAG_M, _FLAG_K = 1, 2, 4, 8, 16, 32


class Strobe128:
    def __init__(self, protocol_label: bytes):
        self.state = bytearray(200)
        self.state[0:6] = bytes([1, _R + 2, 1, 0, 1, 96])
        self.state[6:18] = b"STROBEv1.0.2"
        keccak_f1600(self.state)
        self.pos = 0
        self.pos_begin = 0
        self.cur_flags = 0
        self.meta_ad(protocol_label, False)

    def _run_f(self) -> None:
        self.state[self.pos] ^= self.pos_begin
        self.state[self.pos + 1] ^= 0x04
        self.state[_R + 1] ^= 0x80
        keccak_f1600(self.state)
        self.pos = 0
        self.pos_begin = 0

    def _absorb(self, data: bytes) -> None:
        for byte in data:
            self.state[self.pos] ^= byte
            self.pos += 1
            if self.pos == _R:
                self._run_f()

    def _squeeze(self, n: int) -> bytes:
        out = bytearray()
        for _ in range(n):
            out.append(self.state[self.pos])
            self.state[self.pos] = 0
            self.pos += 1
            if self.pos == _R:
                self._run_f()
        return bytes(out)

    def _begin_op(self, flags: int, more: bool) -> None:
        if more:
            assert flags == self.cur_flags
            return
        assert not flags & _FLAG_T
        old_begin = self.pos_begin
        self.pos_begin = self.pos + 1
        self.cur_flags = flags
        self._absorb(bytes([old_begin, flags]))
        if flags & (_FLAG_C | _FLAG_K) and self.pos != 0:
            self._run_f()

    def meta_ad(self, data: bytes, more: bool) -> None:
        self._begin_op(_FLAG_M | _FLAG_A, more)
        self._absorb(data)

    def ad(self, data: bytes, more: bool) -> None:
        self._begin_op(_FLAG_A, more)
        self._absorb(data)

    def prf(self, n: int) -> bytes:
        self._begin_op(_FLAG_I | _FLAG_A | _FLAG_C, False)
        return self._squeeze(n)


class Transcript:
    """A merlin transcript."""

    def __init__(self, label: bytes):
        self.strobe = Strobe128(b"Merlin v1.0")
        self.append_message(b"dom-sep", label)

    def append_message(self, label: bytes, message: bytes) -> None:
        self.strobe.meta_ad(label, False)
        self.strobe.meta_ad(len(message).to_bytes(4, "little"), True)
        self.strobe.ad(message, False)

    def challenge_bytes(self, label: bytes, n: int) -> bytes:
        self.strobe.meta_ad(label, False)
        self.strobe.meta_ad(n.to_bytes(4, "little"), True)
        return self.strobe.prf(n)


# ---------------------------------------------------------------------
# the curve: extended twisted Edwards coordinates (X : Y : Z : T), a = -1


def _add(p, q):
    x1, y1, z1, t1 = p
    x2, y2, z2, t2 = q
    a = (y1 - x1) * (y2 - x2) % P
    b = (y1 + x1) * (y2 + x2) % P
    c = 2 * D * t1 * t2 % P
    d = 2 * z1 * z2 % P
    e, f, g, h = b - a, d - c, d + c, b + a
    return (e * f % P, g * h % P, f * g % P, e * h % P)


def _neg(p):
    x, y, z, t = p
    return ((-x) % P, y, z, (-t) % P)


def _mul(k: int, p):
    acc = IDENTITY
    while k:
        if k & 1:
            acc = _add(acc, p)
        p = _add(p, p)
        k >>= 1
    return acc


def _is_negative(v: int) -> bool:
    return bool(v % P & 1)


def _sqrt_ratio_m1(u: int, v: int) -> tuple[bool, int]:
    """RFC 9496, 4.2: (was_square, sqrt(u / v) or sqrt(i * u / v))."""
    v3 = v * v % P * v % P
    v7 = v3 * v3 % P * v % P
    r = u * v3 % P * pow(u * v7 % P, (P - 5) // 8, P) % P
    check = v * r % P * r % P
    correct = check == u % P
    flipped = check == (-u) % P
    flipped_i = check == (-u) * SQRT_M1 % P
    if flipped or flipped_i:
        r = r * SQRT_M1 % P
    if _is_negative(r):
        r = (-r) % P
    return correct or flipped, r


def ristretto_decode(enc: bytes):
    """RFC 9496, 4.3.1; None for an encoding that is not canonical or is
    not a point."""
    if len(enc) != 32:
        return None
    s = int.from_bytes(enc, "little")
    if s >= P or s & 1:
        return None
    ss = s * s % P
    u1 = (1 - ss) % P
    u2 = (1 + ss) % P
    u2_sqr = u2 * u2 % P
    v = (-(D * u1 % P * u1) - u2_sqr) % P
    was_square, invsqrt = _sqrt_ratio_m1(1, v * u2_sqr % P)
    den_x = invsqrt * u2 % P
    den_y = invsqrt * den_x % P * v % P
    x = 2 * s * den_x % P
    if _is_negative(x):
        x = (-x) % P
    y = u1 * den_y % P
    t = x * y % P
    if not was_square or _is_negative(t) or y == 0:
        return None
    return (x, y, 1, t)


def ristretto_equal(p, q) -> bool:
    """RFC 9496, 4.5."""
    x1, y1, _, _ = p
    x2, y2, _, _ = q
    return (x1 * y2 - y1 * x2) % P == 0 or (y1 * y2 - x1 * x2) % P == 0


# ---------------------------------------------------------------------
# schnorrkel


def signing_transcript(context: bytes, msg: bytes) -> Transcript:
    t = Transcript(b"SigningContext")
    t.append_message(b"", context)
    t.append_message(b"sign-bytes", msg)
    return t


def challenge(pub: bytes, msg: bytes, r_bytes: bytes,
              context: bytes = b"") -> int:
    t = signing_transcript(context, msg)
    t.append_message(b"proto-name", b"Schnorr-sig")
    t.append_message(b"sign:pk", pub)
    t.append_message(b"sign:R", r_bytes)
    return int.from_bytes(t.challenge_bytes(b"sign:c", 64), "little") % L


def verify(pub: bytes, msg: bytes, sig: bytes, context: bytes = b"") -> bool:
    if len(pub) != 32 or len(sig) != 64:
        return False
    if not sig[63] & 0x80:
        return False  # not marked as a schnorrkel signature
    s = int.from_bytes(sig[32:], "little") & ((1 << 255) - 1)
    if s >= L:
        return False
    a = ristretto_decode(pub)
    r = ristretto_decode(sig[:32])
    if a is None or r is None:
        return False
    k = challenge(pub, msg, sig[:32], context)
    return ristretto_equal(_add(_mul(s, BASE), _neg(_mul(k, a))), r)
