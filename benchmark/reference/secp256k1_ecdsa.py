"""Plain reference: ECDSA over secp256k1 as upstream CometBFT verifies it.

Straightforward Python over integers, written from the published
descriptions and importing nothing from the program:

- the curve and its generator: SEC 2, section 2.4.1 (secp256k1).
- public keys: SEC 1, section 2.3.4, the 33-byte compressed form only
  (upstream crypto/secp256k1 PubKeySize = 33).
- verification: SEC 1, section 4.1.4, with e = SHA-256 of the message.

Choices and departures, each on purpose:
- The signature is the fixed 64-byte r || s, big-endian (upstream's
  format, not DER).
- The low-S rule: a signature whose s lies in the upper half of the
  group order is REFUSED, as upstream's VerifySignature does (the
  malleable twin (r, n - s) of a valid signature is not valid here).
- Arithmetic is affine with one modular inversion an addition: some
  15 ms a verification, so the benchmark asks it about a few dozen
  lanes.
"""

from __future__ import annotations

import hashlib

P = 2**256 - 2**32 - 977
N = 0xFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFEBAAEDCE6AF48A03BBFD25E8CD0364141
G = (0x79BE667EF9DCBBAC55A06295CE870B07029BFCDB2DCE28D959F2815B16F81798,
     0x483ADA7726A3C4655DA4FBFC0E1108A8FD17B448A68554199C47D08FFB10D4B8)


def _add(p, q):
    """Affine addition on y^2 = x^3 + 7; None is the point at infinity."""
    if p is None:
        return q
    if q is None:
        return p
    (x1, y1), (x2, y2) = p, q
    if x1 == x2:
        if (y1 + y2) % P == 0:
            return None
        lam = 3 * x1 * x1 * pow(2 * y1, -1, P) % P
    else:
        lam = (y2 - y1) * pow(x2 - x1, -1, P) % P
    x3 = (lam * lam - x1 - x2) % P
    return (x3, (lam * (x1 - x3) - y1) % P)


def _mul(k: int, p):
    acc = None
    while k:
        if k & 1:
            acc = _add(acc, p)
        p = _add(p, p)
        k >>= 1
    return acc


def decompress(pub: bytes):
    """SEC 1, 2.3.4: (x, y) of a 33-byte compressed key, or None."""
    if len(pub) != 33 or pub[0] not in (2, 3):
        return None
    x = int.from_bytes(pub[1:], "big")
    if x >= P:
        return None
    rhs = (x * x * x + 7) % P
    y = pow(rhs, (P + 1) // 4, P)  # P = 3 mod 4
    if y * y % P != rhs:
        return None
    if y & 1 != pub[0] & 1:
        y = P - y
    return (x, y)


def verify(pub: bytes, msg: bytes, sig: bytes) -> bool:
    if len(sig) != 64:
        return False
    r = int.from_bytes(sig[:32], "big")
    s = int.from_bytes(sig[32:], "big")
    if not (1 <= r < N and 1 <= s < N):
        return False
    if s > N // 2:
        return False  # the low-S rule
    q = decompress(pub)
    if q is None:
        return False
    e = int.from_bytes(hashlib.sha256(msg).digest(), "big")
    w = pow(s, -1, N)
    point = _add(_mul(e * w % N, G), _mul(r * w % N, q))
    return point is not None and point[0] % N == r
