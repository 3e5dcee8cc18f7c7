"""secp256k1 ECDSA keys (Bitcoin-style), host-side.

Behavior parity with reference crypto/secp256k1/secp256k1.go:
- 32-byte private keys; public keys in 33-byte compressed SEC1 form
  (0x02/0x03 ‖ x) (reference :154 PubKeySize comment).
- Sign: ECDSA over SHA-256(msg) with deterministic RFC 6979 nonces,
  R ‖ S fixed 64-byte encoding, S normalized to the lower half-order
  (reference :127-139 via btcec SignCompact).
- Verify: rejects sigs whose S is in the upper half-order (malleability
  rule, reference :193-205) and non-canonical encodings.
- Address = RIPEMD160(SHA256(compressed pubkey)) (reference :155-167).
- GenPrivKeySecp256k1(secret): sha256(secret) mod (n-1) + 1
  (reference :101-125, the FIPS 186-3 A.2.1 shaping).

No batch support, matching the reference ("no batch support" —
SURVEY §2.1): commits with secp256k1 validators take the per-signature
host path while ed25519 lanes ride the TPU kernel.

Verification routes to the native engine (csrc/secp256k1.inc: 5x52
field, wNAF Strauss–Shamir, worker-pool multi-verify) when the .so is
available — the reference gets the same from btcsuite/btcd/btcec's
optimized C-like Go. The textbook short-Weierstrass arithmetic over
python ints below is kept intact as the differential oracle and the
fallback when the toolchain is absent; signing (RFC 6979) is not on
the verify hot path and stays host-Python either way.
"""

from __future__ import annotations

import hashlib
import hmac
import secrets

from . import native as _native
from .keys import HostLeg, PrivKey, PubKey

KEY_TYPE = "tendermint/PubKeySecp256k1"
PRIV_KEY_SIZE = 32
PUB_KEY_SIZE = 33
SIG_SIZE = 64

# Curve: y^2 = x^3 + 7 over F_p
P = 2**256 - 2**32 - 977
N = 0xFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFEBAAEDCE6AF48A03BBFD25E8CD0364141
GX = 0x79BE667EF9DCBBAC55A06295CE870B07029BFCDB2DCE28D959F2815B16F81798
GY = 0x483ADA7726A3C4655DA4FBFC0E1108A8FD17B448A68554199C47D08FFB10D4B8
_HALF_N = N // 2


def _inv(a: int, m: int) -> int:
    return pow(a, m - 2, m)


# -- Jacobian point ops (None = infinity) ---------------------------------

def _jdbl(p):
    if p is None:
        return None
    x, y, z = p
    if y == 0:
        return None
    a = (x * x) % P
    b = (y * y) % P
    c = (b * b) % P
    d = (2 * ((x + b) * (x + b) - a - c)) % P
    e = (3 * a) % P
    f = (e * e) % P
    x3 = (f - 2 * d) % P
    y3 = (e * (d - x3) - 8 * c) % P
    z3 = (2 * y * z) % P
    return (x3, y3, z3)


def _jadd(p, q):
    if p is None:
        return q
    if q is None:
        return p
    x1, y1, z1 = p
    x2, y2, z2 = q
    z1z1 = (z1 * z1) % P
    z2z2 = (z2 * z2) % P
    u1 = (x1 * z2z2) % P
    u2 = (x2 * z1z1) % P
    s1 = (y1 * z2 * z2z2) % P
    s2 = (y2 * z1 * z1z1) % P
    if u1 == u2:
        if s1 != s2:
            return None
        return _jdbl(p)
    h = (u2 - u1) % P
    i = (4 * h * h) % P
    j = (h * i) % P
    r = (2 * (s2 - s1)) % P
    v = (u1 * i) % P
    x3 = (r * r - j - 2 * v) % P
    y3 = (r * (v - x3) - 2 * s1 * j) % P
    z3 = (2 * h * z1 * z2) % P
    return (x3, y3, z3)


def _jmul(k: int, pt):
    acc = None
    while k:
        if k & 1:
            acc = _jadd(acc, pt)
        pt = _jdbl(pt)
        k >>= 1
    return acc


def _to_affine(p):
    if p is None:
        return None
    x, y, z = p
    zi = _inv(z, P)
    zi2 = (zi * zi) % P
    return ((x * zi2) % P, (y * zi2 * zi) % P)


_G = (GX, GY, 1)


def _decompress(pub: bytes):
    """33-byte SEC1 compressed -> (x, y) or None if invalid."""
    if len(pub) != PUB_KEY_SIZE or pub[0] not in (2, 3):
        return None
    x = int.from_bytes(pub[1:], "big")
    if x >= P:
        return None
    y2 = (pow(x, 3, P) + 7) % P
    y = pow(y2, (P + 1) // 4, P)
    if (y * y) % P != y2:
        return None
    if (y & 1) != (pub[0] & 1):
        y = P - y
    return (x, y)


def _compress(x: int, y: int) -> bytes:
    return bytes([2 | (y & 1)]) + x.to_bytes(32, "big")


# -- RFC 6979 deterministic nonce ------------------------------------------

def _rfc6979_k(priv: int, digest: bytes) -> int:
    """Deterministic nonce per RFC 6979 §3.2 with HMAC-SHA256."""
    x = priv.to_bytes(32, "big")
    h1 = digest
    v = b"\x01" * 32
    k = b"\x00" * 32
    k = hmac.new(k, v + b"\x00" + x + h1, hashlib.sha256).digest()
    v = hmac.new(k, v, hashlib.sha256).digest()
    k = hmac.new(k, v + b"\x01" + x + h1, hashlib.sha256).digest()
    v = hmac.new(k, v, hashlib.sha256).digest()
    while True:
        v = hmac.new(k, v, hashlib.sha256).digest()
        t = int.from_bytes(v, "big")
        if 1 <= t < N:
            return t
        k = hmac.new(k, v + b"\x00", hashlib.sha256).digest()
        v = hmac.new(k, v, hashlib.sha256).digest()


class Secp256k1PubKey(PubKey):
    __slots__ = ("_b",)

    def __init__(self, b: bytes):
        if len(b) != PUB_KEY_SIZE:
            raise ValueError(f"secp256k1 pubkey must be {PUB_KEY_SIZE} bytes")
        self._b = bytes(b)

    def address(self) -> bytes:
        """RIPEMD160(SHA256(compressed pubkey)) — Bitcoin style."""
        sha = hashlib.sha256(self._b).digest()
        r = hashlib.new("ripemd160")
        r.update(sha)
        return r.digest()

    def bytes(self) -> bytes:
        return self._b

    def verify_signature(self, msg: bytes, sig: bytes) -> bool:
        if len(sig) != SIG_SIZE:
            return False
        if _native.secp256k1_available():
            return bool(_native.secp256k1_verify(self._b, msg, sig))
        return verify_python(self._b, msg, sig)

    def type_tag(self) -> str:
        return KEY_TYPE

    def __repr__(self):
        return f"Secp256k1PubKey({self._b.hex()[:16]}…)"


def verify_python(pub: bytes, msg: bytes, sig: bytes) -> bool:
    """The pure-Python ECDSA verify — fallback when the native engine
    is absent, and the differential oracle the native path is pinned
    against (tests/test_secp_native.py)."""
    if len(sig) != SIG_SIZE:
        return False
    r = int.from_bytes(sig[:32], "big")
    s = int.from_bytes(sig[32:], "big")
    if not (1 <= r < N and 1 <= s < N):
        return False
    if s > _HALF_N:  # malleability rule: reject upper-half S
        return False
    pt = _decompress(pub)
    if pt is None:
        return False
    e = int.from_bytes(hashlib.sha256(msg).digest(), "big") % N
    w = _inv(s, N)
    u1 = (e * w) % N
    u2 = (r * w) % N
    res = _jadd(_jmul(u1, _G), _jmul(u2, (pt[0], pt[1], 1)))
    aff = _to_affine(res)
    if aff is None:
        return False
    return aff[0] % N == r


def verify_many(items, nchunks: int = 0) -> list:
    """Per-item verdicts for [(pub33, msg, sig64), ...] — ONE native
    call across the worker pool when the engine is up (the commit
    partition path: secp256k1 has no batch equation, but the ctypes
    boundary and the GIL do not need to be crossed per signature), a
    Python loop otherwise. `nchunks` pins the native chunk split for
    determinism tests; semantics are chunk-count-independent."""
    if _native.secp256k1_available():
        # wrong-length pubs/sigs can't be blobbed columnar; substitute a
        # placeholder (always-invalid) row and force the verdict below
        well_formed = [len(p) == PUB_KEY_SIZE and len(s) == SIG_SIZE
                       for p, m, s in items]
        out = _native.secp256k1_multi_verify(
            [(p, m, s) if wf else (b"\x00" * PUB_KEY_SIZE, m,
                                   b"\x00" * SIG_SIZE)
             for (p, m, s), wf in zip(items, well_formed)],
            nchunks,
        )
        if out is not None:
            return [ok and wf for ok, wf in zip(out, well_formed)]
    return [verify_python(p, m, s) for p, m, s in items]


def submit_many(items) -> HostLeg:
    """verify_many from a worker thread, launched now, behind the
    pending interface of the batch verifiers: result() gives (all ok,
    per-item verdicts). The seam a commit's secp256k1 partition takes
    so that it runs under the commit's device leg."""
    items = list(items)

    def run():
        bits = verify_many(items)
        return all(bits), bits

    return HostLeg(run)


class Secp256k1PrivKey(PrivKey):
    __slots__ = ("_d",)

    def __init__(self, key_bytes: bytes):
        if len(key_bytes) != PRIV_KEY_SIZE:
            raise ValueError("secp256k1 privkey must be 32 bytes")
        d = int.from_bytes(key_bytes, "big")
        if not (1 <= d < N):
            raise ValueError("secp256k1 privkey out of range")
        self._d = d

    @classmethod
    def generate(cls) -> "Secp256k1PrivKey":
        while True:
            b = secrets.token_bytes(32)
            d = int.from_bytes(b, "big")
            if 1 <= d < N:
                return cls(b)

    @classmethod
    def from_secret(cls, secret: bytes) -> "Secp256k1PrivKey":
        """GenPrivKeySecp256k1: sha256(secret) mod (n-1), plus 1."""
        fe = int.from_bytes(hashlib.sha256(secret).digest(), "big")
        d = fe % (N - 1) + 1
        return cls(d.to_bytes(32, "big"))

    def sign(self, msg: bytes) -> bytes:
        digest = hashlib.sha256(msg).digest()
        e = int.from_bytes(digest, "big") % N
        k = _rfc6979_k(self._d, digest)
        while True:
            x, _ = _to_affine(_jmul(k, _G))
            r = x % N
            if r != 0:
                s = (_inv(k, N) * (e + r * self._d)) % N
                if s != 0:
                    break
            k = (k + 1) % N or 1
        if s > _HALF_N:
            s = N - s  # lower-S normalization
        return r.to_bytes(32, "big") + s.to_bytes(32, "big")

    def pub_key(self) -> Secp256k1PubKey:
        x, y = _to_affine(_jmul(self._d, _G))
        return Secp256k1PubKey(_compress(x, y))

    def bytes(self) -> bytes:
        return self._d.to_bytes(32, "big")

    def type_tag(self) -> str:
        return KEY_TYPE
