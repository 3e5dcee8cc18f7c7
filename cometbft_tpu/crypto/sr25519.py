"""sr25519 (schnorrkel) keys: Schnorr over ristretto255 with merlin
transcripts.

Behavior parity with reference crypto/sr25519/ (which delegates to
curve25519-voi's schnorrkel implementation):
- 32-byte MiniSecretKey, expanded in Ed25519 mode: SHA-512(mini),
  clamp the low half like ed25519, divide by the cofactor (schnorrkel's
  scalar convention), nonce = high half (privkey.go:15's signingCtx and
  UnmarshalJSON's ExpandEd25519).
- Signing context: merlin Transcript("SigningContext") absorbing the
  empty context label, then per-message "sign-bytes" (reference
  privkey.go:47 NewTranscriptBytes).
- Sign: proto-name "Schnorr-sig", commit pk, witness R = r·B, commit R,
  challenge scalar c = wide-reduced 64-byte challenge "sign:c",
  s = c·key + r; signature = R ‖ s with schnorrkel's bit-255 marker.
- Verify: recompute c from the same transcript, accept iff
  encode(s·B − c·A) == R_bytes (ristretto encoding equality).
- Batch verification: one random-linear-combination check over a
  Pippenger multi-scalar multiplication (reference
  crypto/sr25519/batch.go via schnorrkel VerifyBatch), falling back to
  a per-signature scan for the blame bitmap when the combination
  fails — behind the same BatchVerifier seam (crypto/batch.py).

Address = SHA256-20 of the 32-byte public key (reference pubkey.go:27).
"""

from __future__ import annotations

import hashlib
import secrets

from . import ristretto as R
from .keys import BatchVerifier, HostLeg, PrivKey, PubKey, tmhash20
from .merlin import Transcript

KEY_TYPE = "tendermint/PubKeySr25519"
PUB_KEY_SIZE = 32
PRIV_KEY_SIZE = 32
SIG_SIZE = 64

L = R.ref.L


def _signing_context_transcript(msg: bytes) -> Transcript:
    """signingCtx = NewSigningContext([]byte{}); .NewTranscriptBytes(msg)."""
    t = Transcript(b"SigningContext")
    t.append_message(b"", b"")
    t.append_message(b"sign-bytes", msg)
    return t


def _challenge_scalar(t: Transcript, label: bytes) -> int:
    return int.from_bytes(t.challenge_bytes(label, 64), "little") % L


def _expand_ed25519(mini: bytes) -> tuple[int, bytes]:
    """(key scalar, nonce) — schnorrkel ExpandEd25519."""
    h = hashlib.sha512(mini).digest()
    key = bytearray(h[:32])
    key[0] &= 248
    key[31] &= 63
    key[31] |= 64
    # divide_scalar_bytes_by_cofactor: clamped value ≡ 0 (mod 8), exact
    return int.from_bytes(key, "little") >> 3, h[32:]


def _verify_one(pub: bytes, msg: bytes, sig: bytes) -> bool:
    if len(sig) != SIG_SIZE or not (sig[63] & 0x80):
        return False  # missing schnorrkel v1 marker
    if len(pub) == PUB_KEY_SIZE:
        # the native batch entry with n=1 is the exact single-sig check:
        # z·(s·B − c·A − R) lands in the ristretto identity coset iff
        # s·B − c·A ristretto-equals R (z odd ⇒ invertible, and the
        # 4-torsion coset is closed under odd scalars), which is the
        # encode() comparison below
        import os as _os

        from . import native

        got = native.sr25519_batch_verify([(pub, msg, sig)],
                                          _os.urandom(16))
        if got is not None:
            return got
    a_pt = R.decode(pub)
    if a_pt is None:
        return False
    r_bytes = sig[:32]
    s_enc = bytearray(sig[32:])
    s_enc[31] &= 0x7F
    s = int.from_bytes(s_enc, "little")
    if s >= L:
        return False
    if R.decode(r_bytes) is None:
        return False
    t = _signing_context_transcript(msg)
    t.append_message(b"proto-name", b"Schnorr-sig")
    t.append_message(b"sign:pk", pub)
    t.append_message(b"sign:R", r_bytes)
    c = _challenge_scalar(t, b"sign:c")
    # s·B − c·A must encode to R
    lhs = R.add(R.scalar_mul(s, R.BASE), R.neg(R.scalar_mul(c, a_pt)))
    return R.encode(lhs) == r_bytes


class Sr25519PubKey(PubKey):
    __slots__ = ("_b",)

    def __init__(self, b: bytes):
        if len(b) != PUB_KEY_SIZE:
            raise ValueError(f"sr25519 pubkey must be {PUB_KEY_SIZE} bytes")
        self._b = bytes(b)

    def address(self) -> bytes:
        return tmhash20(self._b)

    def bytes(self) -> bytes:
        return self._b

    def verify_signature(self, msg: bytes, sig: bytes) -> bool:
        return _verify_one(self._b, msg, sig)

    def type_tag(self) -> str:
        return KEY_TYPE

    def __repr__(self):
        return f"Sr25519PubKey({self._b.hex()[:16]}…)"


class Sr25519PrivKey(PrivKey):
    __slots__ = ("_mini", "_key", "_nonce", "_pub")

    def __init__(self, mini: bytes):
        if len(mini) != PRIV_KEY_SIZE:
            raise ValueError("sr25519 privkey must be 32 bytes (MiniSecretKey)")
        self._mini = bytes(mini)
        self._key, self._nonce = _expand_ed25519(self._mini)
        self._pub = R.encode(R.scalar_mul(self._key, R.BASE))

    @classmethod
    def generate(cls) -> "Sr25519PrivKey":
        return cls(secrets.token_bytes(32))

    @classmethod
    def from_secret(cls, secret: bytes) -> "Sr25519PrivKey":
        return cls(hashlib.sha256(secret).digest())

    def sign(self, msg: bytes) -> bytes:
        t = _signing_context_transcript(msg)
        t.append_message(b"proto-name", b"Schnorr-sig")
        t.append_message(b"sign:pk", self._pub)
        # witness scalar: transcript-bound nonce + fresh randomness
        wt = t.clone()
        wt.append_message(b"signing", self._nonce)
        rnd = secrets.token_bytes(32)
        r = int.from_bytes(
            wt.challenge_bytes(b"", 64) + rnd, "little"
        ) % L
        r_bytes = R.encode(R.scalar_mul(r, R.BASE))
        t.append_message(b"sign:R", r_bytes)
        c = _challenge_scalar(t, b"sign:c")
        s = (c * self._key + r) % L
        s_enc = bytearray(s.to_bytes(32, "little"))
        s_enc[31] |= 0x80  # schnorrkel v1 marker
        return r_bytes + bytes(s_enc)

    def pub_key(self) -> Sr25519PubKey:
        return Sr25519PubKey(self._pub)

    def bytes(self) -> bytes:
        return self._mini

    def type_tag(self) -> str:
        return KEY_TYPE


class Sr25519BatchVerifier(BatchVerifier):
    """BatchVerifier seam for sr25519 (reference crypto/sr25519/batch.go).

    Batches of >=4 verify as ONE random-linear-combination multi-scalar
    multiplication (_verify_rlc); transcript hashing stays sequential
    per message (inherent to merlin), but the point arithmetic — the
    actual cost — collapses into a shared Pippenger accumulation.
    """

    def __init__(self, backend: str = "host"):
        self._items: list[tuple[bytes, bytes, bytes]] = []
        self.backend = backend

    def add(self, pub_key: PubKey, msg: bytes, sig: bytes) -> bool:
        if not isinstance(pub_key, Sr25519PubKey):
            return False
        if len(sig) != SIG_SIZE:
            return False
        self._items.append((pub_key.bytes(), msg, sig))
        return True

    def add_rows(self, rows) -> None:
        """add() for a commit's worth of (pub, msg, sig) rows whose
        caller has checked the key type and the 64-byte signatures (the
        columnar commit path gates on both)."""
        self._items.extend(rows)

    def count(self) -> int:
        return len(self._items)

    def verify(self) -> tuple[bool, list[bool]]:
        return _verify_batch(self._items)

    def submit(self) -> HostLeg:
        """The same verdict from a worker thread, launched now: the
        native batch is one ctypes call, so it runs under whatever the
        caller does until result() (a commit's device leg). The items
        are snapshotted: the verifier may be reused after submit()."""
        items = list(self._items)
        return HostLeg(lambda: _verify_batch(items))


def _verify_batch(items) -> tuple[bool, list[bool]]:
    if not items:
        return False, []
    if len(items) >= 4 and _verify_rlc(items):
        return True, [True] * len(items)
    # batch failed (or tiny): per-signature scan gives the bitmap
    # (reference batch.go falls back the same way)
    bits = [_verify_one(p, m, s) for p, m, s in items]
    return all(bits), bits


def _msm(pairs):
    """Multi-scalar multiplication sum(k_i * P_i) via Pippenger bucket
    accumulation, window c=8 (the host-side analogue of the reference's
    curve25519-voi MultiscalarMul used by schnorrkel VerifyBatch)."""
    C_BITS = 8
    K = (1 << C_BITS) - 1
    if not pairs:
        return R.IDENTITY
    max_bits = max(k.bit_length() for k, _ in pairs) or 1
    n_windows = (max_bits + C_BITS - 1) // C_BITS
    acc = R.IDENTITY
    for w in range(n_windows - 1, -1, -1):
        for _ in range(C_BITS if acc is not R.IDENTITY else 0):
            acc = R.add(acc, acc)
        buckets = [None] * (K + 1)
        for k, p in pairs:
            d = (k >> (w * C_BITS)) & K
            if d:
                buckets[d] = p if buckets[d] is None else R.add(buckets[d], p)
        # sum_d d*bucket[d] via suffix running sums
        running = total = None
        for d in range(K, 0, -1):
            if buckets[d] is not None:
                running = (
                    buckets[d] if running is None
                    else R.add(running, buckets[d])
                )
            if running is not None:
                total = running if total is None else R.add(total, running)
        if total is not None:
            acc = R.add(acc, total)
    return acc


def _verify_rlc(items) -> bool:
    """One random-linear-combination check for the whole batch
    (reference crypto/sr25519/batch.go via schnorrkel VerifyBatch):

        [sum z_i s_i]B - sum [z_i c_i]A_i - sum [z_i]R_i == identity

    with fresh 128-bit z_i. False = some signature is bad (or a point
    failed to decode); the caller re-scans per-signature."""
    import os as _os

    from . import native

    # whole-batch native path: ristretto decode + merlin transcripts +
    # mod-L residue + the Pippenger identity check in ONE ctypes call
    # (csrc/sr25519_native.inc). The per-signature Python below — one
    # sqrt chain per decode, ~8 keccaks of STROBE bookkeeping per
    # transcript — cost about 200 ms a 1000 signatures on one host
    # core before the native path; it stays as oracle and fallback.
    if any(len(p) != 32 or len(s) != SIG_SIZE for p, _, s in items):
        return False  # can't blob columnar; Python loop rejects too
    got = native.sr25519_batch_verify(
        items, _os.urandom(16 * len(items)))
    if got is not None:
        return got

    pairs = []
    zs_sum = 0
    for pub, msg, sig in items:
        if len(sig) != SIG_SIZE or not (sig[63] & 0x80):
            return False
        a_pt = R.decode(pub)
        r_pt = R.decode(sig[:32])
        if a_pt is None or r_pt is None:
            return False
        s_enc = bytearray(sig[32:])
        s_enc[31] &= 0x7F
        s = int.from_bytes(s_enc, "little")
        if s >= L:
            return False
        t = _signing_context_transcript(msg)
        t.append_message(b"proto-name", b"Schnorr-sig")
        t.append_message(b"sign:pk", pub)
        t.append_message(b"sign:R", sig[:32])
        c = _challenge_scalar(t, b"sign:c")
        z = int.from_bytes(_os.urandom(16), "little") | 1
        zs_sum = (zs_sum + z * s) % L
        pairs.append(((z * c) % L, R.neg(a_pt)))
        pairs.append((z, R.neg(r_pt)))
    pairs.append((zs_sum, R.BASE))
    # the MSM is pure Edwards arithmetic on Z=1 coset representatives
    # (ristretto decode + neg + BASE all keep Z=1): one native Pippenger
    # call replaces ~130 ms of Python bucket accumulation per 256-sig
    # batch (the reference gets this from curve25519-voi MultiscalarMul)
    from . import native

    got = native.edwards_msm_is_identity(
        [(k, (p[0] % R.P, p[1] % R.P)) for k, p in pairs]
    )
    if got is not None:
        return got
    sx, sy, sz, _ = _msm(pairs)
    # RISTRETTO identity, not exact Edwards identity: each valid
    # signature's equation holds only up to 4-torsion on the coset
    # representatives ristretto decode returns, so the z-weighted sum
    # of a fully-valid batch lands anywhere in the identity coset
    # {(0,1),(0,-1),(+-i,0)} — affine x*y == 0. Checking the exact
    # identity (the round-4 behavior) rejected ~50% of valid batches
    # and silently fell back to the per-signature scan; a forgery
    # hits the 4-element coset with probability ~2^-250, so the
    # tolerant check loses no soundness (schnorrkel's VerifyBatch
    # compares ristretto points, i.e. exactly this).
    return (sx * sy) % R.P == 0 and sz % R.P != 0
