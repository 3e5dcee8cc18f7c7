"""KZG polynomial commitments over BLS12-381 — the multiproof-DAS core.

The polynomial-commitment DA track (ROADMAP #1) replaces the 1D track's
growing Merkle path with a CONSTANT 48-byte opening: a column of the 2D
erasure matrix is a polynomial p of degree < k_r, its commitment is
C = [p(tau)]G1 under a structured reference string of powers
[tau^i]G1, and an opening at row z ships only y = p(z) plus the witness
pi = [q(tau)]G1 for the quotient q = (p - y)/(X - z). The verifier
checks ONE pairing equation

    e(C - [y]G1, G2) == e(pi, [tau - z]G2)

*Batched multiproofs* (the design anchor from "Polynomial Multiproofs
for Scalable Data Availability Sampling") aggregate s same-row column
openings behind a Fiat-Shamir scalar gamma: prover and verifier fold
polynomials / values / commitments as sum gamma^t (.)_t, and the single
48-byte proof answers all s samples — the per-sample wire cost decays
as 32 + 48/s bytes instead of the 1D track's chunk + Merkle path.

Trusted setup: TEST-ONLY and deterministic. tau is derived from a
public seed, so anyone can recompute it — this pins cross-process
vectors (native differential tests, asan selftest, the dasload fleet)
but provides NO soundness against a prover who uses tau. A production
deployment would substitute a ceremony SRS; every consumer below takes
the SRS as a value, so only `setup()` would change.

Every group operation routes through one seam: `msm()` dispatches the
multi-scalar multiplication to the native worker-pool Pippenger engine
(csrc/g1_msm.inc via crypto/native.py) and falls back to
`g1_msm_oracle`, the bit-exact pure-Python mirror of the native ABI
that tests/test_kzg_native.py pins the engine against on accept AND
reject paths.
"""

from __future__ import annotations

import hashlib
import struct
import threading

from ..utils.metrics import crypto_metrics
from . import native as _native
from .bls import (
    G1X,
    G1Y,
    P,
    G2X,
    G2Y,
    R_ORDER,
    _F2ONE,
    _g1_add,
    _g1_affine,
    _g1_mul,
    _g2_add,
    _g2_affine,
    _g2_mul,
    _pairing_product_is_one,
    g1_compress,
    g1_decompress,
    g1_subgroup_check,
    g2_compress,
)

R = R_ORDER  # the Fr scalar-field modulus
SCALAR_SIZE = 32  # big-endian Fr wire encoding
POINT_SIZE = 48  # zcash-compressed G1
PROOF_SIZE = 48  # one opening witness, any number of samples

G1_INF = g1_compress(None)
_G1_GEN = (G1X, G1Y)
_G2_GEN = (G2X, G2Y)
_G2_GEN_BYTES = g2_compress(_G2_GEN)

_DST_MULTI = b"cometbft-tpu/kzg/multiproof/v1"
_DST_PARITY = b"cometbft-tpu/kzg/parity/v1"


# --- Fr / polynomial helpers ----------------------------------------------
# Polynomials are lists of Fr ints, LOW-degree-first.


def fr(x: int) -> int:
    return x % R


def fr_inv(x: int) -> int:
    return pow(x, R - 2, R)


def poly_eval(coeffs, x: int) -> int:
    acc = 0
    for c in reversed(coeffs):
        acc = (acc * x + c) % R
    return acc


def poly_quotient(coeffs, z: int) -> list[int]:
    """q = (p - p(z)) / (X - z) by synthetic division (one pass,
    degree drops by one). The remainder p(z) is discarded — openings
    evaluate separately so the quotient stays a pure witness."""
    n = len(coeffs)
    if n <= 1:
        return []
    q = [0] * (n - 1)
    acc = coeffs[n - 1] % R
    for i in range(n - 2, -1, -1):
        q[i] = acc
        acc = (coeffs[i] + z * acc) % R
    return q


def _poly_mul_linear(coeffs, x: int) -> list[int]:
    """coeffs * (X - x)."""
    out = [0] * (len(coeffs) + 1)
    for i, c in enumerate(coeffs):
        out[i] = (out[i] - c * x) % R
        out[i + 1] = (out[i + 1] + c) % R
    return out


def interpolate(xs, ys) -> list[int]:
    """Coefficients of the unique degree < len(xs) polynomial through
    (xs[i], ys[i]) — Lagrange via the master product, O(k^2)."""
    k = len(xs)
    if k == 0:
        return []
    master = [1]
    for x in xs:
        master = _poly_mul_linear(master, x)
    coeffs = [0] * k
    for i in range(k):
        xi, yi = xs[i] % R, ys[i] % R
        num = poly_quotient(master, xi)  # master / (X - xi), exact
        den = 1
        for j in range(k):
            if j != i:
                den = den * (xi - xs[j]) % R
        scale = yi * fr_inv(den) % R
        for d in range(k):
            coeffs[d] = (coeffs[d] + scale * num[d]) % R
    return coeffs


def lagrange_coeffs_at(xs, x: int) -> list[int]:
    """Weights lambda_i with f(x) = sum lambda_i f(xs[i]) for any f of
    degree < len(xs). These are PUBLIC functions of the evaluation
    grid — the 2D parity-consistency check rides on the fact that they
    apply to commitments exactly as they apply to values."""
    k = len(xs)
    out = []
    for i in range(k):
        num = den = 1
        xi = xs[i] % R
        for j in range(k):
            if j != i:
                num = num * (x - xs[j]) % R
                den = den * (xi - xs[j]) % R
        out.append(num * fr_inv(den) % R)
    return out


# --- deterministic test-only trusted setup --------------------------------

_SETUP_SEED = b"cometbft-tpu insecure kzg test srs v1"


class SRS:
    """Powers-of-tau reference string: [tau^i]G1 for i < degree, plus
    [tau]G2 for the verifier side. `g1_bytes` carries the compressed
    encodings the native MSM consumes directly."""

    __slots__ = ("tau", "degree", "g1", "g1_bytes", "g2_tau",
                 "g2_tau_bytes")

    def __init__(self, tau: int, degree: int):
        self.tau = tau % R
        self.degree = degree
        self.g1 = []
        self.g1_bytes = []
        acc = (G1X, G1Y, 1)
        for _ in range(degree):
            aff = _g1_affine(acc)
            self.g1.append(aff)
            self.g1_bytes.append(g1_compress(aff))
            acc = _g1_mul(self.tau, acc)
        g2t = _g2_affine(_g2_mul(self.tau, (G2X, G2Y, _F2ONE)))
        self.g2_tau = g2t
        self.g2_tau_bytes = g2_compress(g2t)

    def grown(self, degree: int) -> "SRS":
        return self if degree <= self.degree else SRS(self.tau, degree)


_srs_lock = threading.Lock()
_SRS_CACHE: SRS | None = None


def setup(degree: int = 0) -> SRS:
    """The process-wide deterministic test SRS, grown on demand to at
    least `degree` G1 powers (tau = H(seed) mod r — public, hence
    test-only; see module docstring)."""
    global _SRS_CACHE
    with _srs_lock:
        if _SRS_CACHE is None or _SRS_CACHE.degree < degree:
            tau = int.from_bytes(
                hashlib.sha256(_SETUP_SEED).digest(), "big") % R
            base = _SRS_CACHE
            want = max(degree, 16)
            _SRS_CACHE = (base.grown(want) if base is not None
                          else SRS(tau, want))
        return _SRS_CACHE


# --- MSM: the one group-arithmetic seam -----------------------------------


def g1_msm_oracle(scalars_blob: bytes, points_blob: bytes, n: int,
                  skip: bytes | None = None) -> bytes | None:
    """Pure-Python mirror of the native `g1_msm` ABI — the differential
    oracle. Semantics (pinned bit-for-bit by tests/test_kzg_native.py):

    - n == 0 or everything skipped: the compressed identity, accepted.
    - skip[i] truthy: entry i is ignored entirely (never decoded).
    - scalars are 32-byte big-endian and must be < r (0 allowed);
      points are 48-byte zcash-compressed, must decode canonically and
      pass the subgroup check (the identity is allowed and contributes
      nothing). Any violation on a NON-skipped entry rejects the whole
      call (None) — even when its scalar is zero.
    """
    if n == 0:
        return G1_INF
    acc = None
    for i in range(n):
        if skip is not None and skip[i]:
            continue
        s = int.from_bytes(scalars_blob[i * 32:(i + 1) * 32], "big")
        if s >= R:
            return None
        pt = g1_decompress(points_blob[i * 48:(i + 1) * 48])
        if pt is None:
            return None
        if pt == "inf":
            continue
        if not g1_subgroup_check(pt):
            return None
        if s == 0:
            continue
        acc = _g1_add(acc, _g1_mul(s, (pt[0], pt[1], 1)))
    return g1_compress(_g1_affine(acc))


def msm(scalars, points_bytes, *, nchunks: int = 0,
        force_oracle: bool = False) -> bytes:
    """sum [s_i]P_i as compressed bytes — native Pippenger engine when
    the .so exports it, oracle otherwise (`force_oracle` pins the
    Python path for the throughput comparison). Raises ValueError on
    invalid inputs; internal callers pass SRS/commitment points."""
    n = len(scalars)
    sb = b"".join((s % R).to_bytes(32, "big") for s in scalars)
    pb = b"".join(points_bytes)
    cm = crypto_metrics()
    out = None
    if not force_oracle:
        out = _native.g1_msm(sb, pb, n, nchunks=nchunks)
    if out is None:
        out = g1_msm_oracle(sb, pb, n)
        cm.msm_oracle_total.inc()
    else:
        cm.msm_native_total.inc()
        if out is False:
            out = None
    if out is None:
        raise ValueError("invalid MSM input (bad point or scalar)")
    return out


def _msm_or_none(scalars, points_bytes) -> bytes | None:
    """msm() for UNTRUSTED points: None instead of raising."""
    n = len(scalars)
    sb = b"".join((s % R).to_bytes(32, "big") for s in scalars)
    pb = b"".join(points_bytes)
    cm = crypto_metrics()
    out = _native.g1_msm(sb, pb, n)
    if out is not None:
        cm.msm_native_total.inc()
        return out if out is not False else None
    out = g1_msm_oracle(sb, pb, n)
    cm.msm_oracle_total.inc()
    return out


# --- commit / open / verify -----------------------------------------------


def commit(coeffs, srs: SRS | None = None, *, nchunks: int = 0,
           force_oracle: bool = False) -> bytes:
    """C = [p(tau)]G1: one MSM of the coefficients against the SRS
    powers. The SRS slice bounds the committable degree — a column
    commitment produced through this function can never exceed the
    row-count degree bound its sampler assumes."""
    if not coeffs:
        return G1_INF
    srs = (srs or setup(len(coeffs))).grown(len(coeffs))
    return msm(coeffs, srs.g1_bytes[:len(coeffs)], nchunks=nchunks,
               force_oracle=force_oracle)


def open_single(coeffs, z: int, srs: SRS | None = None,
                *, force_oracle: bool = False) -> tuple[int, bytes]:
    """(y, proof): evaluate and commit the quotient witness."""
    y = poly_eval(coeffs, z)
    q = poly_quotient(coeffs, z)
    return y, commit(q, srs, force_oracle=force_oracle)


def _jac(pt) -> tuple | None:
    return None if pt is None else (pt[0], pt[1], 1)


def _verify_pairing(a48: bytes, pi48: bytes, d2_aff, d2_96: bytes) -> bool:
    """e(A, G2) == e(pi, D2) with the infinity corners handled before
    any pairing runs. Native two-pairing GT comparison when available
    (each GT element pins the same Miller+final-exp bytes the oracle
    produces), oracle product-of-pairings otherwise."""
    a_inf = a48 == G1_INF
    pi_inf = pi48 == G1_INF
    d2_inf = d2_aff is None
    if d2_inf:
        # [tau - z]G2 vanishes only if z == tau — unreachable for a
        # sampler (tau is not a row index) but handled for closure:
        # RHS is 1, so the equation holds iff A is the identity.
        return a_inf
    if a_inf or pi_inf:
        return a_inf and pi_inf
    gt_a = _native.bls_pairing(a48, _G2_GEN_BYTES)
    if gt_a is not None:
        gt_pi = _native.bls_pairing(pi48, d2_96)
        if gt_a is False or gt_pi is False or gt_pi is None:
            return False
        return gt_a == gt_pi
    a_pt = g1_decompress(a48)
    pi_pt = g1_decompress(pi48)
    if a_pt in (None, "inf") or pi_pt in (None, "inf"):
        return False
    neg_pi = (pi_pt[0], (-pi_pt[1]) % P)
    return _pairing_product_is_one(
        [(a_pt, _G2_GEN), (neg_pi, d2_aff)])


def _d2_for(z: int, srs: SRS):
    """[tau - z]G2 affine + compressed, from the public SRS element."""
    acc = (srs.g2_tau[0], srs.g2_tau[1], _F2ONE)
    zr = z % R
    if zr:
        acc = _g2_add(acc, _g2_mul(R - zr, (G2X, G2Y, _F2ONE)))
    aff = _g2_affine(acc)
    return aff, (g2_compress(aff) if aff is not None else None)


def verify(commitment: bytes, z: int, y: int, proof: bytes,
           srs: SRS | None = None) -> bool:
    """One opening check: e(C - [y]G1, G2) == e(pi, [tau - z]G2).
    Rejects non-canonical / out-of-subgroup C or pi."""
    srs = srs or setup()
    c_pt = g1_decompress(commitment)
    pi_pt = g1_decompress(proof)
    if c_pt is None or pi_pt is None:
        return False
    for pt in (c_pt, pi_pt):
        if pt != "inf" and not g1_subgroup_check(pt):
            return False
    # A = C - [y]G1
    acc = _jac(None if c_pt == "inf" else c_pt)
    yr = y % R
    if yr:
        acc = _g1_add(acc, _g1_mul(R - yr, (G1X, G1Y, 1)))
    a48 = g1_compress(_g1_affine(acc))
    d2_aff, d2_96 = _d2_for(z, srs)
    return _verify_pairing(a48, proof, d2_aff, d2_96)


# --- batched multiproofs ---------------------------------------------------


def _fs_gamma(commitments, z: int, ys) -> int:
    """Fiat-Shamir folding scalar binding the opened commitments, the
    row point and every claimed value (prover and verifier must hash
    the same transcript or the fold disagrees and verification fails)."""
    h = hashlib.sha256()
    h.update(_DST_MULTI)
    h.update(struct.pack(">I", len(commitments)))
    for c in commitments:
        h.update(c)
    h.update((z % R).to_bytes(32, "big"))
    for y in ys:
        h.update((y % R).to_bytes(32, "big"))
    return int.from_bytes(h.digest(), "big") % R


def open_multi(col_coeffs, commitments, z: int,
               srs: SRS | None = None, *, nchunks: int = 0,
               force_oracle: bool = False) -> tuple[list[int], bytes]:
    """One proof for s same-point openings: fold the columns behind
    gamma, divide once, commit the single quotient. Returns
    (ys, proof48) — the whole response for an s-column sample."""
    ys = [poly_eval(c, z) for c in col_coeffs]
    gamma = _fs_gamma(commitments, z, ys)
    deg = max((len(c) for c in col_coeffs), default=0)
    folded = [0] * deg
    w = 1
    for c in col_coeffs:
        for d, cd in enumerate(c):
            folded[d] = (folded[d] + w * cd) % R
        w = w * gamma % R
    q = poly_quotient(folded, z)
    return ys, commit(q, srs, nchunks=nchunks, force_oracle=force_oracle)


def verify_multi(commitments, z: int, ys, proof: bytes,
                 srs: SRS | None = None) -> bool:
    """Check one batched proof against s commitments: fold commitments
    (one MSM — the native engine's verifier-side job) and values with
    the recomputed gamma, then run the single-opening equation."""
    if len(commitments) != len(ys) or not commitments:
        return False
    srs = srs or setup()
    gamma = _fs_gamma(commitments, z, ys)
    gammas = []
    w = 1
    for _ in commitments:
        gammas.append(w)
        w = w * gamma % R
    c_agg = _msm_or_none(gammas, commitments)
    if c_agg is None:
        return False
    y_agg = 0
    for g, y in zip(gammas, ys):
        y_agg = (y_agg + g * (y % R)) % R
    return verify(c_agg, z, y_agg, proof, srs)


# --- parity-linearity consistency (the lying-encoder check) ----------------


def parity_scalars(k_c: int, m_c: int, commitments) -> list[int]:
    """Scalars for the batched parity-consistency MSM. Column j' >=
    k_c of the 2D extension is DEFINED as the Lagrange combination
    sum_j lambda_j(j') col_j, and commitments are linear, so

        sum_j [sum_j' r^(j'-k_c) lambda_j(j')] C_j
            - sum_j' r^(j'-k_c) C_j'  ==  identity

    for the Fiat-Shamir r derived from the commitment list. A single
    inconsistent parity commitment breaks the identity except with
    negligible probability over r."""
    r = int.from_bytes(
        hashlib.sha256(_DST_PARITY + b"".join(commitments)).digest(),
        "big") % R
    xs = list(range(k_c))
    out = [0] * (k_c + m_c)
    w = 1
    for jp in range(k_c, k_c + m_c):
        lam = lagrange_coeffs_at(xs, jp)
        for j in range(k_c):
            out[j] = (out[j] + w * lam[j]) % R
        out[jp] = (R - w) % R
        w = w * r % R
    return out


def verify_parity_commitments(commitments, k_c: int) -> bool:
    """The sample-free lying-encoder check: every parity-column
    commitment must equal the public Lagrange combination of the data
    columns. One MSM over all n_c commitments, deterministic per
    commitment list — no fraud proofs, no second honest encoder. The
    1D Merkle track provably cannot express this check: hashes are not
    linear, so a root over garbage parity verifies every opening (the
    pinned blindness test in tests/test_kzg_native.py)."""
    n_c = len(commitments)
    m_c = n_c - k_c
    if m_c <= 0 or k_c <= 0:
        return False
    scalars = parity_scalars(k_c, m_c, commitments)
    return _msm_or_none(scalars, commitments) == G1_INF
