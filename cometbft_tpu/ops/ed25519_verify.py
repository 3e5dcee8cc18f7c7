"""Batched Ed25519 ZIP-215 verification — the TPU data-plane kernel.

Per-lane cofactored verification: each lane checks
    [8]([S]B + [k](-A) - R) == identity
with liberal (ZIP-215) decoding of A and R. This is the device half of the
reference's batch verifier (reference: crypto/ed25519/ed25519.go:207-240,
types/validation.go:214 verifyCommitBatch); unlike the CPU random-linear-
combination trick, per-lane verification is embarrassingly parallel on TPU
lanes AND yields the per-signature validity bitmap that the commit-verify
fallback scan needs (reference: types/validation.go:304-311) for free.

The challenge k = SHA-512(R||A||M) mod L is hashed on the host
(crypto/native.py pack_rsk); the device runs signed-digit recoding,
ZIP-215 decompression, the shared-doubling ladder and the S < L range
check. The host only packs fixed-shape byte arrays.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import curve as C
from . import field as F
from . import scalar as SC


def verify_batch_prehashed(a_bytes, r_bytes, s_bytes, k_bytes, live):
    """The tests' second implementation: one point, 64 windows, A
    decompressed in the call. No engine launches it since PR 44 (both run
    decompress_pubkeys once a column and verify_batch_cached_a a batch);
    tests/test_mesh.py and tests/test_curve.py hold those two to it.

    k_bytes: (B, 32) uint8 little-endian canonical k = SHA-512(R||A||M)
    mod L, hashed on the host. The curve-side check is
    [8]([S]B + [k](-A) - R) == identity with liberal decoding.
    """
    # the phases are utils/trace.KERNEL_SCOPES: names on the operations,
    # for a profiler trace; the program is what it is without them
    with jax.named_scope("ladder.scalar_reduce"):
        k_digits = SC.digits_from_bytes(k_bytes)
        s_digits = SC.digits_from_bytes(s_bytes)
        s_ok = SC.lt_l(s_bytes)
    with jax.named_scope("ladder.decompress"):
        ok_a, a_pt = C.decompress(a_bytes)
        ok_r, r_pt = C.decompress(r_bytes)
    with jax.named_scope("ladder.double_scalar"):
        X, Y, Z = C.ladder_sub_mul8(
            s_digits, k_digits, (C.neg(a_pt),), r_pt)
    with jax.named_scope("ladder.compare"):
        ok_eq = F.is_zero(X) & F.eq(Y, Z)
        bits = ok_a & ok_r & ok_eq & s_ok & live
        # scalar summary: every LIVE lane verified (padding lanes are
        # excluded). Fetching this single bool instead of the bitmap
        # keeps the happy-path device→host transfer at pure round-trip
        # latency; the bitmap is only pulled when the summary says some
        # lane failed (reference types/validation.go:304 falls back to a
        # per-sig scan only when the batch verify fails).
        return bits, jnp.all(bits | ~live)


verify_batch_prehashed_jit = jax.jit(verify_batch_prehashed)


def decompress_pubkeys(a_bytes):
    """(B, 32) uint8 pubkey encodings -> (ok, (-A, [2^128](-A))), the
    points negated and extended.

    The A half of the verification equation, split out so callers can
    keep a validator set's decompressed points resident on device: in
    commit replay the SAME pubkey column verifies every height, so the
    32 bytes/lane of A never need to re-cross the host->device link and
    the sqrt-decompression (one of the two per-lane exponentiations)
    runs once per validator-set change instead of once per commit. What
    is kept is the pair that halves the ladder (C.ladder_sub_mul8): 128
    doublings a lane here, once a column, for 128 fewer in every batch."""
    with jax.named_scope("ladder.decompress"):
        ok_a, a_pt = C.decompress(a_bytes)
        neg_a = C.neg(a_pt)
    with jax.named_scope("ladder.a_hi"):
        return ok_a, (neg_a, C.mul_2_128(neg_a))


decompress_pubkeys_jit = jax.jit(decompress_pubkeys)


def verify_batch_cached_a(ok_a, a_points, rsk, live):
    """verify_batch_prehashed with the pubkey stage precomputed by
    decompress_pubkeys (device-resident across submits); the ladder is
    as long as a_points says (32 windows for decompress_pubkeys' pair).

    rsk: (B, 96) uint8 — R || S || k packed in one array so the
    per-commit host->device traffic is a single contiguous transfer
    (the link's fixed per-transfer cost matters at this rate)."""
    r_bytes = rsk[:, :32]
    s_bytes = rsk[:, 32:64]
    k_bytes = rsk[:, 64:]
    with jax.named_scope("ladder.scalar_reduce"):
        k_digits = SC.digits_from_bytes(k_bytes)
        s_digits = SC.digits_from_bytes(s_bytes)
        s_ok = SC.lt_l(s_bytes)
    with jax.named_scope("ladder.decompress"):
        ok_r, r_pt = C.decompress(r_bytes)
    with jax.named_scope("ladder.double_scalar"):
        X, Y, Z = C.ladder_sub_mul8(s_digits, k_digits, a_points, r_pt)
    with jax.named_scope("ladder.compare"):
        ok_eq = F.is_zero(X) & F.eq(Y, Z)
        bits = ok_a & ok_r & ok_eq & s_ok & live
        return bits, jnp.all(bits | ~live)


verify_batch_cached_a_jit = jax.jit(verify_batch_cached_a)
