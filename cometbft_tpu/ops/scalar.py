"""Batched arithmetic mod L (the ed25519 group order) on device.

L = 2^252 + 27742317777372353535851937790883648493. Two jobs, both
vectorized over the signature batch with no host round-trips (the
challenge scalar arrives already reduced: the host hashes it):

- `recode_signed`: scalar -> 64 signed radix-16 digits in [-8, 7] for the
  windowed ladder, via the add-0x888...8 trick (adding 8 to every nibble
  with full carry propagation turns unsigned nibbles into signed digits).
- `lt_l`: the ZIP-215 "reject S >= L" range check as a borrow chain.

Behavior parity: the reference's scalar handling lives inside
curve25519-voi (reference: crypto/ed25519/ed25519.go:13 imports); the
limb formulation here is an original TPU design sharing the 12-bit limb
machinery of ops/field.py.

Carry discipline: digits and the borrow chain need *exact* limb values,
so a sum is finished by one sequential ripple pass. Sequential passes are
O(nlimbs) scalar steps over (B,) vectors — cheap relative to the curve
ladder.
"""

from __future__ import annotations

import numpy as np
import jax.numpy as jnp

from . import field as F

BITS = F.BITS
MASK = F.MASK

L_INT = 2**252 + 27742317777372353535851937790883648493
HALF_INT = int("8" * 64, 16)  # 0x888...8: adds 8 to each of 64 nibbles

_K = 22  # L occupies 22 base-2^12 limbs (bit 252 lives in limb 21)


def _to_limbs(x: int, n: int) -> np.ndarray:
    return np.array([(x >> (BITS * i)) & MASK for i in range(n)], np.int32)


L_LIMBS = jnp.asarray(_to_limbs(L_INT, _K)[:, None])
_HALF_LIMBS = jnp.asarray(_to_limbs(HALF_INT, _K)[:, None])


def bytes_to_limbs(b, nlimbs: int):
    """(B, nbytes) uint8 little-endian -> (nlimbs, B) int32 12-bit limbs."""
    b = b.astype(jnp.int32)
    pad = jnp.zeros(b.shape[:-1] + (1,), jnp.int32)
    padded = jnp.concatenate([b, pad], axis=-1)
    nbytes = b.shape[-1]
    limbs = []
    for j in range(nlimbs):
        bit = BITS * j
        sb = bit // 8
        if sb >= nbytes:
            limbs.append(jnp.zeros(b.shape[:-1], jnp.int32))
            continue
        shift = bit % 8
        v = padded[..., sb] >> shift
        if sb + 1 <= nbytes:
            v = v | (padded[..., min(sb + 1, nbytes)] << (8 - shift))
        limbs.append(v & MASK)
    return jnp.stack(limbs)


def _canon(x):
    """Exact canonicalization: limbs in [0, 2^12), value preserved, by
    one unrolled sequential ripple. Input limbs must be >= 0. The final
    carry out of the top limb is returned as (1, B) (callers for which it
    must be zero assert statically via value bounds). Rows stay 2D
    (kernel-safe: no stack/scatter).
    """
    out = []
    c = jnp.zeros_like(x[0:1])
    for j in range(x.shape[0]):
        t = x[j : j + 1] + c
        out.append(t & MASK)
        c = t >> BITS
    return jnp.concatenate(out, axis=0), c


def _sub_borrow(a, b):
    """a - b limbwise with sequential borrow. Returns (diff, borrow (1,B)).

    a, b canonical limbs of equal length; diff is the base-2^12 two's
    complement result (i.e. a - b mod b^n), borrow_out is 1 where a < b.
    """
    out = []
    c = jnp.zeros_like(a[0:1])
    for j in range(a.shape[0]):
        t = a[j : j + 1] - b[j : j + 1] - c
        out.append(t & MASK)
        c = (t >> BITS) & 1  # arithmetic shift of negative -> -1; mask to 1
    return jnp.concatenate(out, axis=0), c


def lt_l(s_bytes):
    """(B, 32) uint8 little-endian -> bool (B,): value < L (ZIP-215 S check)."""
    s = bytes_to_limbs(s_bytes, _K)
    _, borrow = _sub_borrow(s, jnp.broadcast_to(L_LIMBS, s.shape))
    return (borrow == 1)[0]


def recode_signed(limbs):
    """Canonical (22, B) scalar < 2^255 -> (64, B) int32 digits in [-8, 7].

    value = sum_i digit_i * 16^i. Implemented by adding 0x888...8 (with a
    full carry ripple) and subtracting 8 from every resulting nibble.
    """
    t = limbs + _HALF_LIMBS
    t, _ = _canon(t)  # sums <= 2*4095: one ripple suffices
    digits = []
    for i in range(64):
        limb, pos = divmod(4 * i, BITS)
        nib = (t[limb : limb + 1] >> pos) & 15
        digits.append(nib - 8)
    return jnp.concatenate(digits, axis=0)


def digits_from_bytes(b32):
    """(B, 32) uint8 scalar encoding -> (64, B) signed digits.

    Values >= 2^256 - HALF would overflow nibble 64; callers reject such
    lanes independently (lt_l), so garbage digits there are harmless.
    """
    return recode_signed(F.from_bytes_le(b32))
