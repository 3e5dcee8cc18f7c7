"""GF(2^255 - 19) arithmetic in JAX, vectorized over a trailing batch axis.

Representation: little-endian base-2^12 limbs in int32, shape (22, B).
p = 2^255 - 19; 22 * 12 = 264 bits, so 2^264 ≡ 512 * 19 = 9728 (mod p),
the carry-fold constant FOLD.

Loose invariant (what every op returns and accepts):
    limb 0   in [0, 13824)   (absorbs carry folds; < 2^13.76)
    limbs 1+ in [0, 4300)    (~canonical 2^12 plus ripple slack)
Schoolbook products then sum to at most
    2 * 13823 * 4299 + 20 * 4299^2 < 2^28.9  « int32,
so multiplication never overflows.

Carries are *parallel rounds*, not sequential chains: one round masks every
limb and shifts all carries up one position simultaneously (top carry folds
into limb 0 via FOLD). 2-3 rounds restore the loose invariant for every op's
intermediate bounds (documented per-op below). This keeps traced graphs ~10x
smaller than a sequential 22-step carry chain and maps to pure VPU ops.

Why 12-bit limbs: the TPU VPU has int32 multiply but no 64-bit accumulate,
so limb products plus their 22-term accumulation must fit in int32.

Design (not a port): the reference delegates field arithmetic to
curve25519-voi's amd64 assembly (reference: go.mod:55,
crypto/ed25519/ed25519.go:13); this is a re-derivation for int32 SIMD lanes.
"""

from __future__ import annotations

import numpy as np
import jax.numpy as jnp
from jax import lax

NLIMBS = 22
BITS = 12
MASK = (1 << BITS) - 1
FOLD = 9728  # 2^264 mod p
P_INT = 2**255 - 19

P_LIMBS = np.array(
    [(P_INT >> (BITS * i)) & MASK for i in range(NLIMBS)], dtype=np.int32
)
assert sum(int(l) << (BITS * i) for i, l in enumerate(P_LIMBS)) == P_INT


def from_int(x: int, batch: int | None = None) -> np.ndarray:
    """Host-side: python int -> limb array (NLIMBS,) or broadcast (NLIMBS, B)."""
    x %= P_INT
    limbs = np.array([(x >> (BITS * i)) & MASK for i in range(NLIMBS)], dtype=np.int32)
    if batch is None:
        return limbs
    return np.broadcast_to(limbs[:, None], (NLIMBS, batch)).copy()


def to_int(limbs) -> int:
    """Host-side: limb vector (NLIMBS,) -> python int (no reduction)."""
    arr = np.asarray(limbs, dtype=np.int64)
    return sum(int(v) << (BITS * i) for i, v in enumerate(arr))


def const(x: int):
    """Constant field element shaped (NLIMBS, 1) for broadcasting."""
    return jnp.asarray(from_int(x)[:, None])


def _round(x, fold: bool):
    """One parallel carry round: mask all limbs, shift carries up one slot.

    Signed arithmetic shifts give floor semantics, so this is correct for
    negative limbs (value is preserved mod p). With fold=True the top
    carry re-enters limb 0 scaled by FOLD; with fold=False the top carry
    must be provably zero (only used on the wide product array).
    """
    m = x & MASK
    hi = x >> BITS
    up = jnp.concatenate([jnp.zeros_like(hi[:1]), hi[:-1]], axis=0)
    if fold:
        top = jnp.concatenate(
            [FOLD * hi[-1:], jnp.zeros_like(hi[1:])], axis=0
        )
        return m + up + top
    return m + up


def carry(x):
    """Restore the loose invariant for |limbs| < 2^29 (3 folded rounds).

    Overflow margin: round 1's fold adds FOLD * (|x|max >> 12) < 2^30 to
    limb 0 — int32-safe up to |x| < 2^29.3. Convergence: round 1 leaves
    carries <= 2^17; round 2 collapses all but limbs 0-2 to < 4100 and
    limb 0/1 to < 2^15.1; round 3 lands the loose invariant (limb 0 <=
    4095 + FOLD = 13823, limbs 1.. < 4200). Worst-case chains were checked
    for the actual producers: add (2^14.8), sub (2^23.1), mul (2^28.7),
    mul_small (2^26.8).
    """
    x = _round(x, True)
    x = _round(x, True)
    return _round(x, True)


def add(a, b):
    """Loose + loose: limbs <= 27646; 2 rounds suffice (carries <= 6)."""
    return _round(_round(a + b, True), True)


# 2048*p limbwise: (a - b + SUB_BIAS) is positive limbwise (min limb
# 2048*7 = 14336 > 13823 = max loose limb) AND value-wise (max loose value
# < 2^265.01 < 2048*p ~= 2^266), so sub/neg never go value-negative and
# limb magnitudes stay < 2048*4095 + 13824 < 2^23.1, inside carry()'s domain.
_SUB_BIAS = jnp.asarray((2048 * P_LIMBS.astype(np.int64)).astype(np.int32)[:, None])


def _bias():
    return _KERNEL_BIAS if _KERNEL_BIAS is not None else _SUB_BIAS


def _p_const():
    """P_LIMBS as a (22, 1) value; inside kernels it is derived from the
    bias operand (= 2048 * P_LIMBS) since constants cannot be captured."""
    if _KERNEL_BIAS is not None:
        return _KERNEL_BIAS >> 11
    return jnp.asarray(P_LIMBS[:, None])


def sub(a, b):
    return carry(a - b + _bias())


def neg(a):
    return carry(_bias() - a)


_WIDE = 2 * NLIMBS + 1  # 45 rows; row 44 stays zero (max degree 42)


def _fold_wide(t):
    """(45, B) wide product -> loose (22, B), in 4 carry-shift rounds.

    Bound walk (conv rows < 2^29; rows 43-44 start at 0 since the max
    product degree is 42):
    - round 1 (unfolded): rows <= 4095 + 2^17 < 2^17.05; row 44 stays 0.
    - collapse: lo = t[:22] + FOLD*t[22:44] <= 2^17.05*(1+FOLD) < 1.32e9,
      int32-safe.  (b^22 = 2^264 ≡ FOLD mod p.)
    - round 2 over 23 rows (extra row catches the top carry):
      rows <= 4095 + (1.32e9 >> 12) < 2^18.3.
    - split-fold the top row T <= 2^18.3: T*b^22 ≡ FOLD*(T & MASK) at
      limb 0 (<= 2^25.3) + FOLD*(T >> 12) at limb 1 (<= 2^19.5) — the
      split keeps both contributions int32 where FOLD*T would overflow.
    - rounds 3-4 (folded) land the loose invariant: worst case is limb 1
      <= 4095 + (limb0 <= 4095+2^25.3 >> 12) < 4300.
    """
    batch = t.shape[1]
    t = _round(t, False)
    lo = t[:NLIMBS] + FOLD * t[NLIMBS : 2 * NLIMBS]
    lo = jnp.concatenate([lo, jnp.zeros((1, batch), jnp.int32)], axis=0)
    lo = _round(lo, False)
    top = lo[NLIMBS : NLIMBS + 1]
    x = jnp.concatenate(
        [
            lo[0:1] + FOLD * (top & MASK),
            lo[1:2] + FOLD * (top >> BITS),
            lo[2:NLIMBS],
        ],
        axis=0,
    )
    x = _round(x, True)
    return _round(x, True)


_PALLAS_TILE = 512


def _conv_rows_shifted(a, b):
    """(22, Bt) x (22, Bt) -> (45, Bt) wide product, shifted-row form.

    22 full-width multiply-accumulates (each (22, Bt)-shaped, full VPU
    sublane utilization) instead of 484 scalar-row ops — the layout the
    TPU vector unit wants, and a 20x smaller traced graph. Pure value
    form; runs identically under XLA and inside Pallas kernel bodies
    (measured faster in-kernel than ref-slice accumulation, whose
    unaligned sublane read-modify-writes Mosaic lowers poorly).
    """
    batch = a.shape[1]
    t = None
    for i in range(NLIMBS):
        rows = a[i][None, :] * b
        segs = []
        if i:
            segs.append(jnp.zeros((i, batch), jnp.int32))
        segs.append(rows)
        tail = _WIDE - NLIMBS - i
        if tail:
            segs.append(jnp.zeros((tail, batch), jnp.int32))
        shifted = jnp.concatenate(segs, axis=0) if len(segs) > 1 else segs[0]
        t = shifted if t is None else t + shifted
    return t


# --- kernel context: lets the shared curve/scalar code run INSIDE a fused
# Pallas kernel. When set (trace time only), mul/sq know not to nest a
# pallas_call (which is illegal), and sub/neg use a bias value passed in
# as a kernel input (pallas_call rejects captured array constants, so
# _SUB_BIAS cannot be closed over).
_IN_KERNEL = False
_KERNEL_BIAS = None


class kernel_mode:
    """Context manager marking that field ops are being traced inside a
    Pallas kernel body, with `sub_bias` the in-kernel value of _SUB_BIAS
    (sliced from a (22, 1) operand ref)."""

    def __init__(self, sub_bias=None):
        self.sub_bias = sub_bias

    def __enter__(self):
        global _IN_KERNEL, _KERNEL_BIAS
        self._prev = (_IN_KERNEL, _KERNEL_BIAS)
        _IN_KERNEL = True
        _KERNEL_BIAS = self.sub_bias
        return self

    def __exit__(self, *exc):
        global _IN_KERNEL, _KERNEL_BIAS
        _IN_KERNEL, _KERNEL_BIAS = self._prev
        return False


def _mul_kernel(a_ref, b_ref, o_ref):
    o_ref[...] = _fold_wide(_conv_rows_shifted(a_ref[...], b_ref[...]))


def _sq_kernel(a_ref, o_ref):
    a = a_ref[...]
    o_ref[...] = _fold_wide(_conv_rows_shifted(a, a))


def _on_tpu() -> bool:
    import jax

    return jax.default_backend() == "tpu"


def kernel_width(b: int) -> bool:
    """Does a batch axis of b lanes take the Pallas kernels? One block
    when b < _PALLAS_TILE (any width >= 128), whole tiles otherwise.
    Every BUCKETS entry that reaches the device, and its per-shard width
    on a 4-device mesh, satisfies this (tests/test_tpu_device.py); a
    width that does not takes the XLA value-form of the same math."""
    return b >= 128 and (b % _PALLAS_TILE == 0 or b < _PALLAS_TILE)


def _use_pallas(*arrs) -> bool:
    return _on_tpu() and kernel_width(arrs[0].shape[-1])


def _pallas_binop(kernel, name, *arrs):
    import jax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b = arrs[0].shape[-1]
    tile = min(b, _PALLAS_TILE)
    spec = pl.BlockSpec((NLIMBS, tile), lambda i: (0, i), memory_space=pltpu.VMEM)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((NLIMBS, b), jnp.int32),
        grid=(b // tile,),
        in_specs=[spec] * len(arrs),
        out_specs=spec,
        name=name,
    )(*arrs)


def _bcast(a, b):
    if a.shape[-1] != b.shape[-1]:
        wide = max(a.shape[-1], b.shape[-1])
        a = jnp.broadcast_to(a, (NLIMBS, wide))
        b = jnp.broadcast_to(b, (NLIMBS, wide))
    return a, b


def mul(a, b):
    """Schoolbook 22x22 limb multiply. Loose inputs -> loose output.

    Inside a fused kernel (kernel_mode) and on the CPU mesh this is a
    pure jnp DAG; standalone on TPU it becomes one Pallas kernel (round
    1's einsum formulation was HBM-bound AND blew up XLA compile time).

    Product limbs t[k] = sum_{i+j=k} a[i]b[j] < 2^29 (loose bound above).
    """
    a, b = _bcast(jnp.asarray(a), jnp.asarray(b))
    if _IN_KERNEL:
        return _fold_wide(_conv_rows_shifted(a, b))
    if _use_pallas(a, b):
        return _pallas_binop(_mul_kernel, "field_mul", a, b)
    return _fold_wide(_conv_rows_shifted(a, b))


def sq(a):
    """Squaring: one-input variant of mul (halves HBM reads on TPU)."""
    a = jnp.asarray(a)
    if _IN_KERNEL:
        return _fold_wide(_conv_rows_shifted(a, a))
    if _use_pallas(a):
        return _pallas_binop(_sq_kernel, "field_sq", a)
    return _fold_wide(_conv_rows_shifted(a, a))


def mul_small(a, c: int):
    """Multiply by a small constant 0 <= c < 2^13. |a*c| < 2^26.8 -> carry-able.

    Round 1 fold stays in int32: FOLD * (2^26.8 >> 12) < 2^28.1.
    """
    assert 0 <= c < (1 << 13)
    return carry(a * c)


def _seq_pass(x):
    """Sequential carry pass without fold; returns (limbs, carry_out (1,B)).

    Kernel-safe formulation: rows stay 2D and the result is a concat (no
    stack/scatter, which Mosaic cannot lower).
    """
    out = []
    c = jnp.zeros_like(x[0:1])
    for j in range(NLIMBS):
        t = x[j : j + 1] + c
        out.append(t & MASK)
        c = t >> BITS
    return jnp.concatenate(out, axis=0), c


def _edit_row0(a, delta):
    """a with delta (1,B) added to limb 0 (value-level, kernel-safe)."""
    return jnp.concatenate([a[0:1] + delta, a[1:]], axis=0)


def freeze(a):
    """Canonical representative: limbs < 2^12, value in [0, p).

    Rare op (a handful per signature vs thousands of muls), so the exact
    sequential passes here are fine.
    """
    a = carry(a)
    a, c = _seq_pass(a)
    a = _edit_row0(a, FOLD * c)
    a, c = _seq_pass(a)
    a = _edit_row0(a, FOLD * c)
    a, _ = _seq_pass(a)
    # Fold bits >= 255 out of the top limb (bits 252..263 live there).
    top = a[NLIMBS - 1 : NLIMBS] >> 3
    a = jnp.concatenate([a[: NLIMBS - 1], a[NLIMBS - 1 : NLIMBS] & 7], axis=0)
    a = _edit_row0(a, 19 * top)
    a, _ = _seq_pass(a)  # value now < 2^255 + eps < 2p
    # Conditional subtract p.
    d = a - _p_const()
    d, c = _seq_pass(d)
    nonneg = c == 0  # borrow-free => a >= p
    return jnp.where(nonneg, d, a)


def eq(a, b):
    """Field equality (canonical compare). Returns bool (B,)."""
    return jnp.all(freeze(a) == freeze(b), axis=0)


def is_zero(a):
    return jnp.all(freeze(a) == 0, axis=0)


def parity(a):
    """Least significant bit of the canonical representative. (B,) int32."""
    return freeze(a)[0] & 1


def select(cond, a, b):
    """cond: bool (B,); a, b: (NLIMBS, B)."""
    return jnp.where(cond[None, :], a, b)


def sqn(x, n: int):
    """n repeated squarings via a loop primitive (small traced graph)."""
    if n <= 2:
        for _ in range(n):
            x = sq(x)
        return x
    if _IN_KERNEL:
        return lax.fori_loop(0, n, lambda i, v: sq(v), x)
    return lax.scan(lambda c, _: (sq(c), None), x, None, length=n)[0]


def pow2523(x):
    """x^((p-5)/8) = x^(2^252 - 3), the exponent for combined sqrt/inverse.

    Standard square-and-multiply addition chain (11 muls + 252 squarings),
    re-derived from the exponent's binary structure.
    """
    x2 = sq(x)  # x^2
    x9 = mul(sq(sq(x2)), x)  # x^9
    x11 = mul(x9, x2)  # x^11
    x31 = mul(sq(x11), x9)  # x^(2^5 - 1)
    x_10 = mul(sqn(x31, 5), x31)  # 2^10 - 1
    x_20 = mul(sqn(x_10, 10), x_10)  # 2^20 - 1
    x_40 = mul(sqn(x_20, 20), x_20)  # 2^40 - 1
    x_50 = mul(sqn(x_40, 10), x_10)  # 2^50 - 1
    x_100 = mul(sqn(x_50, 50), x_50)  # 2^100 - 1
    x_200 = mul(sqn(x_100, 100), x_100)  # 2^200 - 1
    x_250 = mul(sqn(x_200, 50), x_50)  # 2^250 - 1
    return mul(sq(sq(x_250)), x)  # x^(2^252 - 3)


def invert(x):
    """x^(p-2): p-2 = 8*(2^252 - 3) + 3."""
    t = pow2523(x)
    for _ in range(3):
        t = sq(t)
    return mul(t, mul(sq(x), x))


def from_bytes_le(b):
    """(B, 32) uint8 little-endian -> (22, B) loose limbs (value < 2^256).

    Callers that need only 255 bits (point decoding) mask the sign bit first.
    """
    b = b.astype(jnp.int32)
    padded = jnp.concatenate([b, jnp.zeros(b.shape[:-1] + (1,), jnp.int32)], axis=-1)
    limbs = []
    for j in range(NLIMBS):
        bit = BITS * j
        sb = bit // 8
        shift = bit % 8
        v = (padded[..., sb] >> shift) | (padded[..., sb + 1] << (8 - shift))
        limbs.append(v & MASK)
    return jnp.stack(limbs)  # (22, B)


def to_bytes_le(a):
    """(22, B) -> (B, 32) uint8 of the canonical representative."""
    a = freeze(a)  # limbs < 2^12, value < p < 2^255
    out = []
    for k in range(32):
        bit = 8 * k
        j = bit // BITS
        shift = bit % BITS
        v = a[j] >> shift
        if shift > BITS - 8 and j + 1 < NLIMBS:
            v = v | (a[j + 1] << (BITS - shift))
        out.append(v & 0xFF)
    return jnp.stack(out, axis=-1).astype(jnp.uint8)
