"""Batched edwards25519 point operations in JAX.

Points are tuples (X, Y, Z, T) of (22, B) int32 limb arrays — extended
homogeneous coordinates on the twisted Edwards curve -x^2 + y^2 = 1 + d x^2 y^2
with x = X/Z, y = Y/Z, T = XY/Z.

The addition law used (add-2008-hwcd-3) is *complete* for a = -1 (a square
mod p) and d non-square, so it is valid for every curve point including the
8-torsion components that ZIP-215 liberal decoding admits — no branch needed
for doubling or identity inputs inside the table build.

Round-2 ladder design (all original TPU work, no reference counterpart —
the reference delegates to curve25519-voi assembly via
crypto/ed25519/ed25519.go:13):
- signed radix-16 digits in [-8, 7] (ops/scalar.py) halve table sizes;
  negation of a cached point is two selects and one field negation.
- tables live in "niels" form (Y+X, Y-X, 2dT [, 2Z]) so a cached-point
  addition costs 8 muls (7 when Z=1, the constant base table).
- doublings skip the T output except when the next op is an addition
  (dbl_no_t: 7 muls vs 8).
"""

from __future__ import annotations

import numpy as np
import jax.numpy as jnp
from jax import lax

from ..crypto import ed25519_ref as ref
from . import field as F

P = F.P_INT
_D2_INT = (2 * ref.D) % P

# Broadcastable (22, 1) constants.
D_C = F.const(ref.D)
D2_C = F.const(_D2_INT)
SQRT_M1_C = F.const(ref.SQRT_M1)
ONE_C = F.const(1)

# The same constants as one stacked host array — Pallas kernels cannot
# close over array constants, so fused kernels take this as an operand:
# rows [0:22)=2d, [22:44)=d, [44:66)=sqrt(-1).
_CONSTS_NP = np.concatenate(
    [F.from_int(_D2_INT)[:, None], F.from_int(ref.D)[:, None],
     F.from_int(ref.SQRT_M1)[:, None]], axis=1
).T.reshape(3 * F.NLIMBS, 1)

# While tracing inside a fused kernel this holds {'d2': (22,1) value, ...}
# so the shared point-op code below picks up operand-backed constants.
_KCONSTS: dict | None = None


def _kc(name, default):
    return _KCONSTS[name] if _KCONSTS is not None else default


def _row0_const(val: int, rows: int, cols: int):
    """Field element val*1 (only limb 0 set) synthesized in-kernel via iota
    — constants that are small integers never need an operand."""
    r = lax.broadcasted_iota(jnp.int32, (rows, cols), 0)
    return jnp.where(r == 0, val, 0)


def identity(batch: int):
    z = jnp.zeros((F.NLIMBS, batch), jnp.int32)
    one = jnp.broadcast_to(jnp.asarray(F.from_int(1))[:, None], (F.NLIMBS, batch))
    return (z, one, one, z)


def add(p, q):
    """Complete unified addition (add-2008-hwcd-3, a=-1). 9 muls."""
    X1, Y1, Z1, T1 = p
    X2, Y2, Z2, T2 = q
    a = F.mul(F.sub(Y1, X1), F.sub(Y2, X2))
    b = F.mul(F.add(Y1, X1), F.add(Y2, X2))
    c = F.mul(F.mul(T1, _kc("d2", D2_C)), T2)
    d = F.mul(F.add(Z1, Z1), Z2)
    e = F.sub(b, a)
    f = F.sub(d, c)
    g = F.add(d, c)
    h = F.add(b, a)
    return (F.mul(e, f), F.mul(g, h), F.mul(f, g), F.mul(e, h))


def to_niels(p):
    """Extended -> cached niels form (Y+X, Y-X, 2dT, 2Z). 1 mul."""
    X, Y, Z, T = p
    return (F.add(Y, X), F.sub(Y, X), F.mul(T, _kc("d2", D2_C)), F.add(Z, Z))


def add_niels(p, n):
    """Extended + niels-cached point. 8 muls."""
    X1, Y1, Z1, T1 = p
    ypx2, ymx2, t2d2, z22 = n
    a = F.mul(F.sub(Y1, X1), ymx2)
    b = F.mul(F.add(Y1, X1), ypx2)
    c = F.mul(T1, t2d2)
    d = F.mul(Z1, z22)
    e = F.sub(b, a)
    f = F.sub(d, c)
    g = F.add(d, c)
    h = F.add(b, a)
    return (F.mul(e, f), F.mul(g, h), F.mul(f, g), F.mul(e, h))


def madd(p, an):
    """Extended + affine niels (Y+X, Y-X, 2dT with Z2=1). 7 muls."""
    X1, Y1, Z1, T1 = p
    ypx2, ymx2, t2d2 = an
    a = F.mul(F.sub(Y1, X1), ymx2)
    b = F.mul(F.add(Y1, X1), ypx2)
    c = F.mul(T1, t2d2)
    d = F.add(Z1, Z1)
    e = F.sub(b, a)
    f = F.sub(d, c)
    g = F.add(d, c)
    h = F.add(b, a)
    return (F.mul(e, f), F.mul(g, h), F.mul(f, g), F.mul(e, h))


def _dbl_efgh(p):
    X1, Y1, Z1, _ = p
    a = F.sq(X1)
    b = F.sq(Y1)
    zz = F.sq(Z1)
    c = F.add(zz, zz)
    e = F.sub(F.sub(F.sq(F.add(X1, Y1)), a), b)
    g = F.sub(b, a)
    f = F.sub(g, c)
    h = F.neg(F.add(a, b))
    return e, f, g, h


def dbl(p):
    """Dedicated doubling (dbl-2008-hwcd, a=-1). 4 sq + 4 mul."""
    e, f, g, h = _dbl_efgh(p)
    return (F.mul(e, f), F.mul(g, h), F.mul(f, g), F.mul(e, h))


def dbl_no_t(p):
    """Doubling that skips the T output (4 sq + 3 mul). The result is NOT
    valid as input to additions — only to further doublings / freezes."""
    e, f, g, h = _dbl_efgh(p)
    return (F.mul(e, f), F.mul(g, h), F.mul(f, g), None)


def neg(p):
    X, Y, Z, T = p
    return (F.neg(X), Y, Z, F.neg(T))


def is_identity(p):
    X, Y, Z, _ = p
    return F.is_zero(X) & F.eq(Y, Z)


def _abs_diff_zero(a, b):
    """(1, B) int32 mask: canonical(a) == canonical(b). Kernel-safe
    keepdims formulation (no reductions to 1-D shapes)."""
    d = jnp.abs(F.freeze(a) - F.freeze(b))
    return (jnp.sum(d, axis=0, keepdims=True) == 0).astype(jnp.int32)


def _decompress_kernel(y_ref, sign_ref, bias_ref, consts_ref,
                       valid_o, x_o, t_o):
    """Fused ZIP-215 decompression (sqrt candidate + checks): ~280 field
    muls in one launch. y arrives as limbs (byte unpacking is mul-free at
    the XLA level); outputs x, t = x*y and the validity mask."""
    nl = F.NLIMBS
    with F.kernel_mode(bias_ref[...]):
        y = y_ref[...]
        batch = y.shape[1]
        d_c = consts_ref[nl : 2 * nl, :]
        sqrtm1 = consts_ref[2 * nl : 3 * nl, :]
        one = _row0_const(1, nl, batch)
        yy = F.sq(y)
        u = F.sub(yy, one)
        v = F.add(F.mul(yy, d_c), one)
        v3 = F.mul(F.sq(v), v)
        v7 = F.mul(F.sq(v3), v)
        x = F.mul(F.mul(u, v3), F.pow2523(F.mul(u, v7)))
        vxx = F.mul(v, F.sq(x))
        ok_direct = _abs_diff_zero(vxx, u)
        ok_flip = _abs_diff_zero(vxx, F.neg(u))
        x = jnp.where(ok_flip != 0, F.mul(x, sqrtm1), x)
        valid = ok_direct | ok_flip
        par = F.freeze(x)[0:1] & 1
        x = jnp.where(par != sign_ref[...], F.neg(x), x)
        t = F.mul(x, y)
    valid_o[...] = valid
    x_o[...] = x
    t_o[...] = t


def _decompress_pallas(y, sign):
    import jax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    batch = y.shape[1]
    tile = min(batch, F._PALLAS_TILE)
    nl = F.NLIMBS
    point_spec = pl.BlockSpec((nl, tile), lambda i: (0, i), memory_space=pltpu.VMEM)
    row_spec = pl.BlockSpec((1, tile), lambda i: (0, i), memory_space=pltpu.VMEM)
    bias_spec = pl.BlockSpec((nl, 1), lambda i: (0, 0), memory_space=pltpu.VMEM)
    consts_spec = pl.BlockSpec(
        (3 * nl, 1), lambda i: (0, 0), memory_space=pltpu.VMEM
    )
    valid, x, t = pl.pallas_call(
        _decompress_kernel,
        out_shape=[
            jax.ShapeDtypeStruct((1, batch), jnp.int32),
            jax.ShapeDtypeStruct((nl, batch), jnp.int32),
            jax.ShapeDtypeStruct((nl, batch), jnp.int32),
        ],
        grid=(batch // tile,),
        in_specs=[point_spec, row_spec, bias_spec, consts_spec],
        out_specs=[row_spec, point_spec, point_spec],
        name="curve_decompress",
    )(y, sign[None, :], jnp.asarray(F._SUB_BIAS), jnp.asarray(_CONSTS_NP))
    return valid[0] != 0, x, t


def decompress(b):
    """ZIP-215 liberal point decoding.

    b: (B, 32) uint8 encodings. Returns (valid: bool (B,), point).
    Non-canonical y (>= p) is reduced mod p; x == 0 with sign bit 1 is
    accepted as x = 0. Invalid (non-square x^2 candidate) lanes return
    valid=False with an arbitrary well-formed point.
    """
    b = jnp.asarray(b)
    sign = (b[:, 31].astype(jnp.int32) >> 7) & 1  # (B,)
    masked = b.at[:, 31].set(b[:, 31] & 0x7F)
    y = F.from_bytes_le(masked)  # < 2^255, loose
    one = jnp.broadcast_to(jnp.asarray(F.from_int(1))[:, None], y.shape)
    if F._use_pallas(y):
        valid, x, t = _decompress_pallas(y, sign)
        return valid, (x, y, one, t)
    yy = F.sq(y)
    u = F.sub(yy, ONE_C)
    v = F.add(F.mul(yy, D_C), ONE_C)
    v3 = F.mul(F.sq(v), v)
    v7 = F.mul(F.sq(v3), v)
    x = F.mul(F.mul(u, v3), F.pow2523(F.mul(u, v7)))
    vxx = F.mul(v, F.sq(x))
    ok_direct = F.eq(vxx, u)
    ok_flip = F.eq(vxx, F.neg(u))
    x = F.select(ok_flip, F.mul(x, SQRT_M1_C), x)
    valid = ok_direct | ok_flip
    flip_sign = F.parity(x) != sign
    x = F.select(flip_sign, F.neg(x), x)
    return valid, (x, y, one, F.mul(x, y))


def compress(p):
    """(B, 32) uint8 canonical encodings (inverts Z; host/test use only)."""
    X, Y, Z, _ = p
    zi = F.invert(Z)
    x = F.freeze(F.mul(X, zi))
    y = F.mul(Y, zi)
    enc = F.to_bytes_le(y)
    return enc.at[:, 31].set(enc[:, 31] | ((x[0] & 1) << 7).astype(jnp.uint8))


# --- Constant base table: affine niels of [i]B for i in 0..8 ---
def _host_base_niels() -> np.ndarray:
    out = np.zeros((9, 3, F.NLIMBS), np.int32)
    out[0, 0] = F.from_int(1)  # identity: y+x=1, y-x=1, 2dxy=0
    out[0, 1] = F.from_int(1)
    for i in range(1, 9):
        x, y = ref._ext_to_affine(ref._ext_scalar_mul(i, ref.B_POINT))
        out[i, 0] = F.from_int((y + x) % P)
        out[i, 1] = F.from_int((y - x) % P)
        out[i, 2] = F.from_int((2 * ref.D * x * y) % P)
    return out


BASE_NIELS = jnp.asarray(_host_base_niels())  # (9, 3, 22)


def lane_table(p):
    """Per-lane niels table of [i]p for i in 0..8, one (9, 4, 22, B) array.

    Built as a 7-step scan of P_{k+1} = P_k + P (one traced add body; an
    unrolled dbl/add chain costs the same muls but 7x the graph)."""
    batch = p[0].shape[1]
    n1 = to_niels(p)

    def body(pk, _):
        nxt = add_niels(pk, n1)
        return nxt, jnp.stack(to_niels(nxt))

    _, rest = lax.scan(body, p, None, length=7)  # (7, 4, 22, B)
    ident = (
        jnp.broadcast_to(jnp.asarray(F.from_int(1))[:, None], (F.NLIMBS, batch)),
        jnp.broadcast_to(jnp.asarray(F.from_int(1))[:, None], (F.NLIMBS, batch)),
        jnp.zeros((F.NLIMBS, batch), jnp.int32),
        jnp.broadcast_to(jnp.asarray(F.from_int(2))[:, None], (F.NLIMBS, batch)),
    )
    head = jnp.stack([jnp.stack(ident), jnp.stack(n1)])  # (2, 4, 22, B)
    return jnp.concatenate([head, rest], axis=0)  # (9, 4, 22, B)


def _select_rows(rows, ncomps, idx_row, batch):
    """Select a table entry per lane by a (1, B) index in 0..8.

    rows(entry, comp) -> (22, ?) array; where-loop formulation
    (kernel-safe: no einsum/gather). Returns `ncomps` (22, B) arrays."""
    comps = []
    for c in range(ncomps):
        acc = None
        for e in range(9):
            row = jnp.broadcast_to(rows(e, c), (F.NLIMBS, batch))
            term = jnp.where(idx_row == e, row, 0)
            acc = term if acc is None else acc + term
        comps.append(acc)
    return comps


def _apply_sign_affine(sign_row, ypx, ymx, t2d):
    return (
        jnp.where(sign_row, ymx, ypx),
        jnp.where(sign_row, ypx, ymx),
        jnp.where(sign_row, F.neg(t2d), t2d),
    )


def _base_madd(r, ws_row, base_rows=None):
    """madd of [digit]B from the constant base table (signed select).

    base_rows: callable(entry, comp) -> (22, 1-or-B) row; defaults to the
    module-level table (XLA path). Kernels pass a VMEM-ref view instead —
    pallas_call rejects captured array constants.
    """
    if base_rows is None:
        base_rows = lambda e, c: BASE_NIELS[e, c][:, None]
    ypx, ymx, t2d = _select_rows(
        base_rows, 3, jnp.abs(ws_row), ws_row.shape[1]
    )
    return madd(r, _apply_sign_affine(ws_row < 0, ypx, ymx, t2d))


def _window_step(r, tbl_rows, ws_row, wk_row, base_rows=None):
    """One radix-16 window: 4 doublings + base madd + lane add.

    r: extended point of (22, B) arrays; tbl_rows: callable(entry, comp)
    -> (22, B) lane-table component; ws_row/wk_row: (1, B) signed digits.
    Pure value-form — runs identically inside the Pallas kernel and on
    the XLA (CPU) path.
    """
    r = dbl_no_t(r)
    r = dbl_no_t(r)
    r = dbl_no_t(r)
    r = dbl(r)
    r = _base_madd(r, ws_row, base_rows)
    # lane-table niels add (4th component z2 carries no sign)
    lypx, lymx, lt2d, lz2 = _select_rows(
        tbl_rows, 4, jnp.abs(wk_row), wk_row.shape[1]
    )
    ypx, ymx, t2d = _apply_sign_affine(wk_row < 0, lypx, lymx, lt2d)
    return add_niels(r, (ypx, ymx, t2d, lz2))


def _kernel_identity(batch: int):
    """Identity point synthesized in-kernel (no captured constants)."""
    z = jnp.zeros((F.NLIMBS, batch), jnp.int32)
    one = _row0_const(1, F.NLIMBS, batch)
    return (z, one, one, z)


def _ladder_sub_kernel(ax, ay, az, at, rx, ry, rz, rt, ws_ref, wk_ref,
                       base_ref, bias_ref, consts_ref, xo, yo, zo, tbl):
    """THE fused Pallas kernel: per tile it builds the 9-entry lane table
    of A in VMEM, runs all 64 shared-doubling windows (fori_loop — one
    traced window body), subtracts R and multiplies by the cofactor, all
    without leaving VMEM. One launch per ladder instead of ~350: on this
    runtime each pallas launch carries ~0.4 ms of serial overhead, which
    dominated the round-2 per-window formulation.

    Outputs: X, Y, Z of [8]([s]B + [k]A - R); the identity test runs at
    the XLA level (freeze has no multiplies).
    """
    global _KCONSTS
    nl = F.NLIMBS
    with F.kernel_mode(bias_ref[...]):
        _KCONSTS = {"d2": consts_ref[0:nl, :]}
        try:
            a_pt = (ax[...], ay[...], az[...], at[...])
            batch = a_pt[0].shape[1]

            # Lane table of [e]A, e in 0..8, niels form, in VMEM scratch.
            ident_n = (
                _row0_const(1, nl, batch),
                _row0_const(1, nl, batch),
                jnp.zeros((nl, batch), jnp.int32),
                _row0_const(2, nl, batch),
            )
            n1 = to_niels(a_pt)
            entries = [ident_n, n1]
            pk = a_pt
            for _ in range(7):
                pk = add_niels(pk, n1)
                entries.append(to_niels(pk))
            for e, niels in enumerate(entries):
                for c in range(4):
                    tbl[(e * 4 + c) * nl : (e * 4 + c + 1) * nl, :] = niels[c]

            def tbl_rows(e, c):
                base = (e * 4 + c) * nl
                return tbl[base : base + nl, :]

            def base_rows(e, c):
                base = (e * 3 + c) * nl
                return base_ref[base : base + nl, :]

            def body(i, r):
                w = 63 - i
                ws = ws_ref[pl_dslice(w, 1), :]
                wk = wk_ref[pl_dslice(w, 1), :]
                return _window_step(r, tbl_rows, ws, wk, base_rows)

            r = lax.fori_loop(0, 64, body, _kernel_identity(batch))
            r = add(r, neg((rx[...], ry[...], rz[...], rt[...])))
            for _ in range(3):
                r = dbl_no_t(r)
                r = (r[0], r[1], r[2], None)
        finally:
            _KCONSTS = None
    xo[...], yo[...], zo[...] = r[0], r[1], r[2]


pl_dslice = None  # bound lazily (pallas import is TPU-path-only)


def _ladder_sub_mul8_pallas(s_digits, k_digits, a_point, r_point):
    global pl_dslice
    import jax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    pl_dslice = pl.dslice
    batch = s_digits.shape[1]
    tile = min(batch, F._PALLAS_TILE)
    nl = F.NLIMBS
    base_flat = jnp.asarray(BASE_NIELS).reshape(9 * 3 * nl, 1)
    bias = jnp.asarray(F._SUB_BIAS)
    consts = jnp.asarray(_CONSTS_NP)

    point_spec = pl.BlockSpec((nl, tile), lambda i: (0, i), memory_space=pltpu.VMEM)
    dig_spec = pl.BlockSpec((64, tile), lambda i: (0, i), memory_space=pltpu.VMEM)
    base_spec = pl.BlockSpec(
        (9 * 3 * nl, 1), lambda i: (0, 0), memory_space=pltpu.VMEM
    )
    bias_spec = pl.BlockSpec((nl, 1), lambda i: (0, 0), memory_space=pltpu.VMEM)
    consts_spec = pl.BlockSpec(
        (3 * nl, 1), lambda i: (0, 0), memory_space=pltpu.VMEM
    )
    out = pl.pallas_call(
        _ladder_sub_kernel,
        out_shape=[jax.ShapeDtypeStruct((nl, batch), jnp.int32)] * 3,
        grid=(batch // tile,),
        in_specs=[point_spec] * 8 + [dig_spec, dig_spec, base_spec,
                                     bias_spec, consts_spec],
        out_specs=[point_spec] * 3,
        scratch_shapes=[pltpu.VMEM((9 * 4 * nl, tile), jnp.int32)],
        name="curve_ladder_sub_mul8",
    )(*a_point, *r_point, s_digits, k_digits, base_flat, bias, consts)
    return tuple(out)


def ladder_sub_mul8(s_digits, k_digits, a_point, r_point):
    """(X, Y, Z) of [8]([s]B + [k]a_point - r_point) — the whole ZIP-215
    verification equation left side. On TPU this is ONE fused kernel."""
    if F._use_pallas(s_digits):
        return _ladder_sub_mul8_pallas(s_digits, k_digits, a_point, r_point)
    r = ladder(s_digits, k_digits, a_point)
    r = add(r, neg(r_point))
    m = mul8(r)
    return (m[0], m[1], m[2])


def ladder(s_digits, k_digits, a_point):
    """[s]B + [k]a_point with shared doublings, signed radix-16 digits.

    s_digits, k_digits: (64, B) int32 in [-8, 7], little-endian (digit i
    weighs 16^i) — from ops.scalar.recode_signed. a_point: batched extended
    point. Scans digits from most to least significant. XLA value-form
    (the TPU path runs the fused kernel via ladder_sub_mul8 instead).
    """
    batch = s_digits.shape[1]
    tbl = lane_table(a_point)
    xs = (jnp.flip(s_digits, axis=0), jnp.flip(k_digits, axis=0))

    def tbl_rows_factory(tblv):
        def tbl_rows(e, c):
            return tblv[e, c]

        return tbl_rows

    def body(r, w):
        ws, wk = w
        r = _window_step(r, tbl_rows_factory(tbl), ws[None, :], wk[None, :])
        return r, None

    r0 = identity(batch)
    r, _ = lax.scan(body, r0, xs)
    return r


def fixed_base(s_digits):
    """[s]B from signed digits (64, B) — keygen/test helper."""
    batch = s_digits.shape[1]

    def body(r, ws):
        r = dbl_no_t(r)
        r = dbl_no_t(r)
        r = dbl_no_t(r)
        r = dbl(r)
        r = _base_madd(r, ws[None, :])
        return r, None

    r, _ = lax.scan(body, identity(batch), jnp.flip(s_digits, axis=0))
    return r


def mul8(p):
    def body(xyz, _):
        r = dbl_no_t((xyz[0], xyz[1], xyz[2], None))
        return (r[0], r[1], r[2]), None

    (x, y, z), _ = lax.scan(body, (p[0], p[1], p[2]), None, length=3)
    return (x, y, z, None)


def scalar_digits(scalars) -> np.ndarray:
    """Host-side: python ints (< 2^253) -> (64, N) int32 signed digits.

    Same recoding as ops.scalar.recode_signed, for host-held scalars
    (test/bench data generation)."""
    half = int("8" * 64, 16)
    out = np.zeros((64, len(scalars)), np.int32)
    for lane, s in enumerate(scalars):
        t = s + half
        for i in range(64):
            out[i, lane] = ((t >> (4 * i)) & 15) - 8
    return out
