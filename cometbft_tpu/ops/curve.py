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


# --- Constant base tables: affine niels of [i * 2^shift]B for i in 0..8 ---
def _host_base_niels(shift: int = 0) -> np.ndarray:
    out = np.zeros((9, 3, F.NLIMBS), np.int32)
    out[0, 0] = F.from_int(1)  # identity: y+x=1, y-x=1, 2dxy=0
    out[0, 1] = F.from_int(1)
    for i in range(1, 9):
        x, y = ref._ext_to_affine(ref._ext_scalar_mul(i << shift, ref.B_POINT))
        out[i, 0] = F.from_int((y + x) % P)
        out[i, 1] = F.from_int((y - x) % P)
        out[i, 2] = F.from_int((2 * ref.D * x * y) % P)
    return out


BASE_NIELS = jnp.asarray(_host_base_niels())  # (9, 3, 22)
# [i * 2^128]B: the base table of the upper 32 digits when the ladder is
# given the pair (A, [2^128]A) and runs 32 windows (ladder_sub_mul8)
BASE_NIELS_HI = jnp.asarray(_host_base_niels(128))
# points a ladder -> its base tables, (9 * points, 3, 22): table j is rows
# [9j, 9j + 9)
_BASE_TABLES = {1: BASE_NIELS, 2: jnp.concatenate([BASE_NIELS, BASE_NIELS_HI])}


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


def _window_step(r, base, lane):
    """One radix-16 window: 4 doublings, then one base madd and one lane
    add for each point the ladder was given.

    r: extended point of (22, B) arrays. base: [(base_rows, ws_row), ...]
    and lane: [(tbl_rows, wk_row), ...], one pair a point: a callable
    (entry, comp) -> table component (base_rows None = BASE_NIELS) and
    that table's (1, B) signed digits of this window. Pure value-form:
    runs identically inside the Pallas kernel and on the XLA (CPU) path.
    """
    r = dbl_no_t(r)
    r = dbl_no_t(r)
    r = dbl_no_t(r)
    r = dbl(r)
    for base_rows, ws_row in base:
        r = _base_madd(r, ws_row, base_rows)
    for tbl_rows, wk_row in lane:
        # lane-table niels add (4th component z2 carries no sign)
        lypx, lymx, lt2d, lz2 = _select_rows(
            tbl_rows, 4, jnp.abs(wk_row), wk_row.shape[1]
        )
        ypx, ymx, t2d = _apply_sign_affine(wk_row < 0, lypx, lymx, lt2d)
        r = add_niels(r, (ypx, ymx, t2d, lz2))
    return r


def _kernel_identity(batch: int):
    """Identity point synthesized in-kernel (no captured constants)."""
    z = jnp.zeros((F.NLIMBS, batch), jnp.int32)
    one = _row0_const(1, F.NLIMBS, batch)
    return (z, one, one, z)


def _ladder_sub_kernel(npts, *refs):
    """THE fused Pallas kernel: per tile it builds the 9-entry lane table
    of each of its `npts` points in VMEM, runs all 64 / npts
    shared-doubling windows (fori_loop: one traced window body),
    subtracts R and multiplies by the cofactor, all without leaving VMEM.
    One launch per ladder instead of ~350: on this runtime each pallas
    launch carries ~0.4 ms of serial overhead, which dominated the
    round-2 per-window formulation.

    Point j is [16^(j * 64 / npts)]A and takes digit rows j * 64 / npts
    and up of ws_ref / wk_ref, and base table j of base_ref.

    Outputs: X, Y, Z of [8]([s]B + [k]A - R); the identity test runs at
    the XLA level (freeze has no multiplies).
    """
    global _KCONSTS
    a_refs, refs = refs[: 4 * npts], refs[4 * npts :]
    (rx, ry, rz, rt, ws_ref, wk_ref, base_ref, bias_ref, consts_ref,
     xo, yo, zo, tbl) = refs
    nl = F.NLIMBS
    windows = 64 // npts
    with F.kernel_mode(bias_ref[...]):
        _KCONSTS = {"d2": consts_ref[0:nl, :]}
        try:
            a_pts = [tuple(a[...] for a in a_refs[4 * j : 4 * j + 4])
                     for j in range(npts)]
            batch = a_pts[0][0].shape[1]
            ident_n = (
                _row0_const(1, nl, batch),
                _row0_const(1, nl, batch),
                jnp.zeros((nl, batch), jnp.int32),
                _row0_const(2, nl, batch),
            )

            def rows_of(ref, j, ncomps):
                """(entry, comp) -> that (22, .) slice of table j in ref."""
                def rows(e, c):
                    row = ((j * 9 + e) * ncomps + c) * nl
                    return ref[row : row + nl, :]

                return rows

            # Lane tables of [e]A_j, e in 0..8, niels form, in VMEM scratch:
            # ONE traced chain for all the points, side by side along the
            # lanes (a chain a point costs the same multiplications and
            # 5 s of lowering a process, warm cache or not).
            wide = a_pts[0] if npts == 1 else tuple(
                jnp.concatenate(cs, axis=1) for cs in zip(*a_pts))
            n1 = to_niels(wide)
            entries = [n1]
            pk = wide
            for _ in range(7):
                pk = add_niels(pk, n1)
                entries.append(to_niels(pk))
            for j in range(npts):
                lanes = slice(j * batch, (j + 1) * batch)
                for e, niels in enumerate([ident_n] + entries):
                    for c in range(4):
                        row = ((j * 9 + e) * 4 + c) * nl
                        tbl[row : row + nl, :] = (
                            niels[c] if e == 0 or npts == 1
                            else niels[c][:, lanes])

            def body(i, r):
                w = windows - 1 - i
                # no `+ 0` for the first point: given one point the traced
                # program is the 64-window one to the operation
                rows = [pl_dslice(w + j * windows if j else w, 1)
                        for j in range(npts)]
                return _window_step(
                    r,
                    [(rows_of(base_ref, j, 3), ws_ref[rows[j], :])
                     for j in range(npts)],
                    [(rows_of(tbl, j, 4), wk_ref[rows[j], :])
                     for j in range(npts)],
                )

            r = lax.fori_loop(0, windows, body, _kernel_identity(batch))
            r = add(r, neg((rx[...], ry[...], rz[...], rt[...])))
            for _ in range(3):
                r = dbl_no_t(r)
                r = (r[0], r[1], r[2], None)
        finally:
            _KCONSTS = None
    xo[...], yo[...], zo[...] = r[0], r[1], r[2]


pl_dslice = None  # bound lazily (pallas import is TPU-path-only)


def _ladder_sub_mul8_pallas(s_digits, k_digits, a_points, r_point):
    global pl_dslice
    import functools

    import jax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    pl_dslice = pl.dslice
    npts = len(a_points)
    batch = s_digits.shape[1]
    tile = min(batch, F._PALLAS_TILE)
    nl = F.NLIMBS
    base_flat = _BASE_TABLES[npts].reshape(npts * 9 * 3 * nl, 1)
    bias = jnp.asarray(F._SUB_BIAS)
    consts = jnp.asarray(_CONSTS_NP)

    point_spec = pl.BlockSpec((nl, tile), lambda i: (0, i), memory_space=pltpu.VMEM)
    dig_spec = pl.BlockSpec((64, tile), lambda i: (0, i), memory_space=pltpu.VMEM)
    base_spec = pl.BlockSpec(
        (npts * 9 * 3 * nl, 1), lambda i: (0, 0), memory_space=pltpu.VMEM
    )
    bias_spec = pl.BlockSpec((nl, 1), lambda i: (0, 0), memory_space=pltpu.VMEM)
    consts_spec = pl.BlockSpec(
        (3 * nl, 1), lambda i: (0, 0), memory_space=pltpu.VMEM
    )
    out = pl.pallas_call(
        functools.partial(_ladder_sub_kernel, npts),
        out_shape=[jax.ShapeDtypeStruct((nl, batch), jnp.int32)] * 3,
        grid=(batch // tile,),
        in_specs=[point_spec] * (4 * npts + 4) + [
            dig_spec, dig_spec, base_spec, bias_spec, consts_spec],
        out_specs=[point_spec] * 3,
        scratch_shapes=[pltpu.VMEM((npts * 9 * 4 * nl, tile), jnp.int32)],
        name="curve_ladder_sub_mul8",
    )(*(c for p in a_points for c in p), *r_point, s_digits, k_digits,
      base_flat, bias, consts)
    return tuple(out)


def ladder_sub_mul8(s_digits, k_digits, a_points, r_point):
    """(X, Y, Z) of [8]([s]B + [k]A - r_point): the whole ZIP-215
    verification equation's left side. On TPU this is ONE fused kernel.

    a_points says how long the ladder is: (A,) runs 64 windows; the pair
    (A, [2^128]A), which a caller that keeps A on the device also keeps
    (mul_2_128), runs 32 windows of two lane adds and two base madds
    each: half the doublings for the same group element, whatever A."""
    if F._use_pallas(s_digits):
        return _ladder_sub_mul8_pallas(s_digits, k_digits, a_points, r_point)
    r = ladder(s_digits, k_digits, a_points)
    r = add(r, neg(r_point))
    m = mul8(r)
    return (m[0], m[1], m[2])


def ladder(s_digits, k_digits, a_points):
    """[s]B + [k]A with shared doublings, signed radix-16 digits.

    s_digits, k_digits: (64, B) int32 in [-8, 7], little-endian (digit i
    weighs 16^i), from ops.scalar.recode_signed. a_points: (A,) or
    (A, [2^128]A), batched extended points (ladder_sub_mul8). Scans
    digits from most to least significant, each point over its own
    64 / len(a_points) rows. XLA value-form (the TPU path runs the fused
    kernel via ladder_sub_mul8 instead).
    """
    npts = len(a_points)
    batch = s_digits.shape[1]
    tbls = [lane_table(p) for p in a_points]
    bases = _BASE_TABLES[npts].reshape(npts, 9, 3, F.NLIMBS)

    base_rows = [lambda e, c, t=t: t[e, c][:, None] for t in bases]
    tbl_rows = [lambda e, c, t=t: t[e, c] for t in tbls]

    def by_point(digits):  # (64, B) -> (windows, npts, B), top window first
        return jnp.flip(digits.reshape(npts, 64 // npts, batch), 1).swapaxes(0, 1)

    def body(r, w):
        ws, wk = w
        r = _window_step(
            r,
            [(rows, ws[j][None, :]) for j, rows in enumerate(base_rows)],
            [(rows, wk[j][None, :]) for j, rows in enumerate(tbl_rows)],
        )
        return r, None

    r, _ = lax.scan(body, identity(batch), (by_point(s_digits), by_point(k_digits)))
    return r


def mul_2_128(p):
    """[2^128]p by 128 doublings (exact for every point, torsion
    included): the second point of ladder_sub_mul8's pair. On TPU one
    fused kernel, a tile at a time like the decompression."""
    if F._use_pallas(p[0]):
        return _mul_2_128_pallas(p)
    xyz, _ = lax.scan(lambda q, _: (dbl_no_t((*q, None))[:3], None),
                      p[:3], None, length=127)
    return dbl((*xyz, None))


def _mul_2_128_kernel(x, y, z, bias_ref, xo, yo, zo, to):
    with F.kernel_mode(bias_ref[...]):
        xyz = lax.fori_loop(
            0, 127, lambda i, q: dbl_no_t((*q, None))[:3],
            (x[...], y[...], z[...]))
        r = dbl((*xyz, None))
    xo[...], yo[...], zo[...], to[...] = r


def _mul_2_128_pallas(p):
    import jax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    batch = p[0].shape[1]
    tile = min(batch, F._PALLAS_TILE)
    nl = F.NLIMBS
    point_spec = pl.BlockSpec((nl, tile), lambda i: (0, i), memory_space=pltpu.VMEM)
    bias_spec = pl.BlockSpec((nl, 1), lambda i: (0, 0), memory_space=pltpu.VMEM)
    return tuple(pl.pallas_call(
        _mul_2_128_kernel,
        out_shape=[jax.ShapeDtypeStruct((nl, batch), jnp.int32)] * 4,
        grid=(batch // tile,),
        in_specs=[point_spec] * 3 + [bias_spec],
        out_specs=[point_spec] * 4,
        name="curve_mul_2_128",
    )(*p[:3], jnp.asarray(F._SUB_BIAS)))


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
