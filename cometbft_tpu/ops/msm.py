"""Random-linear-combination batch verification as one multi-scalar
multiplication — the TPU Pippenger engine.

The reference's CPU batch verifier (crypto/ed25519/ed25519.go:207-240 via
curve25519-voi) collapses N verifications into ONE check

    [8]( [c]B - sum_i [z_i]R_i - sum_i [z_i h_i]A_i ) == identity,
    c = sum_i z_i s_i  (mod L),  z_i random 128-bit, h_i = H(R||A||M)

which is a 2N-point multi-scalar multiplication. Naive Pippenger bucket
accumulation is a scatter — hostile to SIMD lanes — so the TPU engine
inverts the data flow: the HOST (numpy, cometbft_tpu/crypto/rlc.py)
computes all scalars and signed base-2^C digits, sorts the (window,
bucket) contributions, and ships a dense (W*K, S) gather table; the
DEVICE then runs

  1. batched ZIP-215 decompression of all A_i, R_i (existing kernel),
  2. S sequential rounds of lane-parallel mixed additions — each round
     gathers one point per (window, bucket) lane and folds it in,
  3. a masked-tree weighted bucket reduction (sum_b (b+1)*B_b as a
     sum over weight bits of tree-reduced masked partials),
  4. a Horner combine over windows (10 doublings + 1 add per window),
  5. [c]B via the fixed-base ladder, final add, cofactor x8, identity
     check -> ONE scalar verdict.

Per-signature device cost ~1350 field muls vs ~3450 for the per-lane
ladder (ops/ed25519_verify.py) — the bucket axis (W*K = 13312 lanes)
keeps the VPU full while the digit structure lives host-side where
sorting is free. On batch failure the caller falls back to the per-lane
bitmap kernel, mirroring the reference's fallback scan
(types/validation.go:304-311).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from . import curve as C
from . import field as F

# Signed digit decomposition: base 2^C_BITS, buckets hold |digit| in
# [1, K]; every (scalar-class, window) pair owns its own K-lane region
# (26 windows for the 253-bit z*h scalars + 13 for the 128-bit z),
# ordered by descending weight. REGION_DBL[r] is how many doublings the
# Horner chain applies BEFORE folding region r in: 10 when the weight
# drops a window, 0 when region r shares its window with the previous
# one (the z/m split of windows 0..12). Layout authority:
# cometbft_tpu/crypto/rlc.py region_of_m / region_of_z.
C_BITS = 10
K_BUCKETS = 1 << (C_BITS - 1)  # 512
N_WINDOWS = 26
Z_WINDOWS = 13
N_REGIONS = N_WINDOWS + Z_WINDOWS  # 39
WK = N_REGIONS * K_BUCKETS  # 19968 bucket-lanes
REGION_DBL = tuple(
    [0]
    + [C_BITS] * 13  # m24..m12
    + [0 if i % 2 else C_BITS for i in range(1, 26)]  # z12, m11, z11, ...
)
# regions: r0=m25; r1..r13 = m24..m12 (10 dbl each); r14=z12 (0);
# r15=m11 (10); r16=z11 (0); ...; r37=m0 (10); r38=z0 (0)
assert len(REGION_DBL) == N_REGIONS


def _accum_weight_kernel(stream_ref, w_ref, bias_ref, consts_ref,
                         xo, yo, zo, to, acc):
    """Fused accumulate + per-lane weight kernel.

    Grid (n_tiles, S): for one 512-lane tile, S sequential rounds each
    fold one gathered niels point into the VMEM accumulator (7-mul
    madd); the final round multiplies the accumulator by the lane's
    bucket weight (<= 2^C_BITS) with a 10-step double-and-add. One
    launch replaces the ~1300 per-mul launches of the jnp formulation —
    the same fusion lesson as the ladder kernel (ops/curve.py round 2).

    stream_ref: (72, tile) gathered rows for this (s, tile): ypx at
    0:22, the sign flag at row 22, ymx at 24:46, t2d at 48:70 — limb
    groups padded to 24 rows because pallas TPU block sublane dims must
    be multiples of 8. w_ref: (1, tile) int32 weights.
    acc: (4*nl, tile) VMEM scratch persisting across the S minor steps.
    """
    nl = F.NLIMBS
    s = pl.program_id(1)
    n_s = pl.num_programs(1)
    with F.kernel_mode(bias_ref[...]):
        C._KCONSTS = {"d2": consts_ref[0:nl, :]}
        try:
            tile = stream_ref.shape[1]

            @pl.when(s == 0)
            def _init():
                ident = C._kernel_identity(tile)
                for i in range(4):
                    acc[i * nl : (i + 1) * nl, :] = ident[i]

            cur = tuple(acc[i * nl : (i + 1) * nl, :] for i in range(4))
            ypx = stream_ref[0:nl, :]
            ymx = stream_ref[24 : 24 + nl, :]
            t2d = stream_ref[48 : 48 + nl, :]
            negf = stream_ref[22:23, :] != 0
            a = jnp.where(negf, ymx, ypx)
            b = jnp.where(negf, ypx, ymx)
            t = jnp.where(negf, F.neg(t2d), t2d)
            cur = C.madd(cur, (a, b, t))
            for i in range(4):
                acc[i * nl : (i + 1) * nl, :] = cur[i]

            @pl.when(s == n_s - 1)
            def _finish():
                accp = tuple(
                    acc[i * nl : (i + 1) * nl, :] for i in range(4)
                )
                w = w_ref[...]  # (1, tile)
                # seed from the top bit via select (Mosaic rejects the
                # add-onto-identity-constant graph shape), then classic
                # double-and-add over the remaining bits
                ident = C._kernel_identity(tile)
                top = ((w >> (C_BITS - 1)) & 1) != 0
                r = tuple(
                    jnp.where(top, a_c, i_c)
                    for a_c, i_c in zip(accp, ident)
                )
                for bit in range(C_BITS - 2, -1, -1):
                    r = C.dbl(r)
                    radd = C.add(r, accp)
                    sel = ((w >> bit) & 1) != 0
                    r = tuple(jnp.where(sel, ra, rr)
                              for ra, rr in zip(radd, r))
                xo[...], yo[...], zo[...], to[...] = r
        finally:
            C._KCONSTS = None


pl = None  # bound lazily (pallas import is TPU-path-only)


def _accumulate_weighted_pallas(niels, gather_idx, gather_neg, weights):
    """Kernel-path accumulation: ONE row-gather (XLA) + ONE pallas launch.

    niels: 3 coords (22, M). gather_idx/gather_neg: (S, WK).
    weights: (W, K) int32. Returns weighted per-lane extended points
    (4 x (22, WK)).
    """
    global pl
    import jax
    from jax.experimental import pallas as _pl
    from jax.experimental.pallas import tpu as pltpu

    pl = _pl
    nl = F.NLIMBS
    S = gather_idx.shape[0]
    tile = 512
    # one efficient row-major gather per coord; rows padded to 24 (block
    # sublane dims must divide by 8), the sign flag rides in pad row 22
    flat = gather_idx.reshape(-1)
    streams = []
    pad2 = None
    for c in niels:
        rows = c.T  # (M, 22)
        g = jnp.take(rows, flat, axis=0)  # (S*WK, 22)
        g = g.reshape(S, WK, nl).transpose(0, 2, 1)  # (S, nl, WK)
        if pad2 is None:
            pad2 = jnp.zeros((S, 1, WK), jnp.int32)
        streams.append(g)
    neg_row = gather_neg.astype(jnp.int32)[:, None, :]  # (S, 1, WK)
    stream = jnp.concatenate(
        [streams[0], neg_row, pad2,
         streams[1], pad2, pad2,
         streams[2], pad2, pad2],
        axis=1,
    ).reshape(S * 72, WK)
    w_arr = weights.reshape(1, WK).astype(jnp.int32)
    bias = jnp.asarray(F._SUB_BIAS)
    consts = jnp.asarray(C._CONSTS_NP)

    n_tiles = WK // tile
    stream_spec = _pl.BlockSpec(
        (72, tile), lambda t, s: (s, t), memory_space=pltpu.VMEM
    )
    w_spec = _pl.BlockSpec(
        (1, tile), lambda t, s: (0, t), memory_space=pltpu.VMEM
    )
    bias_spec = _pl.BlockSpec(
        (nl, 1), lambda t, s: (0, 0), memory_space=pltpu.VMEM
    )
    consts_spec = _pl.BlockSpec(
        (3 * nl, 1), lambda t, s: (0, 0), memory_space=pltpu.VMEM
    )
    out_spec = _pl.BlockSpec(
        (nl, tile), lambda t, s: (0, t), memory_space=pltpu.VMEM
    )
    out = _pl.pallas_call(
        _accum_weight_kernel,
        out_shape=[jax.ShapeDtypeStruct((nl, WK), jnp.int32)] * 4,
        grid=(n_tiles, S),
        in_specs=[stream_spec, w_spec, bias_spec, consts_spec],
        out_specs=[out_spec] * 4,
        scratch_shapes=[pltpu.VMEM((4 * nl, tile), jnp.int32)],
        name="msm_accumulate_weighted",
    )(stream, w_arr, bias, consts)
    return tuple(out)


def _region_tree_sum(weighted):
    """Plain (unweighted) pairwise tree over the K axis per region:
    (22, WK) -> (22, N_REGIONS). Lane counts shrink fast, so XLA's
    fused jnp path handles it without launch-overhead concerns."""
    pts = tuple(
        x.reshape(F.NLIMBS, N_REGIONS, K_BUCKETS) for x in weighted
    )
    k = K_BUCKETS
    while k > 1:
        half = k // 2
        p = tuple(
            x[..., :half].reshape(F.NLIMBS, -1) for x in pts
        )
        q = tuple(
            x[..., half : 2 * half].reshape(F.NLIMBS, -1) for x in pts
        )
        s = C.add(p, q)
        pts = tuple(x.reshape(F.NLIMBS, N_REGIONS, half) for x in s)
        k = half
    return tuple(x[..., 0] for x in pts)


def _identity_niels(batch: int):
    one = jnp.broadcast_to(
        jnp.asarray(F.from_int(1))[:, None], (F.NLIMBS, batch)
    )
    zero = jnp.zeros((F.NLIMBS, batch), jnp.int32)
    return one, one, zero  # (Y+X, Y-X, 2dT) of (0, 1)


def _accumulate(niels, gather_idx, gather_neg):
    """S rounds of lane-parallel mixed adds.

    niels: (ypx, ymx, t2d) each (22, M) — all points + identity sentinel.
    gather_idx: (S, WK) int32 into M; gather_neg: (S, WK) bool.
    Returns extended-coords accumulators (22, WK).
    """
    ypx, ymx, t2d = niels

    def body(acc, sl):
        idx, neg = sl
        g_ypx = jnp.take(ypx, idx, axis=1)
        g_ymx = jnp.take(ymx, idx, axis=1)
        g_t2d = jnp.take(t2d, idx, axis=1)
        a = F.select(neg, g_ymx, g_ypx)
        b = F.select(neg, g_ypx, g_ymx)
        t = F.select(neg, F.neg(g_t2d), g_t2d)
        return C.madd(acc, (a, b, t)), None

    acc0 = C.identity(WK)
    acc, _ = lax.scan(body, acc0, (gather_idx, gather_neg))
    return acc


def _bucket_reduce(acc, weights):
    """(22, WK) accumulators -> per-window sums sum_lane w_lane * B_lane.

    weights: (W, K) int32 per-lane digit values from the host layout
    (lane weights are data, not structure: hot digit values are split
    across several lanes sharing a weight, so non-uniform scalar
    distributions cost nothing on device).

    Masked-tree: sum w_l B_l = sum_j 2^j (sum_{l: bit_j(w_l)} B_l).
    All C_BITS bit-masked copies are stacked as extra lanes so ONE
    pairwise tree folds the bucket axis for every bit at once (same
    device flops as per-bit trees, 10x smaller XLA graph), then a short
    Horner pass combines the bit partials. Returns extended coords with
    lanes = N_WINDOWS.
    """
    # lanes (WK,) -> (1, W, K), broadcast against the bit axis -> (J, W, K)
    pts = tuple(
        x.reshape(F.NLIMBS, 1, N_REGIONS, K_BUCKETS) for x in acc
    )
    nbits = C_BITS
    # mask (J, W, K): bit j of each lane's weight
    bits = jnp.arange(nbits, dtype=jnp.int32)[:, None, None]
    mask = (((weights[None] >> bits) & 1) != 0)[None]

    ident4 = (
        jnp.zeros((F.NLIMBS, 1, 1, 1), jnp.int32),
        jnp.asarray(F.from_int(1))[:, None, None, None],
        jnp.asarray(F.from_int(1))[:, None, None, None],
        jnp.zeros((F.NLIMBS, 1, 1, 1), jnp.int32),
    )
    masked = tuple(
        jnp.broadcast_to(
            jnp.where(mask, x, i),
            (F.NLIMBS, nbits, N_REGIONS, K_BUCKETS),
        )
        for x, i in zip(pts, ident4)
    )

    k = K_BUCKETS
    while k > 1:
        half = k // 2
        flat_p = tuple(
            x[..., :half].reshape(F.NLIMBS, -1) for x in masked
        )
        flat_q = tuple(
            x[..., half : 2 * half].reshape(F.NLIMBS, -1) for x in masked
        )
        s = C.add(flat_p, flat_q)
        masked = tuple(
            x.reshape(F.NLIMBS, nbits, N_REGIONS, half) for x in s
        )
        k = half
    partials = tuple(x[..., 0] for x in masked)  # (22, J, W)

    # Horner over bits: S = sum_j 2^j T_j
    s = tuple(x[:, nbits - 1] for x in partials)
    for j in range(nbits - 2, -1, -1):
        s = C.dbl(s)
        s = C.add(s, tuple(x[:, j] for x in partials))
    return s


def _window_combine(win_sums):
    """Horner over regions (already ordered by descending weight):
    REGION_DBL[r] doublings, then fold region r's sum in. Regions that
    share a window (the z/m split) get 0 doublings between them.

    win_sums: extended coords (22, N_REGIONS). Returns (22, 1)."""

    def ten_dbl(p):
        for _ in range(C_BITS):
            p = C.dbl(p)
        return p

    def body(acc, xs):
        r_idx, flag = xs
        pt = tuple(
            lax.dynamic_slice_in_dim(x, r_idx, 1, axis=1) for x in win_sums
        )
        acc = lax.cond(flag > 0, ten_dbl, lambda p: p, acc)
        return C.add(acc, pt), None

    acc0 = C.identity(1)
    acc, _ = lax.scan(
        body,
        acc0,
        (
            jnp.arange(N_REGIONS),
            jnp.asarray(REGION_DBL, dtype=jnp.int32),
        ),
    )
    return acc


def expand_stream(stream, stream_neg, counts, s_rounds):
    """Dense contribution stream -> (S, WK) gather table, on device.

    The host ships ~2 bytes per contribution (crypto/rlc.py); the
    padded per-round table the accumulate kernel wants is rebuilt here
    with a cumsum + masked take. stream: (L,) uint16/uint32 where L is
    tier-padded to a multiple of 8192 (stable jit shapes across the
    per-batch random layouts): the first C entries are the dense
    contributions, every trailing slot holds the identity sentinel, and
    invalid gathers target L-1; stream_neg: bit-packed signs over the
    full padded length (L/8 bytes); counts: (WK,) — sum(counts) = C.
    """
    counts = counts.astype(jnp.int32)
    offsets = jnp.cumsum(counts) - counts  # exclusive prefix
    pos = offsets[None, :] + jnp.arange(s_rounds, dtype=jnp.int32)[:, None]
    valid = jnp.arange(s_rounds, dtype=jnp.int32)[:, None] < counts[None, :]
    pos = jnp.where(valid, pos, stream.shape[0] - 1)
    idx = jnp.take(stream, pos).astype(jnp.int32)
    negb = jnp.take(stream_neg, pos >> 3).astype(jnp.int32)
    neg = ((negb >> (pos & 7)) & 1) != 0
    neg = neg & valid  # padding gathers the identity, sign irrelevant
    return idx, neg


def rlc_verify(a_bytes, r_bytes, live, gather_idx, gather_neg, weights,
               c_digits):
    """One-scalar RLC batch verification.

    a_bytes, r_bytes: (B, 32) uint8 encodings.
    live: (B,) bool — padding lanes excluded from the decompression check
          (their z_i are zero host-side, so they never enter the sum).
    gather_idx: (S, WK) int32 — point index per round per bucket-lane;
          R_i at i, A_i at B+i, identity sentinel at 2B.
    gather_neg: (S, WK) bool — effective sign (digit sign pre-negated
          host-side to absorb the -R, -A in the equation).
    weights: (W, K) int32 — per-lane digit weights (host layout).
    c_digits: (64, 1) int32 — signed nibble digits of c = sum z_i s_i.

    Returns scalar bool: the whole batch verifies.
    """
    # the phases are utils/trace.KERNEL_SCOPES: names on the operations,
    # for a profiler trace; the program is what it is without them
    with jax.named_scope("rlc.decompress"):
        ok_a, a_pt = C.decompress(a_bytes)
        ok_r, r_pt = C.decompress(r_bytes)

        # affine niels (Z=1 after decompress): (Y+X, Y-X, 2dT)
        def niels_of(p):
            n = C.to_niels(p)
            return n[0], n[1], n[2]

        na, nr = niels_of(a_pt), niels_of(r_pt)
        ident = _identity_niels(1)
        niels = tuple(
            jnp.concatenate([r_c, a_c, i_c], axis=1)
            for r_c, a_c, i_c in zip(nr, na, ident)
        )

    if F._use_pallas(jnp.zeros((F.NLIMBS, WK), jnp.int32)):
        with jax.named_scope("rlc.accumulate"):
            weighted = _accumulate_weighted_pallas(
                niels, gather_idx, gather_neg, weights
            )
        with jax.named_scope("rlc.bucket_reduce"):
            win_sums = _region_tree_sum(weighted)
    else:
        with jax.named_scope("rlc.accumulate"):
            acc = _accumulate(niels, gather_idx, gather_neg)
        with jax.named_scope("rlc.bucket_reduce"):
            win_sums = _bucket_reduce(acc, weights)
    with jax.named_scope("rlc.window_combine"):
        msm = _window_combine(win_sums)
    with jax.named_scope("rlc.final_check"):
        total = C.add(msm, C.fixed_base(c_digits))
        ok_eq = C.is_identity(C.mul8(total))[0]
        ok_points = jnp.all(ok_a | ~live) & jnp.all(ok_r | ~live)
        return ok_eq & ok_points


rlc_verify_jit = jax.jit(rlc_verify)


def rlc_verify_stream(a_bytes, r_bytes, live, stream, stream_neg, counts,
                      weights, c_digits, *, s_rounds: int):
    """rlc_verify over the compact wire format: the (S, WK) table is
    expanded on device (expand_stream) from the dense contribution
    stream, so the host->device link carries ~2 B/contribution."""
    with jax.named_scope("rlc.expand_stream"):
        gather_idx, gather_neg = expand_stream(
            stream, stream_neg, counts, s_rounds
        )
    return rlc_verify(a_bytes, r_bytes, live, gather_idx, gather_neg,
                      weights, c_digits)


rlc_verify_stream_jit = jax.jit(
    rlc_verify_stream, static_argnames=("s_rounds",)
)
