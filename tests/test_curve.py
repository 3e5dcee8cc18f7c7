"""Differential tests: JAX edwards25519 point ops vs the Python oracle."""

import numpy as np
import jax
import jax.numpy as jnp

from cometbft_tpu.crypto import ed25519_ref as ref
from cometbft_tpu.ops import curve as C
from cometbft_tpu.ops import field as F

P = F.P_INT
rng = np.random.default_rng(7)


def _torsion_point():
    """A nontrivial 8-torsion point: [L]P for P outside the prime subgroup."""
    for y in range(2, 50):
        aff = ref._decode_point(y.to_bytes(32, "little"), zip215=True)
        if aff is None:
            continue
        t = ref._ext_scalar_mul(ref.L, ref._to_ext(aff))
        if not ref._ext_is_identity(t):
            return t
    raise AssertionError("no torsion point found")


TORSION = _torsion_point()


def _rand_points(n):
    """Random curve points; every third has an 8-torsion component mixed in
    (the ZIP-215-admitted points outside the prime-order subgroup that the
    complete addition law must handle)."""
    pts = []
    for i in range(n):
        k = int.from_bytes(rng.bytes(32), "little") % ref.L
        p = ref._ext_scalar_mul(k if k else 1, ref.B_POINT)
        if i % 3 == 2:
            p = ref._ext_add(p, TORSION)
        pts.append(p)
    return pts


def _pack_points(pts):
    """List of python extended points -> batched JAX point (affine-normalized)."""
    coords = []
    for pt in pts:
        x, y = ref._ext_to_affine(pt)
        coords.append((x, y, 1, (x * y) % P))
    arrs = []
    for c in range(4):
        arrs.append(
            jnp.stack([jnp.asarray(F.from_int(p[c])) for p in coords], axis=1)
        )
    return tuple(arrs)


def _affine_of(jp):
    """Batched JAX point -> list of affine tuples via the oracle's math."""
    X, Y, Z, _ = [np.asarray(F.freeze(a)) for a in jp]
    out = []
    for i in range(X.shape[1]):
        x, y, z = F.to_int(X[:, i]), F.to_int(Y[:, i]), F.to_int(Z[:, i])
        zi = pow(z, P - 2, P)
        out.append(((x * zi) % P, (y * zi) % P))
    return out


j_add = jax.jit(C.add)
j_dbl = jax.jit(C.dbl)
j_ladder = jax.jit(C.ladder)
j_decompress = jax.jit(C.decompress)
j_compress = jax.jit(C.compress)


def test_add_dbl_matches_oracle():
    ps = _rand_points(8)
    qs = _rand_points(8)
    got = _affine_of(j_add(_pack_points(ps), _pack_points(qs)))
    want = [ref._ext_to_affine(ref._ext_add(p, q)) for p, q in zip(ps, qs)]
    assert got == want
    got = _affine_of(j_dbl(_pack_points(ps)))
    want = [ref._ext_to_affine(ref._ext_add(p, p)) for p in ps]
    assert got == want


def test_add_identity_and_self():
    """Completeness: P + (-P), P + P, P + identity via the unified formula."""
    ps = _rand_points(4)
    jp = _pack_points(ps)
    s = j_add(jp, jax.jit(C.neg)(jp))
    assert bool(np.asarray(C.is_identity(s)).all())
    ident = C.identity(4)
    got = _affine_of(j_add(jp, ident))
    assert got == [ref._ext_to_affine(p) for p in ps]
    got = _affine_of(j_add(jp, jp))
    assert got == [ref._ext_to_affine(ref._ext_add(p, p)) for p in ps]


def test_decompress_compress_roundtrip():
    ps = _rand_points(8)
    encs = np.stack(
        [np.frombuffer(ref._encode_point(*ref._ext_to_affine(p)), np.uint8) for p in ps]
    )
    valid, jp = j_decompress(jnp.asarray(encs))
    assert bool(np.asarray(valid).all())
    assert _affine_of(jp) == [ref._ext_to_affine(p) for p in ps]
    back = np.asarray(j_compress(jp))
    assert (back == encs).all()


def test_decompress_zip215_semantics():
    def with_sign(y: int) -> bytes:
        b = bytearray(y.to_bytes(32, "little"))
        b[31] |= 0x80
        return bytes(b)

    cases = [
        ref._encode_point(0, 1),  # canonical identity (y=1)
        (1 + P).to_bytes(32, "little"),  # non-canonical y = 1+p (accepted)
        with_sign(1),  # x=0 with sign bit set ("negative zero", accepted)
        (0).to_bytes(32, "little"),  # y=0: order-4 point (sqrt(-1), 0)
        P.to_bytes(32, "little"),  # non-canonical y = 0 + p (accepted)
        with_sign(P),  # non-canonical y=p AND sign bit (accepted, x flipped)
    ]
    # y with no valid x (non-square) and a few small valid ys: oracle decides
    cases += [y.to_bytes(32, "little") for y in range(2, 6)]
    want = [ref._decode_point(e, zip215=True) for e in cases]
    encs = np.stack([np.frombuffer(e, np.uint8) for e in cases])
    valid, jp = j_decompress(jnp.asarray(encs))
    assert list(np.asarray(valid)) == [w is not None for w in want]
    assert want[0] is not None and want[1] is not None and want[2] is not None
    assert want[3] is not None and want[4] is not None and want[5] is not None
    # oracle agreement on decoded coords for the valid ones
    aff = _affine_of(jp)
    for i, w in enumerate(want):
        if w is not None:
            assert aff[i] == w, i


def test_ladder_double_scalar():
    n = 4
    pts = _rand_points(n)
    ss = [int.from_bytes(rng.bytes(32), "little") % ref.L for _ in range(n)]
    ks = [int.from_bytes(rng.bytes(32), "little") % ref.L for _ in range(n)]
    jp = _pack_points(pts)
    r = j_ladder(
        jnp.asarray(C.scalar_digits(ss)), jnp.asarray(C.scalar_digits(ks)), (jp,)
    )
    want = [
        ref._ext_to_affine(
            ref._ext_add(ref._ext_scalar_mul(s, ref.B_POINT), ref._ext_scalar_mul(k, p))
        )
        for s, k, p in zip(ss, ks, pts)
    ]
    assert _affine_of(r) == want


def test_ladder_zero_scalars():
    n = 2
    pts = _rand_points(n)
    jp = _pack_points(pts)
    z = jnp.asarray(C.scalar_digits([0, 0]))
    r = j_ladder(z, z, (jp,))
    assert bool(np.asarray(C.is_identity(r)).all())


def test_fixed_base_matches_scalar_mul():
    ss = [0, 1, 7, ref.L - 1, int.from_bytes(rng.bytes(32), "little") % ref.L]
    r = jax.jit(C.fixed_base)(jnp.asarray(C.scalar_digits(ss)))
    X = np.asarray(F.freeze(r[0]))
    for i, s in enumerate(ss):
        want = ref._ext_scalar_mul(s, ref.B_POINT)
        if s == 0:
            assert F.to_int(X[:, i]) == 0
        else:
            got = _affine_of(tuple(a[:, i:i + 1] for a in r))[0]
            assert got == ref._ext_to_affine(want), i


# --- the pair (A, [2^128]A): a 32-window ladder (ISSUE 39) -----------------

from cometbft_tpu.ops import ed25519_verify as EV  # noqa: E402
from cometbft_tpu.ops import scalar as SC  # noqa: E402

import pytest  # noqa: E402

LANES = 8  # one width for every case: each jitted program compiles once
j_mul_2_128 = jax.jit(C.mul_2_128)
j_sub_mul8 = jax.jit(C.ladder_sub_mul8)
j_digits = jax.jit(SC.digits_from_bytes)
j_128_dbls = jax.jit(
    lambda p: jax.lax.fori_loop(0, 128, lambda i, q: C.dbl(q), p))


def _torsion_points():
    """All 8 points of the torsion subgroup (the identity among them)."""
    for y in range(2, 200):
        aff = ref._decode_point(y.to_bytes(32, "little"), zip215=True)
        if aff is None:
            continue
        t = ref._ext_scalar_mul(ref.L, ref._to_ext(aff))
        pts = [ref._ext_scalar_mul(i, t) for i in range(8)]
        if len({ref._ext_to_affine(p) for p in pts}) == 8:
            return pts
    raise AssertionError("no generator of the torsion subgroup found")


TORSION_8 = _torsion_points()


def _points(kind):
    if kind == "torsion":
        return TORSION_8
    pts = []
    for i in range(LANES):
        k = int.from_bytes(rng.bytes(32), "little") % ref.L
        p = ref._ext_scalar_mul(k or 1, ref.B_POINT)
        if kind == "mixed_order":
            p = ref._ext_add(p, TORSION_8[1 + i % 7])
        pts.append(p)
    return pts


def _scalar_rows(values):
    """(LANES, 32) uint8 little-endian rows of python ints < 2^256."""
    return jnp.asarray(np.stack(
        [np.frombuffer(v.to_bytes(32, "little"), np.uint8) for v in values]))


def _digits_value(digits, lane):
    """The integer the ladder multiplies by, from the digits the device
    made (for 32 bytes of ones the recoding's top carry is lost and this
    is -1: both ladders and the oracle then agree on [-1]A)."""
    d = np.asarray(digits)[:, lane]
    return sum(int(v) << (4 * i) for i, v in enumerate(d)) % (8 * ref.L)


def _ladders_agree(pts, r_pts, s_vals, k_vals):
    """ladder_sub_mul8 given (A, [2^128]A) == given (A,) == the oracle's
    [8]([s]B + [k]A - R), lane by lane."""
    jp, jr = _pack_points(pts), _pack_points(r_pts)
    sd, kd = j_digits(_scalar_rows(s_vals)), j_digits(_scalar_rows(k_vals))
    one = j_sub_mul8(sd, kd, (jp,), jr)
    pair = j_sub_mul8(sd, kd, (jp, j_mul_2_128(jp)), jr)
    want = []
    for i, (p, r) in enumerate(zip(pts, r_pts)):
        q = ref._ext_add(
            ref._ext_scalar_mul(_digits_value(sd, i), ref.B_POINT),
            ref._ext_scalar_mul(_digits_value(kd, i), p))
        q = ref._ext_add(q, ref._ext_neg(r))
        want.append(ref._ext_to_affine(ref._ext_scalar_mul(8, q)))
    assert _affine_of((*one, one[0])) == want  # no T: any 4th
    assert _affine_of((*pair, pair[0])) == want


def _rand_scalars(n):
    return [int.from_bytes(rng.bytes(32), "little") % ref.L for _ in range(n)]


@pytest.mark.parametrize("kind", ["prime_order", "torsion", "mixed_order"])
def test_pair_ladder_matches_one_point_and_oracle(kind):
    """Random s and k over random A, over every one of the 8 torsion
    points, and over A with a torsion component."""
    _ladders_agree(_points(kind), _points("mixed_order"),
                   _rand_scalars(LANES), _rand_scalars(LANES))


EDGE_SCALARS = {
    "0": 0, "1": 1, "2^128-1": 2**128 - 1, "2^128": 2**128,
    "L-1": ref.L - 1, "ones": 2**256 - 1,
}


@pytest.mark.parametrize("name", sorted(EDGE_SCALARS))
def test_pair_ladder_on_edge_scalars(name):
    """k and s at the values where the digit carry crosses row 31 -> 32
    (and at both ends): both at the edge, k alone, s alone."""
    e = EDGE_SCALARS[name]
    rnd = _rand_scalars(LANES)
    s_vals = [e, e, e, *rnd[3:6], e, e]
    k_vals = [e, e, e, e, e, e, *rnd[6:]]
    pts = _points("prime_order")[:4] + _points("mixed_order")[:2] + \
        TORSION_8[3:5]
    _ladders_agree(pts, _points("prime_order"), s_vals, k_vals)


@pytest.mark.parametrize("kind", ["prime_order", "torsion", "mixed_order"])
def test_mul_2_128_matches_128_doublings_and_oracle(kind):
    pts = _points(kind)
    jp = _pack_points(pts)
    got = j_mul_2_128(jp)
    want = [ref._ext_to_affine(ref._ext_scalar_mul(1 << 128, p)) for p in pts]
    assert _affine_of(got) == want
    assert _affine_of(j_128_dbls(jp)) == want
    # T of the result is X * Y / Z: the lane table's first step reads it
    X, Y, Z, T = got
    assert bool(np.asarray(F.eq(F.mul(X, Y), F.mul(Z, T))).all())


@pytest.mark.parametrize("e", range(9))
def test_base_niels_hi_is_the_oracles_multiple_of_2_128_b(e):
    got = [F.to_int(np.asarray(C.BASE_NIELS_HI)[e, c]) for c in range(3)]
    if e == 0:
        assert got == [1, 1, 0]
        return
    x, y = ref._ext_to_affine(ref._ext_scalar_mul(e << 128, ref.B_POINT))
    assert got == [(y + x) % P, (y - x) % P, (2 * ref.D * x * y) % P]


def _zip215_lanes():
    """The encodings of test_decompress_zip215_semantics as pubkeys, each
    with a signature R = [r]B, S = r: valid where A decodes to a torsion
    point (the cofactor kills [k]A), refused elsewhere."""
    encs = [
        ref._encode_point(0, 1), (1 + P).to_bytes(32, "little"),
        (1 | 1 << 255).to_bytes(32, "little"), (0).to_bytes(32, "little"),
        P.to_bytes(32, "little"), (P | 1 << 255).to_bytes(32, "little"),
    ] + [y.to_bytes(32, "little") for y in range(2, 6)]
    lanes = []
    for i, a_enc in enumerate(encs):
        r = _rand_scalars(1)[0]
        r_enc = ref._encode_point(
            *ref._ext_to_affine(ref._ext_scalar_mul(r, ref.B_POINT)))
        lanes.append((a_enc, b"zip215 lane %d" % i,
                      r_enc + r.to_bytes(32, "little")))
    return lanes


def _corrupted_commit_lanes():
    """16 honest lanes, 4 of them then broken each in its own way: a
    flipped R bit, a flipped S bit, S + L, another lane's signature."""
    lanes = []
    for i in range(16):
        seed, msg = bytes(rng.bytes(32)), b"vote %d" % i
        lanes.append((ref.pubkey_from_seed(seed), msg, ref.sign(seed, msg)))
    flip = lambda sig, at: sig[:at] + bytes([sig[at] ^ 4]) + sig[at + 1:]
    a, m, sig = lanes[2]
    lanes[2] = (a, m, flip(sig, 5))
    a, m, sig = lanes[7]
    lanes[7] = (a, m, flip(sig, 40))
    a, m, sig = lanes[11]
    s = int.from_bytes(sig[32:], "little") + ref.L
    lanes[11] = (a, m, sig[:32] + s.to_bytes(32, "little"))
    lanes[13] = (lanes[13][0], lanes[13][1], lanes[12][2])
    return lanes


@pytest.mark.parametrize("lanes_fn", [_zip215_lanes, _corrupted_commit_lanes])
def test_cached_pair_gives_the_bitmap_of_the_one_point_program(lanes_fn):
    """End to end on the CPU's value form: verify_batch_cached_a over
    what decompress_pubkeys returns (the pair, 32 windows) against
    verify_batch_prehashed (one point, 64) and the oracle."""
    import hashlib

    lanes = lanes_fn()
    b = 64  # the smallest bucket: the programs other test files compile too
    cols = np.zeros((4, b, 32), np.uint8)  # A, R, S, k
    for i, (a_enc, msg, sig) in enumerate(lanes):
        k = int.from_bytes(
            hashlib.sha512(sig[:32] + a_enc + msg).digest(), "little") % ref.L
        for c, raw in enumerate((a_enc, sig[:32], sig[32:],
                                 k.to_bytes(32, "little"))):
            cols[c, i] = np.frombuffer(raw, np.uint8)
    live = np.arange(b) < len(lanes)
    want = [ref.verify(*lane) for lane in lanes]
    assert any(want) and not all(want)

    a, r, s, k = (jnp.asarray(c) for c in cols)
    bits_one, ok_one = EV.verify_batch_prehashed_jit(a, r, s, k, live)
    ok_a, a_points = EV.decompress_pubkeys_jit(a)
    assert len(a_points) == 2
    bits_pair, ok_pair = EV.verify_batch_cached_a_jit(
        ok_a, a_points, jnp.concatenate([r, s, k], axis=1), live)
    assert list(np.asarray(bits_one)[:len(lanes)]) == want
    assert list(np.asarray(bits_pair)) == list(np.asarray(bits_one))
    assert not bool(ok_one) and not bool(ok_pair)
