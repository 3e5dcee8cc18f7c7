"""Device-mesh sharding of the signature data plane (SURVEY §2.15/§5.7:
the batch axis is our data-parallel dimension; psum over ICI reduces the
commit-accept bit). Runs on the 8-device virtual CPU mesh (conftest)."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")


def _batch(n):
    import __graft_entry__ as g

    return g._example_batch(n)


def _identity_keys():
    """chip_smoke's two non-canonical encodings of the identity that
    ZIP-215 accepts, and a signature that verifies under either for any
    message: A = identity, so R = [S]B is the whole equation (R = B,
    S = 1)."""
    from chip_smoke import noncanonical_identity_keys

    from cometbft_tpu.crypto import ed25519_ref as ref

    e0, e1 = noncanonical_identity_keys()
    sig = ref._encode_point(ref._Bx, ref._By) + (1).to_bytes(32, "little")
    assert ref.verify(e0, b"any", sig) and ref.verify(e1, b"other", sig)
    return e0, e1, sig


def _invalid_key():
    """A 32-byte string that is no point of the curve under ZIP-215."""
    from cometbft_tpu.crypto import ed25519_ref as ref

    y = 2
    while ref._decode_point(y.to_bytes(32, "little"), zip215=True) is not None:
        y += 1
    return y.to_bytes(32, "little")


def _rsk_row(pub, msg, sig):
    import hashlib

    from cometbft_tpu.crypto import ed25519_ref as ref

    k = int.from_bytes(
        hashlib.sha512(sig[:32] + pub + msg).digest(), "little") % ref.L
    return np.frombuffer(sig + k.to_bytes(32, "little"), np.uint8)


def test_sharded_verify_1d_and_2d_agree():
    from jax.sharding import NamedSharding, PartitionSpec as P

    from cometbft_tpu.ops.ed25519_verify import decompress_pubkeys_jit
    from cometbft_tpu.parallel.mesh import (
        make_mesh,
        make_mesh_2d,
        sharded_decompress_pubkeys_fn,
        sharded_verify_rsk_fn,
    )

    cpus = jax.devices("cpu")
    if len(cpus) < 8:
        pytest.skip("needs 8 virtual devices")
    raw = _batch(64)

    mesh = make_mesh(cpus[:8])
    stage, fn = sharded_decompress_pubkeys_fn(mesh), sharded_verify_rsk_fn(mesh)
    sh1 = NamedSharding(mesh, P("sig"))

    mesh2 = make_mesh_2d(cpus[:8], hosts=2)
    axes2 = ("host", "sig")
    stage2 = sharded_decompress_pubkeys_fn(mesh2, axes2)
    fn2 = sharded_verify_rsk_fn(mesh2, axes2)
    sh2 = NamedSharding(mesh2, P(axes2))

    def run(stage_, fn_, sh, a, rsk, live):
        """The engine's two programs: the column's pair staged on the
        shards, then the verifier against it. -> (staged, ok, bits)."""
        staged = stage_(jax.device_put(a, sh))
        ok, bits = jax.block_until_ready(
            fn_(*staged, *jax.device_put((rsk, live), sh)))
        return staged, bool(ok), np.asarray(bits)

    _, ok1, bits1 = run(stage, fn, sh1, *raw)
    _, ok2, bits2 = run(stage2, fn2, sh2, *raw)

    assert bool(ok1) and bool(ok2)
    assert np.asarray(bits1).all() and np.asarray(bits2).all()

    # flip one signature byte: BOTH layouts must reject, and the psum'd
    # verdict must reflect the single bad lane on whichever shard holds it
    bad = [np.array(a, copy=True) for a in raw]
    bad[1][17, 32] ^= 1  # S of lane 17
    _, okb, bitsb = run(stage, fn, sh1, *bad)
    _, ok2b, bits2b = run(stage2, fn2, sh2, *bad)
    assert not bool(okb) and not bool(ok2b)
    assert not np.asarray(bitsb)[17] and not np.asarray(bits2b)[17]
    assert np.asarray(bitsb).sum() == 63 and np.asarray(bits2b).sum() == 63

    # what the shards keep is what the single chip keeps, leaf for leaf,
    # on either layout: a column with two non-canonical ZIP-215 keys
    # (lane 9 with a signature that verifies, lane 10 with another
    # key's) and a key that is no point (lane 23: ok_a false)
    e0, e1, sig = _identity_keys()
    odd = [np.array(a, copy=True) for a in raw]
    odd[0][9] = np.frombuffer(e0, np.uint8)
    odd[1][9] = _rsk_row(e0, b"lane 9", sig)
    odd[0][10] = np.frombuffer(e1, np.uint8)
    odd[0][23] = np.frombuffer(_invalid_key(), np.uint8)
    one = decompress_pubkeys_jit(odd[0])
    flat_one, tree_one = jax.tree_util.tree_flatten(one)
    assert not np.asarray(one[0])[23] and np.asarray(one[0]).sum() == 63
    for stage_, fn_, sh in ((stage, fn, sh1), (stage2, fn2, sh2)):
        staged, ok, bits = run(stage_, fn_, sh, *odd)
        flat, tree = jax.tree_util.tree_flatten(staged)
        assert tree == tree_one and len(flat) == 9  # ok_a + 2 points x 4
        for got, want in zip(flat, flat_one):
            assert got.shape == want.shape and got.dtype == want.dtype
            assert (np.asarray(got) == np.asarray(want)).all()
            # left where it was computed: lanes (the last axis) sharded
            assert got.sharding.is_equivalent_to(
                NamedSharding(sh.mesh, P(*[None] * (got.ndim - 1),
                                         sh.spec[0])), got.ndim)
        assert not ok
        assert [i for i in range(64) if not bits[i]] == [10, 23]


def test_mesh_2d_shape_validation():
    from cometbft_tpu.parallel.mesh import make_mesh_2d

    cpus = jax.devices("cpu")
    if len(cpus) < 8:
        pytest.skip("needs 8 virtual devices")
    with pytest.raises(ValueError):
        make_mesh_2d(cpus[:7], hosts=2)


# ---------------------------------------------------------------------------
# MeshVerifyEngine: the production sharded path (PR 7). These run on the
# 8-device virtual CPU mesh and double as the tier-1 dryrun smoke for
# mesh regressions — no TPU hardware involved.

from cometbft_tpu.crypto import ed25519 as E
from cometbft_tpu.crypto import ed25519_ref as ref


@pytest.fixture(scope="module")
def eng8():
    from cometbft_tpu.parallel.mesh import MeshVerifyEngine

    cpus = jax.devices("cpu")
    if len(cpus) < 8:
        pytest.skip("needs 8 virtual devices")
    return MeshVerifyEngine(cpus[:8])


def _signed_items(n, corrupt=(), keys=0):
    """n signed lanes over four keys; `keys` picks another four (another
    validator column)."""
    seeds = [bytes([(i + 4 * keys) % 251 + 1]) * 32 for i in range(4)]
    out = []
    for i in range(n):
        seed = seeds[i % 4]
        pub = ref.pubkey_from_seed(seed)
        msg = b"mesh-lane-%04d" % i
        sig = ref.sign(seed, msg)
        if i in corrupt:
            sig = bytes([sig[0] ^ 1]) + sig[1:]  # broken R, canonical S
        out.append((pub, msg, sig))
    return out


def _verifier(items):
    bv = E.Ed25519BatchVerifier()
    for pub, msg, sig in items:
        bv.add(E.Ed25519PubKey(pub), msg, sig)
    return bv


def _packed(items, parts, bucket=None):
    """Production packing: Ed25519BatchVerifier rsk pack + mesh padding."""
    from cometbft_tpu.parallel.mesh import pad_to_shards

    bv = _verifier(items)
    n = bv.count()
    b = pad_to_shards(n, parts, bucket=bucket)
    rsk, live, pub_blob = bv._pack_rsk_live(n, b)
    a_bytes = np.zeros((b, 32), np.uint8)
    a_bytes[:n] = np.frombuffer(bytes(pub_blob), np.uint8).reshape(n, 32)
    return a_bytes, rsk, live


def _single_chip_bits(a_bytes, rsk, live):
    from cometbft_tpu.ops.ed25519_verify import verify_batch_prehashed_jit

    bits, all_ok = verify_batch_prehashed_jit(
        a_bytes, rsk[:, :32], rsk[:, 32:64], rsk[:, 64:], live
    )
    return np.asarray(bits), bool(all_ok)


def test_pad_to_shards_edges():
    from cometbft_tpu.parallel.mesh import pad_to_shards

    assert pad_to_shards(5, 8) == 8        # B < n_devices
    assert pad_to_shards(97, 8) == 104     # prime B
    assert pad_to_shards(0, 8) == 8        # empty batch keeps the shape
    assert pad_to_shards(8, 8) == 8        # already divisible
    assert pad_to_shards(7, 3) == 9
    assert pad_to_shards(100, 8, bucket=256) == 256  # bucket discipline


def test_sharded_matches_single_chip_reject(eng8):
    """Acceptance bar: identical accept/reject bitmaps, sharded vs
    single chip, on a padded (non-divisible) batch with bad lanes on
    different shards — including the final lane."""
    items = _signed_items(13, corrupt={5, 12})
    a_bytes, rsk, live = _packed(items, eng8.n_devices)
    assert a_bytes.shape[0] == 16  # 13 padded over 8 devices
    all_ok, bits = eng8.submit(a_bytes, rsk, live)
    bits_mesh = np.asarray(bits)
    bits_one, ok_one = _single_chip_bits(a_bytes, rsk, live)
    assert not bool(np.asarray(all_ok)) and not ok_one
    assert (bits_mesh == bits_one).all(), "bitmaps must be bit-exact"
    assert [i for i in range(13) if not bits_mesh[i]] == [5, 12]
    assert not bits_mesh[13:].any()  # padded lanes stay dead


def test_sharded_matches_single_chip_accept(eng8):
    items = _signed_items(13)
    a_bytes, rsk, live = _packed(items, eng8.n_devices)
    all_ok, bits = eng8.submit(a_bytes, rsk, live)
    bits_one, ok_one = _single_chip_bits(a_bytes, rsk, live)
    assert bool(np.asarray(all_ok)) and ok_one
    assert (np.asarray(bits) == bits_one).all()
    assert np.asarray(bits)[:13].all()


@pytest.mark.slow  # each distinct lanes-per-shard count is a fresh
# ~60 s XLA CPU compile; the 13→16 padded pair above covers the padding
# invariant in tier-1, this adds the odd-lane-count shape
def test_sharded_prime_batch(eng8):
    """B=97 (prime): pads to 104 = 13 lanes/device; verdict and bitmap
    must agree with the single-chip kernel on the same padded arrays."""
    items = _signed_items(97, corrupt={96})
    a_bytes, rsk, live = _packed(items, eng8.n_devices)
    assert a_bytes.shape[0] == 104
    all_ok, bits = eng8.submit(a_bytes, rsk, live)
    bits_one, ok_one = _single_chip_bits(a_bytes, rsk, live)
    assert not bool(np.asarray(all_ok)) and not ok_one
    assert (np.asarray(bits) == bits_one).all()
    assert not np.asarray(bits)[96]


@pytest.mark.slow  # fresh shard-shape compile, see above
def test_all_dead_shard(eng8):
    """Shards whose every lane is padding (live=False) must not poison
    the psum: batch of 5 over 8 devices leaves 3 devices all-dead."""
    items = _signed_items(5)
    a_bytes, rsk, live = _packed(items, eng8.n_devices)
    assert a_bytes.shape[0] == 8 and live.sum() == 5
    all_ok, bits = eng8.submit(a_bytes, rsk, live)
    assert bool(np.asarray(all_ok))
    assert np.asarray(bits)[:5].all() and not np.asarray(bits)[5:].any()


def test_submit_rejects_nondivisible(eng8):
    a = np.zeros((10, 32), np.uint8)
    with pytest.raises(ValueError, match="pad_to_shards"):
        eng8.submit(a, np.zeros((10, 96), np.uint8), np.zeros(10, bool))


@pytest.fixture
def shard_shape_16(monkeypatch):
    """_launch_mesh pads to the production buckets (64 lanes for 13);
    without them 13 lanes pad to the 16 this file's other engine tests
    compile: one shard shape, 2 lanes a device."""
    monkeypatch.setattr(E, "_bucket", lambda n: n)


def test_invalid_and_noncanonical_keys_match_single_chip(
        eng8, shard_shape_16):
    """ok_a rides with the staged column: a key that is no point reads
    false in the mesh's bitmap as in the single chip's, a non-canonical
    ZIP-215 key with a good signature true, with another key's false."""
    e0, e1, sig = _identity_keys()
    items = _signed_items(13)
    items[4] = (_invalid_key(),) + items[4][1:]
    items[7] = (e0, b"under the identity", sig)
    items[8] = (e1,) + items[8][1:]
    a_bytes, rsk, live = _packed(items, eng8.n_devices)
    all_ok, bits = eng8.submit(a_bytes, rsk, live)
    bits_mesh = np.asarray(bits)
    bits_one, ok_one = _single_chip_bits(a_bytes, rsk, live)
    assert not bool(np.asarray(all_ok)) and not ok_one
    assert (bits_mesh == bits_one).all(), "bitmaps must be bit-exact"
    assert [i for i in range(13) if not bits_mesh[i]] == [4, 8]
    assert bits_mesh[7] and not bits_mesh[13:].any()
    # the engine's verdict through the production launch, blame included
    ok, blame = _verifier(items)._launch_mesh(eng8).result()
    assert not ok and [i for i, x in enumerate(blame) if not x] == [4, 8]


def test_staged_column_hit_miss_and_eviction(
        eng8, shard_shape_16, monkeypatch, tmp_path):
    """The engine keeps a column's decompressed pair on its shards: the
    second launch of a column is a hit (the counter, the span; the
    staging program is not called and no (b, 32) array is built), another
    column is a miss, and the fifth column evicts the first."""
    import json

    from cometbft_tpu.parallel import mesh as M
    from cometbft_tpu.utils import trace
    from cometbft_tpu.utils.metrics import crypto_metrics

    staged, built = [], []
    stage, column = eng8._stage, eng8._column
    monkeypatch.setattr(
        eng8, "_stage", lambda a: staged.append(a.shape) or stage(a))
    monkeypatch.setattr(
        eng8, "_column",
        lambda pubkeys, b: built.append(b) or column(pubkeys, b))
    monkeypatch.setattr(eng8, "_a_cache", {})
    counter = crypto_metrics().a_cache_total

    def launch(keys):
        before = dict(counter.values())
        ok, bits = _verifier(_signed_items(13, keys=keys))._launch_mesh(
            eng8).result()
        assert ok and all(bits)
        after = counter.values()
        moved = {k[0]: after[k] - before.get(k, 0.0) for k in after
                 if after[k] != before.get(k, 0.0)}
        return moved, len(staged), len(built)

    sink = str(tmp_path / "mesh_a_cache.jsonl")
    trace.configure(sink)
    try:
        assert launch(0) == ({"miss": 1.0}, 1, 1)
        assert staged == [(16, 32)] and built == [16]
        assert launch(0) == ({"hit": 1.0}, 1, 1)   # nothing of A is built
        assert launch(1) == ({"miss": 1.0}, 2, 2)  # another column
        assert launch(0) == ({"hit": 1.0}, 2, 2)   # both are kept
        first = next(iter(eng8._a_cache))
        for keys in (2, 3):
            assert launch(keys)[0] == {"miss": 1.0}
        assert len(eng8._a_cache) == M._A_CACHE_SIZE == 4
        assert first in eng8._a_cache
        assert launch(4)[0] == {"miss": 1.0}       # the fifth column
        assert len(eng8._a_cache) == 4 and first not in eng8._a_cache
        assert launch(0) == ({"miss": 1.0}, 6, 6)  # staged afresh
        trace.flush()
    finally:
        trace.disable()
    with open(sink, encoding="utf-8") as f:
        spans = [r for r in map(json.loads, f)
                 if r["name"] == "crypto.mesh_submit"]
    assert [r["a_cache"] for r in spans] == [
        "miss", "hit", "miss", "hit", "miss", "miss", "miss", "miss"]
    # a miss says the column's bytes; a hit ships nothing of A
    assert all((r.get("bytes") == 16 * 32) == (r["a_cache"] == "miss")
               for r in spans)


def test_next_device_round_robin(eng8):
    from cometbft_tpu.utils.metrics import crypto_metrics

    seen = [eng8.next_device() for _ in range(2 * eng8.n_devices)]
    assert seen[: eng8.n_devices] == seen[eng8.n_devices:]
    assert len(set(map(str, seen[: eng8.n_devices]))) == eng8.n_devices
    counts = crypto_metrics().mesh_batches_total.values()
    streamed = {k: v for k, v in counts.items() if k[1] == "stream"}
    assert len(streamed) == eng8.n_devices
    assert all(v == 2.0 for v in streamed.values())


def test_dispatch_terms_calibrated(eng8):
    terms = eng8.dispatch_terms()
    assert terms["put_fixed_s"] > 0 and terms["collective_s"] > 0
    eng8.set_collective_s(1e-4)
    assert eng8.dispatch_terms()["collective_s"] == pytest.approx(1e-4)


def test_get_engine_policy(monkeypatch):
    from cometbft_tpu.parallel import mesh as M

    try:
        monkeypatch.setenv("COMETBFT_TPU_MESH", "0")
        M.reset_engine()
        assert M.get_engine(accel_backed=True) is None
        monkeypatch.delenv("COMETBFT_TPU_MESH")
        M.reset_engine()
        # auto: CPU-only jax keeps the mesh off (native engine wins)
        assert M.get_engine(accel_backed=False) is None
        monkeypatch.setenv("COMETBFT_TPU_MESH", "on")
        M.reset_engine()
        eng = M.get_engine(accel_backed=False)
        assert eng is not None and eng.n_devices == len(jax.devices())
        monkeypatch.setenv("COMETBFT_TPU_MESH", "2")
        M.reset_engine()
        eng = M.get_engine(accel_backed=False)
        assert eng is not None and eng.n_devices == 2
    finally:
        M.reset_engine()  # never leak a cached engine into other tests
