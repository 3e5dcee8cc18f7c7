"""Device-mesh sharding of the signature data plane (SURVEY §2.15/§5.7:
the batch axis is our data-parallel dimension; psum over ICI reduces the
commit-accept bit). Runs on the 8-device virtual CPU mesh (conftest)."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")


def _batch(n):
    import __graft_entry__ as g

    return g._example_batch(n)


def test_sharded_verify_1d_and_2d_agree():
    from jax.sharding import NamedSharding, PartitionSpec as P

    from cometbft_tpu.parallel.mesh import (
        make_mesh,
        make_mesh_2d,
        sharded_verify_rsk_fn,
    )

    cpus = jax.devices("cpu")
    if len(cpus) < 8:
        pytest.skip("needs 8 virtual devices")
    raw = _batch(64)

    mesh = make_mesh(cpus[:8])
    fn = sharded_verify_rsk_fn(mesh)
    args = [jax.device_put(a, NamedSharding(mesh, P("sig"))) for a in raw]
    ok1, bits1 = jax.block_until_ready(fn(*args))

    mesh2 = make_mesh_2d(cpus[:8], hosts=2)
    fn2 = sharded_verify_rsk_fn(mesh2, ("host", "sig"))
    args2 = [
        jax.device_put(a, NamedSharding(mesh2, P(("host", "sig"))))
        for a in raw
    ]
    ok2, bits2 = jax.block_until_ready(fn2(*args2))

    assert bool(ok1) and bool(ok2)
    assert np.asarray(bits1).all() and np.asarray(bits2).all()

    # flip one signature byte: BOTH layouts must reject, and the psum'd
    # verdict must reflect the single bad lane on whichever shard holds it
    bad = [np.array(a, copy=True) for a in raw]
    bad[1][17, 32] ^= 1  # S of lane 17
    argsb = [jax.device_put(a, NamedSharding(mesh, P("sig"))) for a in bad]
    okb, bitsb = jax.block_until_ready(fn(*argsb))
    args2b = [
        jax.device_put(a, NamedSharding(mesh2, P(("host", "sig"))))
        for a in bad
    ]
    ok2b, bits2b = jax.block_until_ready(fn2(*args2b))
    assert not bool(okb) and not bool(ok2b)
    assert not np.asarray(bitsb)[17] and not np.asarray(bits2b)[17]
    assert np.asarray(bitsb).sum() == 63 and np.asarray(bits2b).sum() == 63


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


def _signed_items(n, corrupt=()):
    seeds = [bytes([i % 5 + 1]) * 32 for i in range(4)]
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


def _packed(items, parts, bucket=None):
    """Production packing: Ed25519BatchVerifier rsk pack + mesh padding."""
    from cometbft_tpu.parallel.mesh import pad_to_shards

    bv = E.Ed25519BatchVerifier()
    for pub, msg, sig in items:
        bv.add(E.Ed25519PubKey(pub), msg, sig)
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
