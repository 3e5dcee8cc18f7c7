"""Native GF(2^16) Reed-Solomon (csrc/rs_gf16.inc) vs the numpy oracle
(da/rs.py): both engines implement the same evaluation-form code with
the same first-k-present survivor rule, so encode AND reconstruct must
be byte-identical for every shard geometry, payload shape, and erasure
pattern up to the parity budget. The native codec is also checked for
chunk-count independence (the determinism contract the worker pool
must honor) and for rejecting bad parameters at the C boundary.
"""

import numpy as np
import pytest

from cometbft_tpu.crypto import native
from cometbft_tpu.da import rs

pytestmark = pytest.mark.skipif(
    not native.rs_available(), reason="no native RS codec"
)

rng = np.random.default_rng(23)


def _shards(k, nbytes):
    return [rng.bytes(nbytes) for _ in range(k)]


def _native_encode(data_shards, m, nchunks=0):
    k = len(data_shards)
    out = native.rs_encode(
        b"".join(data_shards), k, m, len(data_shards[0]), nchunks=nchunks
    )
    assert out is not None
    sl = len(data_shards[0])
    return [out[i * sl : (i + 1) * sl] for i in range(m)]


def _native_reconstruct(shards, k, m, nchunks=0):
    sl = max(len(s) for s in shards if s is not None)
    blob = b"".join(s if s is not None else b"\x00" * sl for s in shards)
    present = bytes(0 if s is None else 1 for s in shards)
    out = native.rs_reconstruct(blob, present, k, m, sl, nchunks=nchunks)
    assert out is not None
    return [out[i * sl : (i + 1) * sl] for i in range(k + m)]


def _erase(extended, erased):
    return [None if i in erased else s for i, s in enumerate(extended)]


# word counts around the chunk-split and table boundaries: 1 word, 2,
# odd, powers of two +-1
EDGE_NBYTES = [2, 4, 6, 14, 16, 18, 62, 64, 66, 254, 256, 258]


def test_encode_differential_edge_sizes():
    for nbytes in EDGE_NBYTES:
        for k, m in [(1, 1), (2, 1), (3, 2), (5, 3), (16, 16)]:
            data = _shards(k, nbytes)
            assert _native_encode(data, m) == rs.encode_oracle(data, m), (
                nbytes, k, m,
            )


def test_reconstruct_differential_random_erasures():
    for trial in range(20):
        k = int(rng.integers(1, 20))
        m = int(rng.integers(1, 20))
        nbytes = 2 * int(rng.integers(1, 120))
        data = _shards(k, nbytes)
        parity = rs.encode_oracle(data, m)
        extended = data + parity
        n_erase = int(rng.integers(0, m + 1))
        erased = set(
            rng.choice(k + m, size=n_erase, replace=False).tolist()
        )
        got_n = _native_reconstruct(_erase(extended, erased), k, m)
        got_o = rs.reconstruct_oracle(_erase(extended, erased), k, m)
        assert got_n == got_o == extended, (trial, k, m, sorted(erased))


def test_reconstruct_from_parity_only():
    # every data shard erased: survivors are all parity evaluations
    k = m = 8
    data = _shards(k, 32)
    extended = data + rs.encode_oracle(data, m)
    shards = _erase(extended, set(range(k)))
    assert _native_reconstruct(shards, k, m) == extended
    assert rs.reconstruct_oracle(shards, k, m) == extended


def test_chunk_count_determinism():
    k, m, nbytes = 8, 8, 1000
    data = _shards(k, nbytes)
    ref_p = _native_encode(data, m, nchunks=1)
    extended = data + ref_p
    erased = {0, 3, 9, 14}
    ref_r = _native_reconstruct(_erase(extended, erased), k, m, nchunks=1)
    for nchunks in (2, 3, 7):
        assert _native_encode(data, m, nchunks=nchunks) == ref_p, nchunks
        assert (
            _native_reconstruct(_erase(extended, erased), k, m,
                                nchunks=nchunks)
            == ref_r
        ), nchunks


def test_dispatch_uses_native_and_matches_oracle():
    # the public entry points route through the native codec when
    # present; pin the oracle to a poisoned stub to prove routing, then
    # compare a fresh call against the real oracle
    k, m = 6, 4
    data = _shards(k, 40)
    orig = rs.encode_oracle
    rs.encode_oracle = lambda *a, **kw: pytest.fail("oracle called")
    try:
        parity = rs.encode_shards(data, m)
    finally:
        rs.encode_oracle = orig
    assert parity == rs.encode_oracle(data, m)
    ext = data + parity
    holes = ext.copy()
    holes[1] = holes[7] = None
    assert rs.reconstruct_shards(holes, k, m) == ext


def test_native_rejects_bad_params():
    blob = b"\x00" * 8
    # k == 0
    assert native.rs_encode(b"", 0, 1, 2) is None
    # odd / zero shard length
    assert native.rs_encode(blob, 4, 1, 0) is None
    assert native.rs_encode(b"\x00" * 12, 4, 1, 3) is None
    # k + m over the shard-count ceiling
    assert native.rs_encode(b"\x00" * 2 * 4000, 4000, 200, 2) is None


def test_native_insufficient_shards_returns_none():
    k = m = 4
    sl = 16
    blob = b"\x00" * ((k + m) * sl)
    present = bytes([1, 1, 1, 0, 0, 0, 0, 0])  # 3 < k survivors
    assert native.rs_reconstruct(blob, present, k, m, sl) is None


def test_reconstruct_shards_raises_beyond_budget():
    k = m = 4
    data = _shards(k, 16)
    ext = rs.encode_shards(data, m)
    holes = _erase(ext, set(range(m + 1)))  # m+1 erasures
    with pytest.raises(rs.RSError):
        rs.reconstruct_shards(holes, k, m)


def test_threads_reported():
    assert native.rs_threads() >= 1


def test_worker_pool_survives_concurrent_callers():
    """ctypes drops the GIL, so two Python threads reach the engine's one
    worker pool together (a core committing while a replica re-encodes).
    Before Pool::run serialized its callers the second reset the first's
    counters and both waited forever — tests/test_replication.py hung."""
    import threading

    from cometbft_tpu.crypto import native

    if not native.available():
        pytest.skip("native engine unavailable")
    k, m, shard_len = 16, 16, 4096
    blob = bytes(range(256)) * (k * shard_len // 256)
    want = native.rs_encode(blob, k, m, shard_len, nchunks=8)
    assert want is not None
    bad = []

    def hammer():
        for _ in range(400):
            if native.rs_encode(blob, k, m, shard_len, nchunks=8) != want:
                bad.append(1)

    threads = [threading.Thread(target=hammer, daemon=True)
               for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not any(t.is_alive() for t in threads), "worker pool deadlocked"
    assert not bad
