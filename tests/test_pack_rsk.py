"""The ladder's and the mesh's host stage: R||S||k rows, one per lane.

`Ed25519BatchVerifier._pack_rsk_live` fills a (bucket, 96) array through
the native packer (csrc `ed25519_pack_rsk`: 8-way SHA-512 where eight
consecutive lengths agree, k mod L in C) and, where there is no native
library, through a hashlib loop. The device trusts these bytes, so the
two must agree byte for byte, whichever of add() and add_batch() filled
the verifier, and both must agree with k = SHA-512(R||A||M) mod L worked
out here.

The native packer splits its lanes into chunks over the C++ worker pool
when the pool is free and packs on the calling thread when it is not:
the rows must be the same for every chunk count, from any number of
threads at once, and a pool that another engine holds must never make
the packer wait.
"""

import hashlib
import os
import threading
import time

import numpy as np
import pytest

from cometbft_tpu.crypto import ed25519 as E
from cometbft_tpu.crypto import ed25519_ref as ref
from cometbft_tpu.crypto import native
from cometbft_tpu.utils.metrics import crypto_metrics

pytestmark = pytest.mark.skipif(
    not native.available(), reason="no C++ toolchain")


def _scalar(v: int) -> np.ndarray:
    return np.frombuffer(v.to_bytes(32, "little"), np.uint8)


def _lanes(n, lens, seed, s_values=()):
    """n lanes of random R, A and messages with S < L; `lens` is one
    length for all or one a lane; `s_values` {lane: S} overrides S."""
    rng = np.random.default_rng(seed)
    pubs = rng.integers(0, 256, (n, 32), np.uint8)
    sigs = rng.integers(0, 256, (n, 64), np.uint8)
    sigs[:, 63] &= 0x0F  # S < 2^252 < L
    for lane, s in dict(s_values).items():
        sigs[lane, 32:] = _scalar(s)
    lens = np.broadcast_to(np.asarray(lens, np.uint32), (n,))
    msgs = [rng.bytes(int(ln)) for ln in lens]
    return pubs, sigs, msgs


def _ragged(n, seed):
    """Runs of equal lengths of every size from 1 to 9 (the 8-way
    grouping's boundaries), lengths from empty to three SHA-512 blocks."""
    rng = np.random.default_rng(seed)
    out = []
    while len(out) < n:
        out += [int(rng.choice([0, 1, 47, 48, 100, 111, 112, 175, 300]))] * (
            int(rng.integers(1, 10)))
    return out[:n]


L = ref.L
CASES = {
    # every bucket the host engine's limit leaves to the device, full
    "bucket-64": lambda: _lanes(64, 100, 1),
    "bucket-256": lambda: _lanes(256, 100, 2),
    "bucket-1024": lambda: _lanes(1024, 100, 3),
    "bucket-4096": lambda: _lanes(4096, 100, 4),
    # n not a multiple of 8, and a bucket left part empty
    "n-13-of-64": lambda: _lanes(13, 100, 5),
    "n-1001-of-1024": lambda: _lanes(1001, 118, 6),
    "ragged-lengths": lambda: _lanes(203, _ragged(203, 7), 7),
    "empty-messages": lambda: _lanes(40, 0, 8),
    "messages-of-three-blocks": lambda: _lanes(17, 300, 9),
    "edge-scalars": lambda: _lanes(
        24, 100, 10, {0: 0, 1: 1, 7: L - 1, 8: L - 1, 23: 0}),
    # S >= L: the precheck zeroes the lane's R||S and flags it
    "precheck-failed-lanes": lambda: _lanes(
        24, 100, 11, {0: L, 5: L + 1, 8: 2**256 - 1, 23: 2**255}),
}
S_TOO_BIG = {"precheck-failed-lanes": [0, 5, 8, 23]}
# the sizes at which the packer splits on its own (the benchmark's two
# buckets), and ragged lengths long enough for every chunk start to fall
# inside a run of equal lengths; the native packer only (chunked below)
BIG_CASES = {
    "ragged-2003-runs-straddle-chunks": lambda: _lanes(
        2003, _ragged(2003, 12), 12),
    "mega-commit-10000": lambda: _lanes(10000, 118, 13),
    "catch-up-window-65000": lambda: _lanes(65000, 118, 14),
}
CHUNKS = (1, 2, 3, 8)


def _filled(pubs, sigs, msgs, columns: bool):
    bv = E.Ed25519BatchVerifier(backend="tpu")
    if columns:
        bv.add_batch(pubs, sigs, b"".join(msgs),
                     np.asarray([len(m) for m in msgs], np.uint32))
    else:
        for pub, sig, msg in zip(pubs, sigs, msgs):
            bv.add(E.Ed25519PubKey(pub.tobytes()), msg, sig.tobytes())
    return bv


def _want(pubs, sigs, msgs, b, bad=()):
    """The rows worked out here: R||S as given (zeroed where the
    precheck failed), k = SHA-512(R||A||M) mod L by hashlib."""
    n = len(msgs)
    want = np.zeros((b, 96), np.uint8)
    want[:n, :64] = sigs
    want[list(bad), :64] = 0
    for i in range(n):
        pre = want[i, :32].tobytes() + pubs[i].tobytes() + msgs[i]
        k = int.from_bytes(hashlib.sha512(pre).digest(), "little") % L
        want[i, 64:] = _scalar(k)
    return want


@pytest.mark.parametrize("case", sorted(CASES))
def test_native_packer_matches_the_hashlib_fallback(monkeypatch, case):
    pubs, sigs, msgs = CASES[case]()
    n = len(msgs)
    b = E._bucket(n)
    bad = S_TOO_BIG.get(case, [])

    got = {}
    for columns in (False, True):
        bv = _filled(pubs, sigs, msgs, columns)
        assert [i for i, f in enumerate(bv._precheck_fail) if f] == bad
        rsk, live, pub_blob = bv._pack_rsk_live(n, b)
        assert bytes(pub_blob) == pubs.tobytes()
        assert live[:n].all() and not live[n:].any()
        got["native", columns] = rsk
    # the same verifier with no native packer: hashlib, lane by lane
    with monkeypatch.context() as mp:
        mp.setattr(native, "pack_rsk", lambda *a, **kw: None)
        for columns in (False, True):
            bv = _filled(pubs, sigs, msgs, columns)
            got["hashlib", columns] = bv._pack_rsk_live(n, b)[0]

    want = _want(pubs, sigs, msgs, b, bad)
    for key, rsk in got.items():
        assert rsk.shape == (b, 96) and rsk.dtype == np.uint8
        assert rsk.tobytes() == want.tobytes(), key


def _pack(bv, nchunks=0):
    """The native packer on a filled verifier's own buffers, as
    _pack_rsk_live calls it: (chunks it reports, rows)."""
    n = bv.count()
    rsk = np.zeros((E._bucket(n), 96), np.uint8)
    ran = native.pack_rsk(n, bv._sig_buf, bv._pub_buf, bv._msg_buf,
                          np.asarray(bv._msg_lens, np.uint64), rsk, nchunks)
    return ran, rsk


@pytest.fixture(scope="module")
def packed_once():
    """Each case's lanes, hashlib rows and one-chunk rows, made once for
    the chunk counts that share them."""
    cache = {}

    def get(case):
        if case not in cache:
            pubs, sigs, msgs = {**CASES, **BIG_CASES}[case]()
            bv = _filled(pubs, sigs, msgs, columns=True)
            want = _want(pubs, sigs, msgs, E._bucket(len(msgs)),
                         S_TOO_BIG.get(case, []))
            ran, one = _pack(bv, 1)
            assert ran == 1 and one.tobytes() == want.tobytes()
            cache[case] = bv, [len(m) for m in msgs], one.tobytes()
        return cache[case]

    return get


@pytest.mark.parametrize("nchunks", CHUNKS)
@pytest.mark.parametrize("case", sorted({**CASES, **BIG_CASES}))
def test_rows_are_the_same_for_every_chunk_count(packed_once, case, nchunks):
    """Byte for byte the one-chunk rows (which are hashlib's): lane
    counts that are no multiple of 8, fewer lanes than chunks (empty
    chunks), runs of equal lengths cut by a chunk start."""
    bv, lens, one = packed_once(case)
    n = len(lens)
    starts = [(n * c // nchunks) & ~7 for c in range(1, nchunks)]
    if case.startswith("ragged-2003") and nchunks > 1:
        assert any(lens[s - 1] == lens[s] for s in starts), starts
    if case == "n-13-of-64" and nchunks == 8:
        assert len(set(starts)) < len(starts)  # some chunks are empty
    ran, rsk = _pack(bv, nchunks)
    assert ran == nchunks
    assert rsk.tobytes() == one


@pytest.mark.parametrize("case", sorted(BIG_CASES))
def test_chunk_count_follows_the_lane_count(packed_once, case):
    """Left to itself the packer takes one chunk a 1,024 lanes, the
    pool's width at most; the rows are the one-chunk rows."""
    bv, lens, one = packed_once(case)
    ran, rsk = _pack(bv)
    assert ran == max(1, min(native.rs_threads(), len(lens) // 1024))
    assert rsk.tobytes() == one


@pytest.mark.parametrize("n", [64, 256, 1024, 2047])
def test_small_batches_never_wake_the_pool(n):
    """Under two chunks' worth of lanes (the calibration probe's 1,024
    too) the calling thread packs, and says so."""
    bv = _filled(*_lanes(n, 100, n), columns=True)
    assert _pack(bv)[0] == 1
    before = dict(crypto_metrics().pack_total.values())
    bv._pack_rsk_live(n, E._bucket(n))
    after = crypto_metrics().pack_total.values()
    assert {k: v - before.get(k, 0.0) for k, v in after.items()
            if v != before.get(k, 0.0)} == {("small",): 1.0}


def test_four_threads_packing_at_once():
    """One of them gets the pool's slot, the others pack on their own
    threads; nobody blocks and every row is right."""
    cases = [_lanes(4096 + 8 * t, 100 + t, 20 + t) for t in range(4)]
    filled = [_filled(*c, columns=True) for c in cases]
    wants = [_want(*c, E._bucket(len(c[2]))).tobytes() for c in cases]
    wrong, seen = [], set()
    start = threading.Barrier(4)

    def work(t):
        start.wait()
        for _ in range(12):
            ran, rsk = _pack(filled[t])
            seen.add(ran)
            if rsk.tobytes() != wants[t]:
                wrong.append(t)

    threads = [threading.Thread(target=work, args=(t,)) for t in range(4)]
    for th in threads:
        th.start()
    for th in threads:
        th.join(timeout=120)
    assert not any(th.is_alive() for th in threads)
    assert not wrong
    assert seen <= {0, min(native.rs_threads(), 4)}


@pytest.mark.skipif((os.cpu_count() or 1) < 2,
                    reason="the pool spawns no worker on one core")
def test_packer_gives_way_to_a_pool_that_is_taken():
    """A long secp256k1 batch on another thread holds the pool's one
    slot: the packer does not queue behind it, packs on its own thread
    (pool = busy) and returns right rows while that batch still runs."""
    from cometbft_tpu.crypto import secp256k1 as K

    if not native.secp256k1_available():
        pytest.skip("no native secp256k1 engine")
    sk = K.Secp256k1PrivKey.from_secret(b"\x07" * 32)
    msg = b"holds the pool"
    long_batch = [(sk.pub_key().bytes(), msg, sk.sign(msg))] * 6000
    lanes = _lanes(4096, 100, 30)
    bv = _filled(*lanes, columns=True)
    want = _want(*lanes, 4096).tobytes()

    held = []
    holder = threading.Thread(
        target=lambda: held.append(native.secp256k1_multi_verify(long_batch)))
    busy = ("busy",)
    holder.start()
    gave_way = False
    while holder.is_alive() and not gave_way:
        before = crypto_metrics().pack_total.values().get(busy, 0.0)
        rsk, _live, _pubs = bv._pack_rsk_live(4096, 4096)
        assert rsk.tobytes() == want
        gave_way = (holder.is_alive() and
                    crypto_metrics().pack_total.values().get(busy, 0.0)
                    == before + 1.0)
        time.sleep(0.001)
    holder.join(timeout=120)
    assert gave_way
    assert held and all(held[0])
