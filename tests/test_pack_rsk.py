"""The ladder's and the mesh's host stage: R||S||k rows, one per lane.

`Ed25519BatchVerifier._pack_rsk_live` fills a (bucket, 96) array through
the native packer (csrc `ed25519_pack_rsk`: 8-way SHA-512 where eight
consecutive lengths agree, k mod L in C) and, where there is no native
library, through a hashlib loop. The device trusts these bytes, so the
two must agree byte for byte, whichever of add() and add_batch() filled
the verifier, and both must agree with k = SHA-512(R||A||M) mod L worked
out here.
"""

import hashlib

import numpy as np
import pytest

from cometbft_tpu.crypto import ed25519 as E
from cometbft_tpu.crypto import ed25519_ref as ref
from cometbft_tpu.crypto import native

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


def _filled(pubs, sigs, msgs, columns: bool):
    bv = E.Ed25519BatchVerifier(backend="tpu")
    if columns:
        bv.add_batch(pubs, sigs, b"".join(msgs),
                     np.asarray([len(m) for m in msgs], np.uint32))
    else:
        for pub, sig, msg in zip(pubs, sigs, msgs):
            bv.add(E.Ed25519PubKey(pub.tobytes()), msg, sig.tobytes())
    return bv


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
        mp.setattr(native, "pack_rsk", lambda *a, **kw: False)
        for columns in (False, True):
            bv = _filled(pubs, sigs, msgs, columns)
            got["hashlib", columns] = bv._pack_rsk_live(n, b)[0]

    want = np.zeros((b, 96), np.uint8)
    want[:n, :64] = sigs
    want[bad, :64] = 0
    for i in range(n):
        pre = want[i, :32].tobytes() + pubs[i].tobytes() + msgs[i]
        k = int.from_bytes(hashlib.sha512(pre).digest(), "little") % L
        want[i, 64:] = _scalar(k)
    for key, rsk in got.items():
        assert rsk.shape == (b, 96) and rsk.dtype == np.uint8
        assert rsk.tobytes() == want.tobytes(), key
