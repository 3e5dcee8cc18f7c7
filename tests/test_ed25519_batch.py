"""End-to-end batch verification tests: TPU kernel vs the Python oracle.

force_perlane pins the pallas bitmap kernel (production dispatch would
route these small batches to the native C++ RLC engine and large ones
to the TPU MSM engine - covered in test_dispatch.py).
"""

import numpy as np

from cometbft_tpu.crypto import ed25519_ref as ref
from cometbft_tpu.crypto.ed25519 import (
    Ed25519BatchVerifier,
    Ed25519PrivKey,
    Ed25519PubKey,
)

rng = np.random.default_rng(21)


def _signed(n, msg_len=120):
    out = []
    for i in range(n):
        seed = bytes(rng.bytes(32))
        msg = bytes(rng.bytes(msg_len))
        sig = ref.sign(seed, msg)
        out.append((ref.pubkey_from_seed(seed), msg, sig))
    return out


def test_batch_all_valid():
    items = _signed(20)
    bv = Ed25519BatchVerifier(backend="tpu", force_perlane=True)
    for pub, msg, sig in items:
        assert bv.add(Ed25519PubKey(pub), msg, sig)
    ok, bits = bv.verify()
    assert ok and all(bits) and len(bits) == 20


def test_batch_mixed_validity_bitmap():
    items = _signed(12)
    bv = Ed25519BatchVerifier(backend="tpu", force_perlane=True)
    bad_idx = {1, 5, 11}
    for i, (pub, msg, sig) in enumerate(items):
        if i in bad_idx:
            sig = bytes([sig[0] ^ 1]) + sig[1:]
        bv.add(Ed25519PubKey(pub), msg, sig)
    ok, bits = bv.verify()
    assert not ok
    assert [not b for b in bits] == [i in bad_idx for i in range(12)]


def test_batch_noncanonical_s_rejected_up_front():
    (pub, msg, sig), = _signed(1)
    s = int.from_bytes(sig[32:], "little")
    mal = sig[:32] + (s + ref.L).to_bytes(32, "little")
    bv = Ed25519BatchVerifier(backend="tpu", force_perlane=True)
    assert not bv.add(Ed25519PubKey(pub), msg, mal)
    ok, bits = bv.verify()
    assert not ok and bits == [False]


def _torsion_point():
    for y in range(2, 50):
        aff = ref._decode_point(y.to_bytes(32, "little"), zip215=True)
        if aff is None:
            continue
        t = ref._ext_scalar_mul(ref.L, ref._to_ext(aff))
        if not ref._ext_is_identity(t):
            return t
    raise AssertionError("no torsion point found")


def test_batch_zip215_torsion_and_noncanonical_points():
    """Consensus-critical ZIP-215 edge cases, end to end through the kernel:

    - A or R shifted by an 8-torsion point still verifies (the cofactored
      equation [8]X kills torsion), and kernel == oracle on every lane.
    - Non-canonical encodings (y >= p) of A still verify.
    - Sign-bit flips of canonical points (almost surely) fail both paths.
    """
    import hashlib

    t8 = _torsion_point()

    def torsion_signed(seed_bytes: bytes, msg: bytes, shift_a: bool):
        """Sign so that the *torsion-shifted* encoding of A (or R) verifies:
        valid under the cofactored ZIP-215 equation ([8]t8 == identity),
        invalid under cofactorless verification."""
        a = int.from_bytes(seed_bytes, "little") % ref.L
        r = int.from_bytes(hashlib.sha512(seed_bytes).digest(), "little") % ref.L
        A_pt = ref._ext_scalar_mul(a, ref.B_POINT)
        R_pt = ref._ext_scalar_mul(r, ref.B_POINT)
        if shift_a:
            A_pt = ref._ext_add(A_pt, t8)
        else:
            R_pt = ref._ext_add(R_pt, t8)
        A_enc = ref._encode_point(*ref._ext_to_affine(A_pt))
        R_enc = ref._encode_point(*ref._ext_to_affine(R_pt))
        k = int.from_bytes(hashlib.sha512(R_enc + A_enc + msg).digest(), "little") % ref.L
        s = (r + k * a) % ref.L
        return A_enc, msg, R_enc + s.to_bytes(32, "little")

    cases = []
    for i, (pub, msg, sig) in enumerate(_signed(3)):
        cases.append((pub, msg, sig))
        # pubkey with 8-torsion component: valid only cofactored
        cases.append(torsion_signed(bytes([i]) + msg[:31], b"torsion-A", True))
        # R with 8-torsion component: valid only cofactored
        cases.append(torsion_signed(bytes([i + 64]) + msg[:31], b"torsion-R", False))
        # sign-bit flip of A: invalid
        cases.append((bytes([*pub[:31], pub[31] ^ 0x80]), msg, sig))
    # identity pubkey (a=0): S = r, A encoded canonically (y=1) and
    # non-canonically (y=1+p); both must verify under ZIP-215
    rng2 = np.random.default_rng(3)
    r_seed = bytes(rng2.bytes(32))
    r_scalar = int.from_bytes(r_seed, "little") % ref.L
    r_enc = ref._encode_point(*ref._ext_to_affine(ref._ext_scalar_mul(r_scalar, ref.B_POINT)))
    msg = b"identity-key-msg"
    sig_id = r_enc + r_scalar.to_bytes(32, "little")
    cases.append((ref._encode_point(0, 1), msg, sig_id))
    cases.append(((1 + ref.P).to_bytes(32, "little"), msg, sig_id))

    want = [ref.verify(p, m, s) for p, m, s in cases]
    # the torsion/non-canonical constructions must actually be the
    # interesting (valid) cases, not vacuous failures
    assert want[0] and want[1] and want[2] and not want[3]
    assert want[-2] and want[-1]

    bv = Ed25519BatchVerifier(backend="tpu", force_perlane=True)
    for pub, msg_, sig in cases:
        bv.add(Ed25519PubKey(pub), msg_, sig)
    _, bits = bv.verify()
    assert [bool(b) for b in bits] == want


def test_cpu_backend_matches():
    items = _signed(6)
    bv = Ed25519BatchVerifier(backend="cpu")
    for i, (pub, msg, sig) in enumerate(items):
        if i == 2:
            msg = msg + b"!"
        bv.add(Ed25519PubKey(pub), msg, sig)
    ok, bits = bv.verify()
    assert not ok and bits.count(False) == 1 and not bits[2]


def test_priv_key_roundtrip():
    pk = Ed25519PrivKey.generate()
    msg = b"vote"
    sig = pk.sign(msg)
    assert pk.pub_key().verify_signature(msg, sig)
    assert not pk.pub_key().verify_signature(msg + b"x", sig)
    pk2 = Ed25519PrivKey(pk.bytes())
    assert pk2.pub_key().bytes() == pk.pub_key().bytes()
    assert len(pk.pub_key().address()) == 20


def test_pipelined_submit_and_collect():
    """submit() snapshots per-batch state: reusing/mutating the verifier
    after submit must not corrupt in-flight results, and collect_pending
    fetches many batches with one transfer."""
    from cometbft_tpu.crypto.ed25519 import collect_pending

    items = _signed(5)
    bv = Ed25519BatchVerifier(backend="tpu", force_perlane=True)
    for pub, msg, sig in items[:3]:
        bv.add(Ed25519PubKey(pub), msg, sig)
    p1 = bv.submit()
    # mutate after submit: add an oversize message (host-fallback lane)
    # and a corrupted signature, then submit again
    big = bytes(rng.bytes(500))
    seed = bytes(rng.bytes(32))
    bv.add(Ed25519PubKey(ref.pubkey_from_seed(seed)), big, ref.sign(seed, big))
    pub4, msg4, sig4 = items[3]
    bv.add(Ed25519PubKey(pub4), msg4 + b"!", sig4)
    p2 = bv.submit()
    (ok1, bits1), (ok2, bits2) = collect_pending([p1, p2])
    assert ok1 and bits1 == [True, True, True]
    assert not ok2 and bits2 == [True, True, True, True, False]
    # individual result() agrees with collect_pending
    ok1b, bits1b = p1.result()
    assert (ok1b, bits1b) == (ok1, bits1)


def test_a_cache_hits_on_the_same_column_and_misses_on_another_k(tmp_path):
    """The device-resident pubkey cache is keyed by the WHOLE batch's
    pubkey column: the second launch of one column hits, the same set
    taken k = 2 times over (a merged batch of two requests) is another
    key and misses. crypto.device_launch says which, and so does
    crypto_a_cache_total for untraced nodes."""
    import json

    from cometbft_tpu.crypto import ed25519 as E
    from cometbft_tpu.utils import trace
    from cometbft_tpu.utils.metrics import crypto_metrics

    items = _signed(12)

    def launch(k):
        bv = Ed25519BatchVerifier(backend="tpu", force_perlane=True)
        for pub, msg, sig in items * k:
            bv.add(Ed25519PubKey(pub), msg, sig)
        ok, bits = bv.verify()
        assert ok and len(bits) == 12 * k

    E._A_CACHE.clear()
    sink = str(tmp_path / "a_cache.jsonl")
    trace.configure(sink)
    try:
        for k in (1, 1, 2, 1):
            launch(k)
        trace.flush()
    finally:
        trace.disable()
        E._A_CACHE.clear()
    with open(sink, encoding="utf-8") as f:
        got = [r["a_cache"] for r in map(json.loads, f)
               if r["name"] == "crypto.device_launch"]
    assert got == ["miss", "hit", "miss", "hit"]
    assert dict(crypto_metrics().a_cache_total.values()) == {
        ("hit",): 2.0, ("miss",): 2.0}
