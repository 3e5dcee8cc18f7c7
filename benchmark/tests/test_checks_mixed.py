"""The mixed-curve cell's comparisons (drivers/commit_verify_mixed.py), fed by
hand: a minority lane that was broken and not refused, a verdict by another
curve's rules, blame on a higher index, and the two corrupted copies."""

import numpy as np
import pytest

from benchmark.drivers import commit_verify_mixed as M
from benchmark.reference import secp256k1_ecdsa as ref_k1

MSG = b"vote sign bytes"


def _lanes(curve, n=6):
    from cometbft_tpu.crypto.secp256k1 import Secp256k1PrivKey
    from cometbft_tpu.crypto.sr25519 import Sr25519PrivKey

    rng = np.random.default_rng(11)
    make = (Sr25519PrivKey if curve == "sr25519"
            else Secp256k1PrivKey.from_secret)
    keys = [make(rng.bytes(32)) for _ in range(n)]
    return [(k.pub_key().bytes(), MSG + bytes([i]), k.sign(MSG + bytes([i])))
            for i, k in enumerate(keys)]


def _break(lanes, at, fn):
    pub, msg, sig = lanes[at]
    sig = bytearray(sig)
    fn(sig)
    return lanes[:at] + [(pub, msg, bytes(sig))] + lanes[at + 1:]


@pytest.mark.parametrize("curve,fn", [
    ("sr25519", M._flip(7, 0x01)),
    ("sr25519", M._sr_negative_r),
    ("secp256k1", M._flip(7, 0x01)),
    ("secp256k1", M._secp_upper_s),
])
def test_a_minority_lane_broken_and_not_refused_is_not_correct(curve, fn):
    lanes = _break(_lanes(curve), 2, fn)
    bits = M.program_minority_bits(curve, lanes)
    assert bits == [True, True, False, True, True, True]
    assert all(c.ok for c in M.minority_checks("t", curve, bits, lanes,
                                               {2}, seed=3))
    # a verifier that skipped the lane, or judged it by laxer rules
    lax = [True] * len(lanes)
    failing = [c.name for c in M.minority_checks("t", curve, lax, lanes,
                                                 {2}, seed=3) if not c.ok]
    assert f"t.{curve}.lanes_differing_from_generator" in failing
    assert any("plain_reference" in name for name in failing)
    if curve == "secp256k1":
        assert any("openssl_low_s" in name for name in failing)


def test_a_good_lane_refused_is_not_correct_either():
    lanes = _lanes("sr25519")
    strict = [True, True, True, False, True, True]
    assert not all(c.ok for c in M.minority_checks("t", "sr25519", strict,
                                                   lanes, set(), seed=3))


def test_a_lane_left_unjudged_is_not_correct():
    lanes = _lanes("secp256k1")
    checks = M.minority_checks("t", "secp256k1", [True] * 5, lanes, set(), 3)
    assert [c.ok for c in checks] == [False]


def test_openssl_alone_accepts_the_upper_s_twin_and_the_rule_refuses_it():
    lanes = _break(_lanes("secp256k1"), 0, M._secp_upper_s)
    assert int.from_bytes(lanes[0][2][32:], "big") > ref_k1.N // 2
    assert M.openssl_ecdsa_low_s(lanes) == [False] + [True] * 5
    assert not ref_k1.verify(*lanes[0])


def test_blame_on_a_higher_index_is_not_correct():
    assert M.blame_check("x", "invalid signature at index 17", 17).ok
    assert not M.blame_check("x", "invalid signature at index 170", 17).ok
    assert not M.blame_check("x", "invalid signature at index 5012", 17).ok
    assert not M.blame_check("x", "accepted", 17).ok


class _Sig:
    def __init__(self, signature):
        self.signature = signature


class _Commit:
    def __init__(self, n):
        self.signatures = [_Sig(bytes([i]) * 64) for i in range(n)]

    def invalidate_memos(self):
        pass


def test_the_corrupted_copies_are_what_the_cell_says():
    by_curve = {"ed25519": list(range(0, 48, 2)) + [45, 47],
                "sr25519": [1, 3, 5, 7, 9], "secp256k1": [11, 13, 15]}
    weird = [45, 47]
    commit = _Commit(48)
    bad, why = M.corrupt_three_curves(commit, by_curve, weird, seed=9)
    curves = [w.split(":")[0] for w in why.values()]
    assert sorted(curves) == ["ed25519"] * 4 + ["secp256k1"] * 2 \
        + ["sr25519"] * 2
    for i, w in why.items():
        assert i in by_curve[w.split(":")[0]]
        assert bad.signatures[i].signature != commit.signatures[i].signature
    assert weird[1] in why and weird[0] not in why
    assert all(bad.signatures[i].signature == commit.signatures[i].signature
               for i in range(48) if i not in why)
    # the same seed breaks the same lanes
    assert M.corrupt_three_curves(commit, by_curve, weird, seed=9)[1] == why
    # the second copy: one minority lane, in the upper half of the commit
    # (the highest one where a rehearsal's few all fall in the lower half)
    one, idx = M.corrupt_one_minority_lane(commit, by_curve, seed=9)
    assert idx == 15
    assert [i for i in range(48) if one.signatures[i].signature
            != commit.signatures[i].signature] == [idx]
    by_curve["secp256k1"] = [11, 13, 40]
    _, idx = M.corrupt_one_minority_lane(commit, by_curve, seed=9)
    assert idx == 40


def _curve_after_curve(chain, vals, bid, height, commit):
    """A verify_commit that judges ed25519 first and the other curves after
    it, and blames the first bad lane it meets: every lane by its own rules,
    the blame not the configuration's."""
    from benchmark.harness import check as C
    from cometbft_tpu.types import validation

    lanes = C.commit_lanes(chain, vals, commit)
    curves = [M.curve_of(v.pub_key) for v in vals.validators]
    for curve in ("ed25519",) + M.MINORITY:
        for i, (v, c) in enumerate(zip(vals.validators, curves)):
            if c == curve and not v.pub_key.verify_signature(*lanes[i][1:]):
                raise validation.ErrInvalidSignature(
                    f"invalid signature at index {i}")


class _Ctx:
    seed = 12

    class cell:
        params = {"device_from_lanes": 1024}


def test_the_blame_probe_passes_on_the_program():
    checks = M.blame_probe(seed=12)
    assert len(checks) == 3 and all(c.ok for c in checks)
    # each curve's first lane was the lowest bad lane in turn
    assert len({c.limit for c in checks}) == 3


def test_a_program_that_blames_curve_after_curve_ends_before_any_data(
        monkeypatch):
    from cometbft_tpu.types import validation

    monkeypatch.setattr(validation, "verify_commit", _curve_after_curve)
    checks = M.blame_probe(seed=12)
    assert len(checks) == 3 and not all(c.ok for c in checks)
    driver = M.Driver(_Ctx())
    monkeypatch.setattr(driver, "_build", lambda: pytest.fail("data built"))
    with pytest.raises(SystemExit, match="cannot run this configuration"):
        driver.setup()


def test_the_probe_leaves_a_wrong_verdict_to_the_comparisons(monkeypatch):
    """Under the accept_all control a bad ed25519 lane passes: the probe
    orders the lanes that ARE refused, finds the order kept, and the run
    goes on to its result line (`correct` false there)."""
    from benchmark.harness import faults
    from cometbft_tpu.crypto import ed25519 as E

    for cls in (E.PendingBatch, E.DonePending):
        monkeypatch.setattr(cls, "result", cls.result)  # put back after
    faults.accept_all()
    checks = M.blame_probe(seed=12)
    assert checks and all(c.ok for c in checks)
    assert all("index" in c.limit for c in checks)
