"""The mixed-curve commit (ed25519 + sr25519 + secp256k1 validators) through
types/validation.verify_commit, against the plain per-lane judgement of the
benchmark's three references and a power tally; the order in which the legs
are launched and awaited; and the two new references against their published
vectors and against the program's host engines."""

import copy
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.reference import ed25519_zip215 as ref_ed  # noqa: E402
from benchmark.reference import secp256k1_ecdsa as ref_k1  # noqa: E402
from benchmark.reference import sr25519_schnorrkel as ref_sr  # noqa: E402
from cometbft_tpu.crypto import secp256k1 as K1  # noqa: E402
from cometbft_tpu.crypto import sr25519 as SR  # noqa: E402
from cometbft_tpu.crypto.ed25519 import Ed25519PrivKey  # noqa: E402
from cometbft_tpu.types import (  # noqa: E402
    BlockID,
    BlockIDFlag,
    Commit,
    CommitSig,
    PartSetHeader,
    Timestamp,
    validation,
)
from cometbft_tpu.types.validator_set import Validator, ValidatorSet  # noqa: E402

CHAIN = "mixed-chain"
HEIGHT = 7
REFS = {"ed25519": ref_ed.verify, "sr25519": ref_sr.verify,
        "secp256k1": ref_k1.verify}


def curve_of(pub_key) -> str:
    return pub_key.type_tag().rsplit("PubKey", 1)[-1].lower()


@pytest.fixture(scope="module")
def world():
    """40 ed25519 + 5 sr25519 + 3 secp256k1 validators of equal power in the
    set's own order, and a commit all of them signed."""
    rng = np.random.default_rng(26)
    privs = [Ed25519PrivKey(rng.bytes(32)) for _ in range(40)]
    privs += [SR.Sr25519PrivKey(rng.bytes(32)) for _ in range(5)]
    privs += [K1.Secp256k1PrivKey.from_secret(rng.bytes(32)) for _ in range(3)]
    vals = ValidatorSet([Validator.from_pub_key(p.pub_key(), 10)
                         for p in privs])
    by_addr = {p.pub_key().address(): p for p in privs}
    bid = BlockID(b"\xaa" * 32, PartSetHeader(1, b"\xbb" * 32))
    commit = Commit(height=HEIGHT, round=0, block_id=bid, signatures=[
        CommitSig(BlockIDFlag.COMMIT, v.address,
                  Timestamp(1_700_000_000, i), b"")
        for i, v in enumerate(vals.validators)])
    for i, v in enumerate(vals.validators):
        commit.signatures[i].signature = by_addr[v.address].sign(
            commit.vote_sign_bytes(CHAIN, i))
    commit.invalidate_memos()
    by_curve = {"ed25519": [], "sr25519": [], "secp256k1": []}
    for i, v in enumerate(vals.validators):
        by_curve[curve_of(v.pub_key)].append(i)
    assert [len(by_curve[c]) for c in by_curve] == [40, 5, 3]
    return vals, bid, commit, by_curve


def plain_judgement(vals, commit) -> str:
    """What upstream's per-signature loop gives: every present lane by its
    own curve's reference in commit order, then the +2/3 tally."""
    tally = 0
    for i, cs in enumerate(commit.signatures):
        if cs.is_absent():
            continue
        pub = vals.validators[i].pub_key
        if not REFS[curve_of(pub)](pub.bytes(),
                                   commit.vote_sign_bytes(CHAIN, i),
                                   cs.signature):
            return f"invalid signature at index {i}"
        if cs.is_commit():
            tally += vals.validators[i].voting_power
    if tally <= vals.total_voting_power() * 2 // 3:
        return "not enough voting power"
    return "accepted"


def program_judgement(vals, bid, commit) -> str:
    try:
        validation.verify_commit(CHAIN, vals, bid, HEIGHT, commit)
    except validation.ErrInvalidSignature as e:
        return str(e)
    except validation.ErrNotEnoughVotingPower:
        return "not enough voting power"
    return "accepted"


def broken(commit, idxs):
    bad = copy.deepcopy(commit)
    for i in idxs:
        sig = bytearray(bad.signatures[i].signature)
        sig[40] ^= 0x01
        bad.signatures[i].signature = bytes(sig)
    bad.invalidate_memos()
    return bad


def test_honest_mixed_commit_is_accepted(world):
    vals, bid, commit, _ = world
    assert plain_judgement(vals, commit) == "accepted"
    assert program_judgement(vals, bid, commit) == "accepted"


# which lane of each curve is broken: (ed25519, sr25519, secp256k1) as
# positions in that curve's index list, None for a curve left whole. The
# blame rule: the LOWEST bad index of the commit, whichever curve holds it.
CASES = {
    "ed25519 alone": (3, None, None),
    "sr25519 alone": (None, 2, None),
    "secp256k1 alone": (None, None, 1),
    "ed25519 lowest of two": (0, -1, None),
    "sr25519 lowest of two": (None, 0, -1),
    "secp256k1 lowest of two": (-1, None, 0),
    "ed25519 lowest of three": (0, -1, -1),
    "sr25519 lowest of three": (-1, 0, -1),
    "secp256k1 lowest of three": (-1, -1, 0),
}


@pytest.mark.parametrize("case", list(CASES))
def test_bad_lanes_are_blamed_on_the_lowest_index(world, case):
    vals, bid, commit, by_curve = world
    idxs = [by_curve[c][pos] for c, pos in zip(by_curve, CASES[case])
            if pos is not None]
    want = f"invalid signature at index {min(idxs)}"
    lowest = curve_of(vals.validators[min(idxs)].pub_key)
    if len(idxs) > 1:
        # the fixture's order has to put the named curve first
        assert case.startswith(lowest), (case, sorted(idxs))
    bad = broken(commit, idxs)
    assert plain_judgement(vals, bad) == want
    assert program_judgement(vals, bid, bad) == want


def test_under_two_thirds_is_refused(world):
    vals, bid, commit, _ = world
    thin = copy.deepcopy(commit)
    for i in range(0, 48, 3)[:16]:  # 32 of 48 equal powers sign: not > 2/3
        thin.signatures[i] = CommitSig.absent()
    thin.invalidate_memos()
    assert plain_judgement(vals, thin) == "not enough voting power"
    assert program_judgement(vals, bid, thin) == "not enough voting power"


# ---------------------------------------------------------------------
# the order of the legs


class _Handle:
    def __init__(self, log, who, verdict):
        self.log, self.who, self.verdict = log, who, verdict

    def prefetch(self):
        self.log.append(f"{self.who}.prefetch")

    def result(self):
        self.log.append(f"{self.who}.result")
        return self.verdict


class _Recording:
    """A stand-in batch verifier that accepts everything and records what
    verify_commit asks of it."""

    def __init__(self, log, who):
        self.log, self.who, self.n = log, who, 0

    def add(self, pub, msg, sig):
        self.n += 1
        return True

    def count(self):
        return self.n

    def verify(self):
        self.log.append(f"{self.who}.verify")
        return True, [True] * self.n

    def submit(self):
        self.log.append(f"{self.who}.submit")
        return _Handle(self.log, self.who, (True, [True] * self.n))


@pytest.fixture
def recorded(monkeypatch):
    from cometbft_tpu.crypto import batch

    log = []

    def create(pub, backend="tpu"):
        curve = curve_of(pub)
        return None if curve == "secp256k1" else _Recording(log, curve)

    def submit_many(rows):
        log.append("secp256k1.submit")
        return _Handle(log, "secp256k1", (True, [True] * len(rows)))

    monkeypatch.setattr(batch, "create_batch_verifier", create)
    monkeypatch.setattr(K1, "submit_many", submit_many)
    return log


def test_both_host_legs_are_launched_before_any_verdict_is_awaited(
        world, recorded):
    vals, bid, commit, _ = world
    validation.verify_commit(CHAIN, vals, bid, HEIGHT, commit)
    first_result = min(i for i, e in enumerate(recorded)
                       if e.endswith(".result"))
    launched = recorded[:first_result]
    assert {"sr25519.submit", "secp256k1.submit", "ed25519.submit"} \
        <= set(launched)
    # the host legs are handed to their threads before ed25519's submit()
    # packs on this one, and the device's verdict is awaited first
    assert launched.index("ed25519.submit") > max(
        launched.index("sr25519.submit"), launched.index("secp256k1.submit"))
    assert recorded[first_result] == "ed25519.result"
    assert sorted(e for e in recorded if e.endswith(".result")) == [
        "ed25519.result", "secp256k1.result", "sr25519.result"]


def test_a_single_curve_commit_makes_exactly_todays_calls(recorded,
                                                          monkeypatch):
    from cometbft_tpu.crypto import keys
    from cometbft_tpu.utils import factories as fx

    def no_thread(*a, **kw):
        raise AssertionError("an ed25519-only commit started a host leg")

    monkeypatch.setattr(keys.HostLeg, "__init__", no_thread)
    signers = fx.make_signers(8, seed=5)
    vals = fx.make_validator_set(signers)
    bid = fx.make_block_id(b"one-curve")
    commit = fx.make_commit(CHAIN, HEIGHT, 0, bid, vals,
                            {s.address(): s for s in signers})
    validation.verify_commit(CHAIN, vals, bid, HEIGHT, commit)
    assert recorded == ["ed25519.submit", "ed25519.prefetch",
                        "ed25519.result"]


def test_each_leg_is_a_child_span_with_its_own_and_waited_time(world,
                                                               tmp_path):
    import json

    from cometbft_tpu.utils import trace

    vals, bid, commit, _ = world
    sink = str(tmp_path / "mixed.jsonl")
    trace.configure(sink)
    try:
        validation.verify_commit(CHAIN, vals, bid, HEIGHT, commit)
        trace.flush()
        with open(sink, encoding="utf-8") as f:
            recs = [json.loads(line) for line in f]
    finally:
        trace.disable()
    root = next(r for r in recs if r["name"] == "types.verify_commit")
    legs = {r["curve"]: r for r in recs
            if r["name"] == "crypto.commit_partition"}
    assert {c: (r["path"], r["n"]) for c, r in legs.items()} == {
        "ed25519": ("batch", 40), "sr25519": ("batch", 5),
        "secp256k1": ("native-multi", 3)}
    for r in legs.values():
        assert r["kind"] == "span" and r["parent"] == root["id"]
        assert r["root"] == root["id"] and r["id"] != root["id"]
        assert root["t0_ns"] <= r["t0_ns"] <= r["t1_ns"] <= root["t1_ns"]
        assert 0 <= r["waited_ms"] <= r["dur_ms"] + 0.01
        assert 0 <= r["own_ms"] <= r["dur_ms"] + 0.01
        assert "self_ms" not in r
    # launched host legs first, awaited in reverse: the legs overlap
    assert legs["secp256k1"]["t0_ns"] <= legs["sr25519"]["t0_ns"] \
        <= legs["ed25519"]["t0_ns"]
    assert legs["ed25519"]["t1_ns"] <= legs["sr25519"]["t1_ns"] \
        <= legs["secp256k1"]["t1_ns"]


# ---------------------------------------------------------------------
# the references: published vectors


def test_reference_keccak_and_merlin_vectors():
    st = bytearray(200)
    ref_sr.keccak_f1600(st)
    assert st[:8].hex() == "e7dde140798f25f1"  # Keccak-f[1600] of zeros
    # the merlin crate's equivalence test
    t = ref_sr.Transcript(b"test protocol")
    t.append_message(b"some label", b"some data")
    assert t.challenge_bytes(b"challenge", 32).hex() == (
        "d5a21972d0d5fe320c0d263fac7fffb8145aa640af6e9bca177c03c7efcf0615")


def test_reference_ristretto_decodes_rfc9496_generator_multiples():
    encodings = [  # RFC 9496, appendix A.1
        "0000000000000000000000000000000000000000000000000000000000000000",
        "e2f2ae0a6abc4e71a884a961c500515f58e30b6aa582dd8db6a65945e08d2d76",
        "6a493210f7499cd17fecb510ae0cea23a110e8d5b901f8acadd3095c73a3b919",
        "94741f5d5d52755ece4f23f044ee27d5d1ea1e2bd196b462166b16152a9d0259",
        "da80862773358b466ffadfe0b3293ab3d9fd53c5ea6c955358f568322daf6a57",
    ]
    for k, enc in enumerate(encodings):
        point = ref_sr.ristretto_decode(bytes.fromhex(enc))
        assert point is not None
        assert ref_sr.ristretto_equal(point, ref_sr._mul(k, ref_sr.BASE))
        assert (k == 0) == ref_sr.ristretto_equal(point, ref_sr.IDENTITY)


@pytest.mark.parametrize("enc", [
    # RFC 9496, appendix A.3: non-canonical field encodings, a negative
    # field element, a non-square x^2
    "00ffffffffffffffffffffffffffffffffffffffffffffffffffffffffffffff",
    "edffffffffffffffffffffffffffffffffffffffffffffffffffffffffffff7f",
    "0100000000000000000000000000000000000000000000000000000000000000",
    "26948d35ca62e643e26a83177332e6b6afeb9d08e4268b650f1f5bbd8d81d371",
])
def test_reference_ristretto_refuses_rfc9496_bad_encodings(enc):
    assert ref_sr.ristretto_decode(bytes.fromhex(enc)) is None


def test_reference_ecdsa_accepts_the_rfc6979_vector():
    # bitcoin-core's deterministic-nonce vector: key 1, "Satoshi Nakamoto"
    sig = bytes.fromhex(
        "934b1ea10a4b3c1757e2b0c017d0b6143ce3c9a7e6a4a49860d7a6ab210ee3d8"
        "2442ce9d2b916064108014783e923ec36b49743e2ffa1c4496f01a512aafd9e5")
    pub = b"\x02" + ref_k1.G[0].to_bytes(32, "big")
    assert ref_k1.decompress(pub) == ref_k1.G
    assert ref_k1.verify(pub, b"Satoshi Nakamoto", sig)
    assert not ref_k1.verify(pub, b"Satoshi Nakamoto!", sig)


# ---------------------------------------------------------------------
# the references against the program's host engines


def _sr_lane():
    key = SR.Sr25519PrivKey(b"\x31" * 32)
    msg = b"vote sign bytes of some height"
    return key.pub_key(), msg, key.sign(msg)


def _k1_lane():
    key = K1.Secp256k1PrivKey.from_secret(b"validator-9")
    msg = b"vote sign bytes of some height"
    return key.pub_key(), msg, key.sign(msg)


def _negative_twin(sig: bytes) -> bytes:
    s = int.from_bytes(sig[:32], "little")
    return ((ref_sr.P - s) % ref_sr.P).to_bytes(32, "little") + sig[32:]


def _upper_s(sig: bytes) -> bytes:
    s = int.from_bytes(sig[32:], "big")
    return sig[:32] + (ref_k1.N - s).to_bytes(32, "big")


def _flipped(sig: bytes) -> bytes:
    return sig[:9] + bytes([sig[9] ^ 0x04]) + sig[10:]


@pytest.mark.parametrize("curve,change,want", [
    ("sr25519", None, True),
    ("sr25519", _flipped, False),
    ("sr25519", _negative_twin, False),  # non-canonical ristretto R
    ("sr25519", lambda s: s[:63] + bytes([s[63] & 0x7F]), False),  # no marker
    ("secp256k1", None, True),
    ("secp256k1", _flipped, False),
    ("secp256k1", _upper_s, False),  # plain ECDSA accepts this twin
])
def test_reference_and_host_engine_agree(curve, change, want):
    pub, msg, sig = _sr_lane() if curve == "sr25519" else _k1_lane()
    if change is not None:
        sig = change(sig)
    assert REFS[curve](pub.bytes(), msg, sig) is want
    assert pub.verify_signature(msg, sig) is want
    # and the verifiers verify_commit hands a partition to
    if curve == "sr25519":
        bv = SR.Sr25519BatchVerifier()
        assert bv.add(pub, msg, sig)
        assert bv.submit().result() == (want, [want])
    else:
        assert K1.submit_many([(pub.bytes(), msg, sig)]).result() == (
            want, [want])
