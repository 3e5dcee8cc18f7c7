"""verify_commit / verify_commit_light from a commit's decode columns
(types/validation.commit_lanes) against the per-slot loop: the same commit
bytes, once decoded (columnar) and once decoded with its memos dropped
(per-slot), must give the same outcome: accepted, or the same exception type
and message. And that the columnar path engages, builds no CommitSig, and says
so in its span and its counter."""

import json

import numpy as np
import pytest

from cometbft_tpu.crypto import ed25519_ref as ref
from cometbft_tpu.crypto import secp256k1 as K1
from cometbft_tpu.crypto import sr25519 as SR
from cometbft_tpu.crypto.ed25519 import Ed25519PrivKey
from cometbft_tpu.types import (
    BlockIDFlag,
    Commit,
    CommitSig,
    Timestamp,
    validation,
)
from cometbft_tpu.types.validator_set import Validator, ValidatorSet
from cometbft_tpu.utils import factories as fx
from cometbft_tpu.utils import trace
from cometbft_tpu.utils.metrics import crypto_metrics

CHAIN = "columnar-chain"
HEIGHT = 9
ED, SR_TAG, K1_TAG = (validation._ED_TAG, validation._SR_TAG,
                      validation._SECP_TAG)

MODES = {
    "full": validation.verify_commit,
    "light": validation.verify_commit_light,
    "light_all": lambda *a, **kw: validation.verify_commit_light(
        *a, verify_all_signatures=True, **kw),
}


def path_counts() -> dict:
    return crypto_metrics().commit_path_total.values()


def moved(before: dict) -> dict:
    return {k: v - before.get(k, 0.0) for k, v in path_counts().items()
            if v != before.get(k, 0.0)}


def outcome(mode: str, vals, bid, commit, height=HEIGHT, **kw):
    """("accepted",) or (exception type, message), and which path the call
    counted itself under."""
    before = path_counts()
    try:
        MODES[mode](CHAIN, vals, bid, height, commit, **kw)
        out = ("accepted",)
    except validation.CommitError as e:
        out = (type(e).__name__, str(e))
    return out, moved(before)


def both_paths(mode, vals, bid, commit, want_path="columnar", **kw):
    """The outcome of `commit`'s bytes on the columnar and on the per-slot
    path, asserted equal; `want_path` is what the decoded commit must take
    (a reason where the case is one the columnar gates decline)."""
    commit.invalidate_memos()
    enc = commit.encode()
    fresh = Commit.decode(enc)
    assert fresh.verify_columns() is not None
    got, took = outcome(mode, vals, bid, fresh, **kw)
    slots = Commit.decode(enc)
    slots.invalidate_memos()
    want, took_slots = outcome(mode, vals, bid, slots, **kw)
    assert got == want
    if want_path is not None:
        key = (("columnar", "") if want_path == "columnar"
               else ("per_slot", want_path))
        assert took == {key: 1.0}, took
        assert took_slots == {("per_slot", "no_columns"): 1.0}, took_slots
    return got


# ---------------------------------------------------------------------
# an all-ed25519 set


@pytest.fixture(scope="module")
def world():
    """12 ed25519 validators of equal power, the last two under ZIP-215
    non-canonical encodings of the identity, and what signs for them."""
    y = (2**255 - 19) + 1
    e0 = y.to_bytes(32, "little")
    e1 = bytearray(e0)
    e1[31] |= 0x80
    signers = fx.make_signers(10, seed=27)
    signers += [fx.ScalarSigner(0, e0), fx.ScalarSigner(0, bytes(e1))]
    vals = fx.make_validator_set(signers)
    by_addr = {s.address(): s for s in signers}
    weird = [i for i, v in enumerate(vals.validators)
             if by_addr[v.address].scalar == 0]
    return vals, by_addr, fx.make_block_id(b"columnar"), weird


def signed(world, **kw) -> Commit:
    vals, by_addr, bid, _ = world
    return fx.make_commit(CHAIN, HEIGHT, 0, bid, vals, by_addr, **kw)


def mutate(commit, idx, fn):
    sig = bytearray(commit.signatures[idx].signature)
    fn(sig)
    commit.signatures[idx].signature = bytes(sig)


def flip_r(sig):
    sig[3] ^= 0x10


def flip_s(sig):
    sig[40] ^= 0x01


def s_plus_l(sig):
    sig[32:] = (int.from_bytes(sig[32:], "little") + ref.L).to_bytes(
        32, "little")


def garbage(sig):
    sig[:] = bytes(range(32)) + (1).to_bytes(32, "little")


def _normal(world, n=1):
    """The first n slots that hold an ordinary key."""
    return [i for i in range(12) if i not in world[3]][:n]


def case_honest(world):
    return signed(world), ("accepted",)


def case_flipped_r(world):
    c, (i,) = signed(world), _normal(world)
    mutate(c, i + 3, flip_r)
    return c, ("ErrInvalidSignature", f"invalid signature at index {i + 3}")


def case_flipped_s(world):
    c = signed(world)
    mutate(c, 5, flip_s)
    return c, ("ErrInvalidSignature", "invalid signature at index 5")


def case_s_not_below_l(world):
    c = signed(world)
    mutate(c, 4, s_plus_l)
    return c, ("ErrInvalidSignature", "invalid signature at index 4")


def case_noncanonical_key_honest_and_wrong(world):
    # one odd key keeps its valid signature (must be accepted as a lane),
    # the other gets a wrong one
    c, i = signed(world), world[3][1]
    mutate(c, i, garbage)
    refused = ("ErrInvalidSignature", f"invalid signature at index {i}")
    # light semantics stop at the ninth COMMIT lane (90 > 80)
    return c, {"light": refused if i < 9 else ("accepted",), "*": refused}


def case_two_bad_lanes_lowest_blamed(world):
    c = signed(world)
    mutate(c, 7, flip_s)
    mutate(c, 2, flip_r)
    return c, ("ErrInvalidSignature", "invalid signature at index 2")


def case_absent_slots(world):
    return signed(world, absent={1, 6, 11}), ("accepted",)


def case_absent_slots_and_a_bad_lane(world):
    # today's message names the lane among the judged ones, not the
    # commit's slot: slot 8 with slots 1 and 6 absent is lane 6
    c = signed(world, absent={1, 6})
    mutate(c, 8, flip_s)
    return c, ("ErrInvalidSignature", "invalid signature at index 6")


def case_nil_votes_verified_not_counted(world):
    # 12 x 10 = 120, threshold 80: nine COMMIT votes (90) carry it and the
    # three NIL votes are verified without being counted
    return signed(world, nil={0, 4, 9}), ("accepted",)


def case_nil_votes_leave_too_little(world):
    return signed(world, nil={0, 3, 4, 9}), {
        "*": ("ErrNotEnoughVotingPower", "tallied 80 <= threshold 80")}


def case_bad_nil_vote(world):
    # full semantics verify a NIL vote, light semantics never look at it
    c = signed(world, nil={3})
    mutate(c, 3, flip_s)
    return c, {"full": ("ErrInvalidSignature",
                        "invalid signature at index 3"),
               "*": ("accepted",)}


def case_power_exactly_two_thirds(world):
    return signed(world, absent={2, 5, 7, 10}), {
        "*": ("ErrNotEnoughVotingPower", "tallied 80 <= threshold 80")}


def case_power_one_over_two_thirds(world):
    return signed(world, absent={2, 5, 7}), ("accepted",)


def case_bad_lane_past_the_light_cut(world):
    # light semantics stop at the ninth COMMIT lane (90 > 80): a bad tenth
    # is never judged unless every signature is asked for
    c = signed(world)
    mutate(c, 10, flip_s)
    return c, {"light": ("accepted",),
               "*": ("ErrInvalidSignature", "invalid signature at index 10")}


def case_bad_lane_at_the_light_cut(world):
    c = signed(world)
    mutate(c, 8, flip_s)
    return c, ("ErrInvalidSignature", "invalid signature at index 8")


CASES = {f.__name__[5:]: f for f in (
    case_honest, case_flipped_r, case_flipped_s, case_s_not_below_l,
    case_noncanonical_key_honest_and_wrong,
    case_two_bad_lanes_lowest_blamed, case_absent_slots,
    case_absent_slots_and_a_bad_lane, case_nil_votes_verified_not_counted,
    case_nil_votes_leave_too_little, case_bad_nil_vote,
    case_power_exactly_two_thirds, case_power_one_over_two_thirds,
    case_bad_lane_past_the_light_cut, case_bad_lane_at_the_light_cut)}


def _want(want, mode):
    return want.get(mode, want.get("*")) if isinstance(want, dict) else want


@pytest.mark.parametrize("mode", list(MODES))
@pytest.mark.parametrize("case", list(CASES))
def test_columnar_and_per_slot_agree(world, case, mode):
    vals, _, bid, _ = world
    commit, want = CASES[case](world)
    assert both_paths(mode, vals, bid, commit) == _want(want, mode)


@pytest.mark.parametrize("mode", list(MODES))
def test_reference_backend_agrees(world, mode):
    """backend "cpu" (every lane by the pure-Python reference, nothing
    launched) judges the columnar lanes as it judges the items."""
    vals, _, bid, _ = world
    assert both_paths(mode, vals, bid, signed(world, absent={3}),
                      backend="cpu") == ("accepted",)
    bad = signed(world, nil={1})
    mutate(bad, 6, flip_r)
    mutate(bad, 9, s_plus_l)
    # light semantics never take the NIL slot, so slot 6 is their lane 5
    assert both_paths(mode, vals, bid, bad, backend="cpu") == (
        "ErrInvalidSignature",
        f"invalid signature at index {6 if mode == 'full' else 5}")


@pytest.mark.parametrize("mode", list(MODES))
def test_address_mismatch_is_the_per_slot_loops_error(world, mode):
    vals, _, bid, _ = world
    c = signed(world)
    c.signatures[4].validator_address = vals.validators[5].address
    assert both_paths(mode, vals, bid, c, want_path="address") == (
        "ErrInvalidSignature", "address mismatch at index 4")


@pytest.mark.parametrize("mode", list(MODES))
def test_wrong_size_commit(world, mode):
    vals, _, bid, _ = world
    c = signed(world)
    c.signatures.append(CommitSig.absent())
    # refused before the commit becomes lanes: neither path is counted
    assert both_paths(mode, vals, bid, c, want_path=None) == (
        "ErrInvalidCommitSize", "validator set size 12 != commit size 13")
    got, took = outcome(mode, vals, bid, Commit.decode(c.encode()))
    assert took == {}


@pytest.mark.parametrize("mode", list(MODES))
def test_wrong_height_and_block(world, mode):
    vals, _, bid, _ = world
    enc = signed(world).encode()
    got, took = outcome(mode, vals, bid, Commit.decode(enc), height=HEIGHT + 1)
    assert got == ("ErrInvalidCommitHeight", "expected height 10, got 9")
    got, took = outcome(mode, vals, fx.make_block_id(b"other"),
                        Commit.decode(enc))
    assert got == ("ErrInvalidBlockID", "commit is for a different block")
    assert took == {}


@pytest.mark.parametrize("what,reason", [
    ("short signature", "shape"), ("address on an absent slot", "shape"),
    ("one lane", "shape")])
def test_odd_shapes_decline(world, what, reason):
    vals, by_addr, bid, _ = world
    if what == "short signature":
        c = signed(world)
        c.signatures[6].signature = c.signatures[6].signature[:63]
        want = ("ErrInvalidSignature", "invalid signature at index 6")
    elif what == "address on an absent slot":
        c = signed(world)
        c.signatures[6] = CommitSig(BlockIDFlag.ABSENT,
                                    vals.validators[6].address,
                                    Timestamp(), b"")
        want = ("accepted",)
    else:  # fewer lanes than a batch: judged singly, as today
        c = signed(world, absent=set(range(1, 12)))
        want = ("ErrNotEnoughVotingPower", "tallied 10 <= threshold 80")
    assert both_paths("full", vals, bid, c, want_path=reason) == want


# ---------------------------------------------------------------------
# a mixed set, and a set the columnar path must leave alone


@pytest.fixture(scope="module")
def mixed():
    """9 ed25519 + 4 sr25519 + 3 secp256k1 validators of equal power in the
    set's own order, and the keys that sign for them."""
    rng = np.random.default_rng(27)
    privs = [Ed25519PrivKey(rng.bytes(32)) for _ in range(9)]
    privs += [SR.Sr25519PrivKey(rng.bytes(32)) for _ in range(4)]
    privs += [K1.Secp256k1PrivKey.from_secret(rng.bytes(32))
              for _ in range(3)]
    vals = ValidatorSet([Validator.from_pub_key(p.pub_key(), 10)
                         for p in privs])
    by_addr = {p.pub_key().address(): p for p in privs}
    by_curve = {ED: [], SR_TAG: [], K1_TAG: []}
    for i, v in enumerate(vals.validators):
        by_curve[v.pub_key.type_tag()].append(i)
    return vals, by_addr, fx.make_block_id(b"mixed"), by_curve


def signed_by_keys(vals, by_addr, bid, absent=(), nil=()) -> Commit:
    commit = Commit(height=HEIGHT, round=0, block_id=bid, signatures=[
        CommitSig.absent() if i in absent else CommitSig(
            BlockIDFlag.NIL if i in nil else BlockIDFlag.COMMIT, v.address,
            Timestamp(1_700_000_000, 1000 * i), b"")
        for i, v in enumerate(vals.validators)])
    for i, v in enumerate(vals.validators):
        if i not in absent:
            commit.signatures[i].signature = by_addr[v.address].sign(
                commit.vote_sign_bytes(CHAIN, i))
    commit.invalidate_memos()
    return commit


@pytest.mark.parametrize("mode", list(MODES))
@pytest.mark.parametrize("curve", ["none", ED, SR_TAG, K1_TAG, "all"])
def test_mixed_set_bad_lane_on_each_curve(mixed, curve, mode):
    vals, by_addr, bid, by_curve = mixed
    absent = {by_curve[ED][0]}
    c = signed_by_keys(vals, by_addr, bid, absent=absent,
                       nil={by_curve[SR_TAG][0]})
    bad = ([] if curve == "none"
           else [by_curve[t][1] for t in by_curve] if curve == "all"
           else [by_curve[curve][1]])
    for i in bad:
        mutate(c, i, flip_s)
    got = both_paths(mode, vals, bid, c)
    if mode == "light":
        # 16 x 10, threshold 106: the first eleven COMMIT lanes are judged
        judged = [i for i, cs in enumerate(c.signatures) if cs.is_commit()]
        bad = [i for i in bad if i in judged[:11]]
    if not bad:
        assert got == ("accepted",)
    else:
        # the lowest bad lane over all curves, counted among judged lanes
        lanes = [i for i, cs in enumerate(c.signatures)
                 if (cs.is_commit() if mode != "full"
                     else not cs.is_absent())]
        assert got == ("ErrInvalidSignature",
                       f"invalid signature at index {lanes.index(min(bad))}")


def test_a_set_with_a_bls_key_is_left_to_the_per_slot_loop(world):
    from cometbft_tpu.crypto.bls import BlsPrivKey

    _, by_addr, bid, _ = world
    signers = list(by_addr.values())
    bls_key = BlsPrivKey.from_secret(b"columnar-bls").pub_key()
    vals = ValidatorSet(
        [Validator.from_pub_key(s.pub_key(), 10) for s in signers]
        + [Validator.from_pub_key(bls_key, 10)])
    slot = [i for i, v in enumerate(vals.validators)
            if v.pub_key.type_tag() == validation._BLS_TAG]
    # the BLS validator did not sign, so every signature is 64 bytes and
    # the commit decodes into columns; its key type alone declines
    c = fx.make_commit(CHAIN, HEIGHT, 0, bid, vals, by_addr,
                       absent=set(slot))
    for mode in MODES:
        assert both_paths(mode, vals, bid, c, want_path="key_type") == (
            "accepted",)
    mutate(c, 3 if slot[0] > 3 else 5, flip_r)
    got = both_paths("full", vals, bid, c, want_path="key_type")
    assert got[0] == "ErrInvalidSignature"


# ---------------------------------------------------------------------
# the lanes themselves, against the loop


@pytest.mark.parametrize("seed", range(6))
@pytest.mark.parametrize("light", [False, True])
def test_lanes_equal_the_loops_items(mixed, seed, light):
    """commit_lanes against _slot_items, lane for lane, over random
    ABSENT / NIL / COMMIT patterns and powers on the mixed set: pubkey,
    sign bytes, signature, counted power, and light's cut."""
    vals0, _, bid, _ = mixed
    rng = np.random.default_rng([27, seed])
    vals = ValidatorSet([
        Validator(v.address, v.pub_key, int(rng.integers(1, 50)))
        for v in vals0.validators])
    flags = rng.choice([1, 2, 2, 2, 3], size=len(vals))
    commit = Commit(height=HEIGHT, round=0, block_id=bid, signatures=[
        CommitSig.absent() if f == 1 else CommitSig(
            BlockIDFlag(int(f)), v.address,
            Timestamp(1_700_000_000 + int(rng.integers(0, 3)),
                      int(rng.integers(0, 10**9))),
            rng.bytes(63) + b"\x00")  # S < L: add() keeps the bytes
        for f, v in zip(flags, vals.validators)])
    commit = Commit.decode(commit.encode())
    cut_at = (vals.total_voting_power() * 2 // 3
              if light and seed % 2 else None)
    lanes = validation.commit_lanes(CHAIN, vals, commit, not light, cut_at)
    assert not isinstance(lanes, str), lanes
    assert commit.signatures._real is None
    items, _ = validation._slot_items(CHAIN, vals, commit, light, cut_at)
    assert lanes.n == len(items)
    assert lanes.power == sum(p for _, _, _, p in items)
    got = [None] * lanes.n
    for tag, (pos, slots, _) in lanes.curves.items():
        if tag == ED:
            from cometbft_tpu.crypto.ed25519 import Ed25519BatchVerifier

            bv = Ed25519BatchVerifier(backend="cpu")
            lanes.add_ed25519(bv)
            bv._materialize()
            rows = bv._items
        else:
            rows = lanes.rows(tag)
        assert len(rows) == len(pos)
        for lane, slot, row in zip(pos.tolist(), slots.tolist(), rows):
            assert vals.validators[slot].pub_key.type_tag() == tag
            got[lane] = row
    assert got == [(pub.bytes(), msg, sig) for pub, msg, sig, _ in items]


# ---------------------------------------------------------------------
# that it engages, and what it saves


def _commit_items_spans(sink) -> list[dict]:
    trace.flush()
    with open(sink, encoding="utf-8") as f:
        return [r for r in map(json.loads, f)
                if r.get("name") == "types.commit_items"]


def test_the_columnar_path_engages_and_builds_no_commitsig(world, tmp_path):
    vals, _, bid, _ = world
    hand_built = signed(world)
    fresh = Commit.decode(hand_built.encode())
    sink = str(tmp_path / "spans.jsonl")
    trace.configure(sink)
    try:
        before = path_counts()
        validation.verify_commit(CHAIN, vals, bid, HEIGHT, fresh)
        assert fresh.signatures._real is None  # no CommitSig was built
        assert moved(before) == {("columnar", ""): 1.0}
        before = path_counts()
        validation.verify_commit(CHAIN, vals, bid, HEIGHT, hand_built)
        assert moved(before) == {("per_slot", "no_columns"): 1.0}
        spans = _commit_items_spans(sink)
    finally:
        trace.disable()
    assert [(s["path"], s.get("reason"), s["n"]) for s in spans] == [
        ("columnar", None, 12), ("per_slot", "no_columns", 12)]
    assert all("sign_bytes_ms" in s for s in spans)
    text = "\n".join(crypto_metrics().commit_path_total.expose())
    assert 'path="columnar",reason=""' in text
    assert 'path="per_slot",reason="no_columns"' in text


def test_key_columns_follow_the_set(world):
    vals0, by_addr, _, _ = world
    vals = vals0.copy()
    kc = vals.key_columns()
    assert vals.key_columns() is kc  # memoised
    addr_rows, pub_rows, powers = vals.ed25519_columns()
    assert addr_rows is kc.addr_rows and powers is kc.powers
    assert list(kc.curves) == [ED]
    idx, rows = kc.curves[ED]
    assert rows is pub_rows and idx.tolist() == list(range(12))
    assert [bytes(r) for r in pub_rows] == [
        v.pub_key.bytes() for v in vals.validators]
    assert [bytes(r) for r in addr_rows] == [
        v.address for v in vals.validators]
    assert powers.tolist() == [v.voting_power for v in vals.validators]
    # copies share the membership's columns, whichever of them is asked
    # first (state hands every height a fresh copy of the set)
    first = vals0.copy()
    later = first.copy_increment_proposer_priority(1).copy()
    assert first.key_columns() is later.key_columns()
    # a change of membership gives that set columns of its own and leaves
    # the sets it was copied from alone
    gone = vals.validators[0]
    vals.update_with_change_set([Validator(gone.address, gone.pub_key, 0)])
    kc2 = vals.key_columns()
    assert kc2 is not kc and len(kc2.powers) == 11
    assert vals0.key_columns() is kc and len(kc.powers) == 12
    assert vals.copy().key_columns() is kc2
    assert [bytes(r) for r in kc2.addr_rows] == [
        v.address for v in vals.validators]
    # a second key type: ed25519_columns has nothing to give, key_columns
    # gives each curve its validators
    sr = SR.Sr25519PrivKey(bytes(range(32))).pub_key()
    vals.update_with_change_set([Validator.from_pub_key(sr, 10)])
    assert vals.ed25519_columns() is None
    kc3 = vals.key_columns()
    assert sorted(kc3.curves) == sorted([ED, SR_TAG])
    (at,), rows = kc3.curves[SR_TAG]
    assert vals.validators[at].pub_key.bytes() == bytes(rows[0]) == sr.bytes()
