"""A block's state-store records are encoded once: a frozen ValidatorSet
keeps its encoding, a mutable one is encoded afresh from its membership's
constant bytes, State.encode joins. Every case holds the program's bytes
to the plain encoder of tests/state_encoding_reference.py, which is
written from encoding/proto alone."""

import hashlib
import random
from dataclasses import replace

import pytest

import state_encoding_reference as ref
from cometbft_tpu.crypto.ed25519 import Ed25519PubKey
from cometbft_tpu.state.types import (
    State,
    decode_validator_set,
    encode_validator_set,
)
from cometbft_tpu.storage import MemKV, StateStore
from cometbft_tpu.types import Timestamp, Validator, ValidatorSet
from cometbft_tpu.types.basic import BlockID, PartSetHeader
from cometbft_tpu.utils.metrics import state_metrics

SIZES = (1, 4, 150, 1000)
PRIORITIES = ("zero", "positive", "negative", "rotated")


def pub_key(rng, kind):
    if kind == "ed25519":
        return Ed25519PubKey(rng.randbytes(32))
    if kind == "secp256k1":
        from cometbft_tpu.crypto.secp256k1 import Secp256k1PubKey

        return Secp256k1PubKey(b"\x02" + rng.randbytes(32))
    from cometbft_tpu.crypto.bls import BlsPubKey

    return BlsPubKey(rng.randbytes(48))


def make_set(n, keys="ed25519", priorities="rotated", seed=0):
    """A seeded set of n members: `keys` = ed25519, or mixed (the three
    key types the codec knows, by turns); `priorities` all zero, all
    positive, all negative (ten-byte varints), or what three rotations
    leave."""
    rng = random.Random(hashlib.sha256(
        f"{n}/{keys}/{priorities}/{seed}".encode()).digest())
    kinds = ("ed25519", "secp256k1", "bls") if keys == "mixed" else (
        "ed25519",)
    vals = []
    for i in range(n):
        pk = pub_key(rng, kinds[i % len(kinds)])
        # (an address is 20 bytes of the key's hash; which hash is the
        # key type's own business and not the codec's)
        prio = {"zero": 0, "positive": rng.randrange(1, 1 << 40),
                "negative": -rng.randrange(1, 1 << 40)}.get(priorities, 0)
        vals.append(Validator(hashlib.sha256(pk.bytes()).digest()[:20], pk,
                              rng.randrange(1, 1000), prio))
    vs = ValidatorSet(vals, increment_first=False)
    if priorities == "rotated":
        vs.increment_proposer_priority(3)
    return vs


def make_state(vs, height=7):
    """A state as _update_state leaves one: three sets of one membership,
    each a rotation on."""
    nxt = vs.copy_increment_proposer_priority(1)
    return State(
        chain_id="encode-once", initial_height=1, last_block_height=height,
        last_block_id=BlockID(b"\xaa" * 32, PartSetHeader(3, b"\xbb" * 32)),
        last_block_time=Timestamp(1_700_000_000 + height, 5),
        validators=nxt, last_validators=vs,
        next_validators=nxt.copy_increment_proposer_priority(1),
        last_height_validators_changed=3, last_results_hash=b"\x01" * 32,
        app_hash=b"\x02" * 8, last_height_params_changed=2)


def counts():
    v = state_metrics().valset_encode_total.values()
    return int(v.get(("hit",), 0)), int(v.get(("miss",), 0))


def same_set(a, b):
    return (a.members == b.members and a.priorities() == b.priorities()
            and a.get_proposer() == b.get_proposer())


@pytest.mark.parametrize("priorities", PRIORITIES)
@pytest.mark.parametrize("keys", ("ed25519", "mixed"))
@pytest.mark.parametrize("n", SIZES)
def test_a_set_encodes_as_the_reference_mutable_and_frozen(
        n, keys, priorities):
    vs = make_set(n, keys, priorities)
    want = ref.validator_set(vs)
    assert encode_validator_set(vs) == want  # mutable
    assert encode_validator_set(vs.copy().freeze()) == want
    if priorities == "negative":
        # a negative priority is its two's complement: ten bytes
        assert len(want) >= n * 11
    back = decode_validator_set(want)
    assert same_set(back, vs)
    assert encode_validator_set(back) == want


@pytest.mark.parametrize("priorities", PRIORITIES)
@pytest.mark.parametrize("keys", ("ed25519", "mixed"))
@pytest.mark.parametrize("n", SIZES)
def test_a_state_encodes_as_the_reference_and_round_trips(
        n, keys, priorities):
    st = make_state(make_set(n, keys, priorities))
    enc = st.encode()
    assert enc == ref.state(st)
    assert st.encode() == enc  # from the kept bytes
    back = State.decode(enc)
    assert back.encode() == enc
    assert same_set(back.next_validators, st.next_validators)
    # without its sets (a state of genesis has no last_validators)
    bare = replace(st, last_validators=None)
    assert bare.encode() == ref.state(bare)


@pytest.mark.parametrize("times", (1, 2, 7))
@pytest.mark.parametrize("n", SIZES)
def test_a_rotated_copy_encodes_its_own_priorities(n, times):
    vs = make_set(n).freeze()
    kept = encode_validator_set(vs)
    turned = vs.copy()
    turned.increment_proposer_priority(times)
    got = encode_validator_set(turned)
    assert got == ref.validator_set(turned)
    assert (got != kept) == (n > 1)  # (one member always wins and pays)
    assert encode_validator_set(vs) == kept == ref.validator_set(vs)


def change_set(vs, what, rng):
    rows = vs.validators
    if what == "join":
        pk = pub_key(rng, "ed25519")
        return [Validator(hashlib.sha256(pk.bytes()).digest()[:20], pk, 77)]
    if what == "leave":
        return [Validator(rows[-1].address, rows[-1].pub_key, 0)]
    if what == "repower":
        return [Validator(v.address, v.pub_key, v.voting_power + 13)
                for v in rows[: max(1, len(rows) // 3)]]
    return (change_set(vs, "join", rng) + change_set(vs, "leave", rng)
            + change_set(vs, "repower", rng)[:1])


@pytest.mark.parametrize("what", ("join", "leave", "repower", "all"))
@pytest.mark.parametrize("keys", ("ed25519", "mixed"))
@pytest.mark.parametrize("n", (4, 150, 1000))
def test_a_changed_membership_encodes_its_own_members(n, keys, what):
    vs = make_set(n, keys).freeze()
    kept = encode_validator_set(vs)
    changed = vs.copy()
    changed.update_with_change_set(change_set(vs, what, random.Random(n)))
    changed.increment_proposer_priority(1)
    assert changed._memo is not vs._memo
    got = encode_validator_set(changed)
    assert got == ref.validator_set(changed) != kept
    assert same_set(decode_validator_set(got), changed)
    # the old membership's constants and the frozen set's bytes stand
    assert encode_validator_set(vs) == kept
    assert encode_validator_set(vs.copy()) == kept


@pytest.mark.parametrize("n", SIZES)
def test_a_frozen_set_keeps_its_bytes_and_a_copy_starts_without(n):
    vs = make_set(n)
    first = encode_validator_set(vs)
    second = encode_validator_set(vs)
    assert first == second and first is not second
    assert counts() == (0, 2)  # a mutable set keeps nothing
    vs.freeze()
    third = encode_validator_set(vs)  # encoded, then frozen: afresh, kept
    assert third == first and counts() == (0, 3)
    assert encode_validator_set(vs) is third and counts() == (1, 3)
    twin = vs.copy()
    assert twin._enc is None and twin._memo is vs._memo
    assert encode_validator_set(twin) == third and counts() == (1, 4)
    if n > 1:
        twin.increment_proposer_priority(1)
        assert encode_validator_set(twin) != third
    assert encode_validator_set(vs) is third
    with pytest.raises(RuntimeError):
        vs.increment_proposer_priority(1)


@pytest.mark.parametrize("n", SIZES)
def test_the_members_constant_bytes_are_built_once_a_membership(n):
    vs = make_set(n)
    assert "enc_consts" not in vs._memo
    encode_validator_set(vs)
    consts = vs._memo["enc_consts"]
    assert len(consts) == n
    turned = vs.copy_increment_proposer_priority(2)
    encode_validator_set(turned)
    assert turned._memo["enc_consts"] is consts
    # the set's bytes are those constants, each with its priority behind
    for const, v in zip(consts, vs.validators):
        assert ref.validator(v).startswith(const)
        assert ref.validator(replace(v, proposer_priority=0)) == const


@pytest.mark.parametrize("backend", ("mem", "sqlite"))
@pytest.mark.parametrize("n", (4, 150))
def test_a_save_a_block_encodes_one_set_afresh(tmp_path, n, backend):
    """The states of consecutive heights as _update_state makes them: two
    of a state's three sets are the frozen objects the height before has
    encoded, and the two sets save() writes beside the state are two of
    the three inside it."""
    from cometbft_tpu.storage import open_kv

    kv = MemKV() if backend == "mem" else open_kv(str(tmp_path / "s.db"))
    ss = StateStore(kv)
    st = make_state(make_set(n), height=1)
    ss.save(st)
    assert counts() == (2, 3)  # a state's sets are new objects: three
    for h in range(2, 8):
        st = replace(
            st, last_block_height=h, last_validators=st.validators,
            validators=st.next_validators,
            next_validators=st.next_validators
            .copy_increment_proposer_priority(1))
        before = counts()
        ss.save(st)
        assert counts() == (before[0] + 4, before[1] + 1), h
        assert kv.get(b"S:cur") == ref.state(st)
        assert kv.get(b"SV:" + (h + 1).to_bytes(8, "big")) == (
            ref.validator_set(st.validators))
        assert kv.get(b"SV:" + (h + 2).to_bytes(8, "big")) == (
            ref.validator_set(st.next_validators))
    assert ss.encode_seconds > 0 and ss.write_seconds > 0
    loaded = ss.load()
    before = counts()
    ss.save(loaded)  # decoded from the store: three new objects again
    assert counts() == (before[0] + 2, before[1] + 3)
    assert loaded.encode() == ref.state(st) == kv.get(b"S:cur")
    kv.close()
