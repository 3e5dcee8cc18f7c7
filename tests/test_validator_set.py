"""ValidatorSet tests: ordering, proposer rotation, updates, hashing."""

import functools
import hashlib
import random

import pytest

from cometbft_tpu.crypto.ed25519 import Ed25519PrivKey, Ed25519PubKey
from cometbft_tpu.state.types import State, encode_validator_set
from cometbft_tpu.types import Validator, ValidatorSet
from cometbft_tpu.types import validator_set as VS

PARENT_STATE_DIGEST = (
    "f81f45d7b6d71a08c1f07201db65d8a2511ad4008b6a360fde30f0de915ef707")


def _mk_vals(powers):
    out = []
    for i, p in enumerate(powers):
        pk = Ed25519PrivKey(bytes([i + 1]) * 32)
        out.append(Validator.from_pub_key(pk.pub_key(), p))
    return out


def test_ordering_power_desc_then_address():
    vals = _mk_vals([5, 20, 10, 20])
    vs = ValidatorSet(vals)
    powers = [v.voting_power for v in vs.validators]
    assert powers == sorted(powers, reverse=True)
    # equal powers tie-break by address ascending
    twenties = [v for v in vs.validators if v.voting_power == 20]
    assert twenties[0].address < twenties[1].address


def test_round_robin_equal_powers():
    vs = ValidatorSet(_mk_vals([10, 10, 10]))
    seen = []
    for _ in range(6):
        seen.append(vs.get_proposer().address)
        vs.increment_proposer_priority(1)
    assert seen[:3] == seen[3:6]
    assert len(set(seen[:3])) == 3


def test_proposer_frequency_proportional_to_power():
    vs = ValidatorSet(_mk_vals([1, 2, 3]))
    counts = {}
    for _ in range(600):
        addr = vs.get_proposer().address
        counts[addr] = counts.get(addr, 0) + 1
        vs.increment_proposer_priority(1)
    by_power = {v.address: v.voting_power for v in vs.validators}
    freq = sorted((counts[a], by_power[a]) for a in counts)
    assert freq[0][1] == 1 and freq[-1][1] == 3
    assert abs(freq[0][0] - 100) <= 2 and abs(freq[-1][0] - 300) <= 2


def test_hash_changes_with_membership_and_power():
    vs1 = ValidatorSet(_mk_vals([10, 10]))
    vs2 = ValidatorSet(_mk_vals([10, 11]))
    vs3 = ValidatorSet(_mk_vals([10, 10, 10]))
    assert vs1.hash() != vs2.hash() != vs3.hash()
    assert vs1.hash() == ValidatorSet(_mk_vals([10, 10])).hash()


def test_update_with_change_set():
    vals = _mk_vals([10, 20, 30])
    vs = ValidatorSet(vals)
    # change power of one, remove one, add one
    newcomer = _mk_vals([1, 1, 1, 40])[3]
    changes = [
        Validator(vals[0].address, vals[0].pub_key, 15),  # power change
        Validator(vals[1].address, vals[1].pub_key, 0),  # removal
        newcomer,  # addition
    ]
    vs.update_with_change_set(changes)
    assert len(vs) == 3
    assert vs.total_voting_power() == 15 + 30 + 40
    idx, v = vs.get_by_address(vals[0].address)
    assert v.voting_power == 15
    assert not vs.has_address(vals[1].address)
    # newcomer entered with the priority penalty (lowest priority)
    _, nv = vs.get_by_address(newcomer.address)
    assert nv.proposer_priority <= min(
        v.proposer_priority for v in vs.validators
    ) + 1


def test_update_rejects_bad_changes():
    vals = _mk_vals([10, 20])
    vs = ValidatorSet(vals)
    with pytest.raises(ValueError):
        vs.update_with_change_set(
            [Validator(b"\x99" * 20, vals[0].pub_key, 0)]
        )  # removing unknown
    with pytest.raises(ValueError):
        vs.update_with_change_set(
            [
                Validator(vals[0].address, vals[0].pub_key, 5),
                Validator(vals[0].address, vals[0].pub_key, 6),
            ]
        )  # duplicate


def test_empty_set_rejected():
    with pytest.raises(ValueError):
        ValidatorSet([])


# --- the rotation: the column path against the integer path (PR 37)
#
# Three voices that must agree on every set: the set's own rotation (int64
# numpy where its magnitudes allow, `column`), validator_set.rotate_integer
# (Python ints clipped at every step: the fallback), and _UpstreamSet below,
# the object-by-object walk this repo ran until PR 37, kept here word for
# word as upstream's IncrementProposerPriority has it.


class _UpstreamSet:
    def __init__(self, vals, proposer=None):
        self.validators = [v.copy() for v in vals]
        self.total = sum(v.voting_power for v in vals)
        self.proposer = proposer

    @staticmethod
    def _higher(a, b):
        if a.proposer_priority != b.proposer_priority:
            return a if a.proposer_priority > b.proposer_priority else b
        return a if a.address < b.address else b

    def rescale(self, diff_max):
        if diff_max <= 0:
            return
        prios = [v.proposer_priority for v in self.validators]
        diff = abs(max(prios) - min(prios))
        ratio = (diff + diff_max - 1) // diff_max
        if diff > diff_max:
            for v in self.validators:
                v.proposer_priority = VS._trunc_div(v.proposer_priority, ratio)

    def shift_by_avg(self):
        avg = sum(v.proposer_priority for v in self.validators) // len(
            self.validators)
        for v in self.validators:
            v.proposer_priority = VS._clip(v.proposer_priority - avg)

    def increment(self, times):
        self.rescale(2 * self.total)
        self.shift_by_avg()
        for _ in range(times):
            for v in self.validators:
                v.proposer_priority = VS._clip(
                    v.proposer_priority + v.voting_power)
            mostest = functools.reduce(self._higher, self.validators)
            mostest.proposer_priority = VS._clip(
                mostest.proposer_priority - self.total)
            self.proposer = mostest


@functools.lru_cache(maxsize=None)
def _keys(n):
    # any 32 bytes make a key with an address: nothing here verifies
    return [Ed25519PubKey(hashlib.sha256(b"rot%d" % i).digest())
            for i in range(n)]


POWERS = {
    "churn": lambda rng, n: [rng.randint(30, 100) for _ in range(n)],
    "equal": lambda rng, n: [10] * n,
    # MaxTotalVotingPower / n: a step of the rotation is 2^60, so int64
    # holds a few turns from zero and none from a priority of that size
    "cap": lambda rng, n: [
        VS.MAX_TOTAL_VOTING_POWER // n - rng.randint(0, 3) for _ in range(n)],
}
PRIORITIES = {
    "fresh": lambda rng, n, total: [0] * n,
    # spread beyond 2 x total, mostly negative: the rescale fires and
    # truncation differs from floor
    "spread": lambda rng, n, total: [
        rng.randint(-7 * total, 3 * total) | 1 for _ in range(n)],
    "ties": lambda rng, n, total: [rng.choice((-5, 0, 7)) for _ in range(n)],
    "edge": lambda rng, n, total: [
        rng.choice((1, -1)) * (((1 << 62) - 1) // n) for _ in range(n)],
    # nothing int64 arithmetic could sum or shift: the integer path's
    "huge": lambda rng, n, total: [
        rng.choice((VS.I64_MAX, VS.I64_MIN, VS.I64_MAX - 3, VS.I64_MIN + 9,
                    -(1 << 62), 0)) for _ in range(n)],
}


def _rows(n, powers, prios, seed=0):
    rng = random.Random(f"{seed}/{n}/{powers}/{prios}")
    pw = POWERS[powers](rng, n)
    pr = PRIORITIES[prios](rng, n, sum(pw))
    return [Validator(k.address(), k, w, p)
            for k, w, p in zip(_keys(n), pw, pr)]


def _same(vs: ValidatorSet, other) -> None:
    """`vs` against an _UpstreamSet or another ValidatorSet: priorities,
    proposer, hash, encoded bytes."""
    if isinstance(other, _UpstreamSet):
        rows = sorted(other.validators, key=VS._sort_key)
        other = ValidatorSet(rows, increment_first=False,
                             proposer_address=other.proposer.address)
    assert vs.priorities() == other.priorities()
    assert vs.get_proposer() == other.get_proposer()
    assert vs.hash() == other.hash()
    assert encode_validator_set(vs) == encode_validator_set(other)
    assert [v.proposer_priority for v in vs.validators] == vs.priorities()


def _integer_twin(vs: ValidatorSet, times: int) -> ValidatorSet:
    prios, winner = VS.rotate_integer(
        vs.priorities(), [m.voting_power for m in vs.members],
        [m.address for m in vs.members], vs.total_voting_power(), times)
    return ValidatorSet(
        [Validator(*m, p) for m, p in zip(vs.members, prios)],
        increment_first=False,
        proposer_address=vs.members[winner].address)


@pytest.mark.parametrize("prios", sorted(PRIORITIES))
@pytest.mark.parametrize("powers", sorted(POWERS))
@pytest.mark.parametrize("n", (1, 2, 4, 150, 1000))
def test_column_rotation_equals_integer_rotation(n, powers, prios):
    rows = _rows(n, powers, prios)
    source = ValidatorSet(rows, increment_first=False)
    before = source.priorities()
    assert before == [v.proposer_priority
                      for v in sorted(rows, key=VS._sort_key)]
    for times in (1, 3, 64):
        vs = source.copy()
        path = vs.increment_proposer_priority(times)
        _same(vs, _integer_twin(source, times))
        upstream = _UpstreamSet(rows)
        upstream.increment(times)
        _same(vs, upstream)
        if prios == "huge":
            assert path == "integer" or n == 1
        elif powers != "cap" and (prios != "edge" or n > 1):
            assert path == "column"
        # and on from there, a turn at a time, as a chain does
        for _ in range(3):
            vs = vs.copy()
            vs.increment_proposer_priority(1)
            upstream.increment(1)
        _same(vs, upstream)
    assert source.priorities() == before


def test_a_tie_for_the_highest_priority_goes_to_the_lower_address():
    rows = _rows(150, "churn", "fresh")
    for v in rows:
        v.voting_power = 50  # every turn starts from a tie of all or most
    vs = ValidatorSet(rows, increment_first=False)
    by_address = sorted(v.address for v in rows)
    for turn in range(150):
        assert vs.increment_proposer_priority(1) == "column"
        assert vs.get_proposer().address == by_address[turn]


def test_the_guard_sends_what_int64_cannot_hold_to_the_integer_path():
    k = _keys(3)
    # a sum of three priorities of 2^61 leaves 2^62
    big = [Validator(x.address(), x, 10, 1 << 61) for x in k]
    assert ValidatorSet(big, increment_first=False) \
        .increment_proposer_priority(1) == "integer"
    small = [Validator(x.address(), x, 10, 1 << 59) for x in k]
    assert ValidatorSet(small, increment_first=False) \
        .increment_proposer_priority(1) == "column"
    # 64 turns of a total near the cap leave int64; three do not
    cap = [Validator(x.address(), x, VS.MAX_TOTAL_VOTING_POWER // 3)
           for x in k]
    vs = ValidatorSet(cap, increment_first=False)
    assert vs.copy().increment_proposer_priority(3) == "column"
    assert vs.copy().increment_proposer_priority(64) == "integer"
    with pytest.raises(ValueError, match="outside int64"):
        ValidatorSet([Validator(k[0].address(), k[0], 10, 1 << 63)])


@pytest.mark.parametrize("prios", ("fresh", "spread", "huge"))
@pytest.mark.parametrize("n", (2, 4, 150, 1000))
def test_a_changed_membership_recentres_as_upstream_does(n, prios):
    """update_with_change_set scales and centres the new set's priorities
    through the same two paths."""
    rows = _rows(n, "churn", prios)
    vs = ValidatorSet(rows, increment_first=False)
    newcomer = Ed25519PubKey(hashlib.sha256(b"newcomer").digest())
    changes = [Validator(newcomer.address(), newcomer, 77),
               Validator(rows[0].address, rows[0].pub_key, 0),
               Validator(rows[1].address, rows[1].pub_key, 31)]
    mirror = ValidatorSet(rows, increment_first=False)
    vs.update_with_change_set(changes)
    # the mirror: upstream's steps by hand on rows of Python ints
    kept = {v.address: v.copy() for v in rows[1:]}
    kept[rows[1].address].voting_power = 31
    tvp = sum(v.voting_power for v in rows) + 77 + (31 - rows[1].voting_power)
    kept[newcomer.address()] = Validator(
        newcomer.address(), newcomer, 77, -(tvp + (tvp >> 3)))
    up = _UpstreamSet(kept.values())
    up.rescale(2 * up.total)
    up.shift_by_avg()
    want = {v.address: v.proposer_priority for v in up.validators}
    assert {v.address: v.proposer_priority for v in vs.validators} == want
    assert mirror.priorities() == [v.proposer_priority for v in sorted(
        rows, key=VS._sort_key)]
    up.increment(1)
    vs.increment_proposer_priority(1)
    _same(vs, up)


# --- what a copy shares and what it owns


def test_rotating_a_copy_leaves_the_source_as_it_was():
    source = ValidatorSet(_rows(150, "churn", "spread"))
    before = (source.priorities(), source.get_proposer(),
              encode_validator_set(source))
    rows_before = source.validators
    copy = source.copy()
    copy.increment_proposer_priority(3)
    assert copy.priorities() != before[0]
    assert (source.priorities(), source.get_proposer(),
            encode_validator_set(source)) == before
    assert source.validators is rows_before  # its rows were not rebuilt
    assert [v.proposer_priority for v in copy.validators] == copy.priorities()


def test_a_copy_shares_the_membership_and_all_derived_from_it():
    source = ValidatorSet(_rows(150, "churn", "fresh"))
    cols = source.key_columns()
    copy = source.copy()
    copy.increment_proposer_priority(1)
    again = copy.copy()
    again.increment_proposer_priority(64)
    for vs in (copy, again):
        assert vs.members is source.members
        assert vs.key_columns() is cols
        assert vs.hash() == source.hash()
    # asked of a copy first, the source has it too
    fresh = ValidatorSet(_rows(4, "equal", "fresh"))
    assert fresh.copy().key_columns() is fresh.key_columns()


def test_update_with_change_set_on_a_copy_leaves_the_source_whole():
    rows = _rows(150, "churn", "ties")
    source = ValidatorSet(rows)
    before = (source.members, source.hash(), source.key_columns(),
              source.priorities(), source.total_voting_power(),
              encode_validator_set(source))
    copy = source.copy()
    copy.update_with_change_set(
        [Validator(rows[3].address, rows[3].pub_key, 0),
         Validator(rows[4].address, rows[4].pub_key, 99)])
    copy.increment_proposer_priority(1)
    assert len(copy) == 149 and copy.hash() != before[1]
    assert not copy.has_address(rows[3].address)
    assert source.has_address(rows[3].address)
    assert copy.key_columns() is not before[2]
    assert copy.total_voting_power() != before[4]
    after = (source.members, source.hash(), source.key_columns(),
             source.priorities(), source.total_voting_power(),
             encode_validator_set(source))
    assert after[0] is before[0] and after[2] is before[2]
    assert after[1:2] + after[3:] == before[1:2] + before[3:]


def test_a_copy_of_a_frozen_set_is_mutable_and_the_frozen_one_still_raises():
    frozen = ValidatorSet(_rows(4, "churn", "fresh")).freeze()
    before = frozen.priorities()
    copy = frozen.copy()
    copy.increment_proposer_priority(1)
    copy.update_with_change_set(
        [Validator(frozen.members[0].address, frozen.members[0].pub_key, 5)])
    with pytest.raises(RuntimeError, match="frozen"):
        frozen.increment_proposer_priority(1)
    with pytest.raises(RuntimeError, match="frozen"):
        frozen.update_with_change_set(
            [Validator(frozen.members[0].address, frozen.members[0].pub_key, 5)])
    assert frozen.priorities() == before
    assert frozen.get_proposer() is not None  # a read, not a mutation


# --- 64 blocks through _update_state


def _state_after_64_blocks():
    """1000 validators of powers 30-100, 64 blocks through
    BlockExecutor._update_state, updates in blocks 10, 20, ... as the churn
    cell has them (by turns a join with the lowest leaving, and 5
    members re-powered)."""
    from types import SimpleNamespace

    from cometbft_tpu.abci.types import FinalizeBlockResponse, ValidatorUpdate
    from cometbft_tpu.state.execution import BlockExecutor, make_genesis_state
    from cometbft_tpu.types import BlockID, Timestamp

    rng = random.Random(37)
    state = make_genesis_state(
        "pin-rotation", ValidatorSet(_rows(1000, "churn", "fresh", seed=37)))
    ex = BlockExecutor(None, backend="cpu")
    paths = []
    for h in range(1, 65):
        updates = []
        if h % 20 == 10:
            key = hashlib.sha256(b"joins%d" % h).digest()
            lowest = state.next_validators.members[-1]
            updates = [ValidatorUpdate(key, power=rng.randint(30, 100)),
                       ValidatorUpdate(lowest.pub_key.bytes(), power=0)]
        elif h % 20 == 0:
            updates = [
                ValidatorUpdate(m.pub_key.bytes(), power=rng.randint(30, 100))
                for m in rng.sample(state.next_validators.members, 5)]
        block = SimpleNamespace(header=SimpleNamespace(
            height=h, time=Timestamp.from_unix_ns(1_700_000_000_000 + h)))
        out = ex._update_state(
            state, BlockID(hashlib.sha256(b"block%d" % h).digest()), block,
            FinalizeBlockResponse(validator_updates=updates,
                                  app_hash=b"app%d" % h))
        state, path = out if isinstance(out, tuple) else (out, None)
        paths.append(path)
    return state, paths


def test_64_blocks_through_update_state_encode_to_the_parents_bytes():
    """The digest of State.encode() read on the tree before PR 37, whose
    sets were 1000 Validator objects a copy."""
    state, paths = _state_after_64_blocks()
    assert set(paths) == {"column"}
    assert state.last_height_validators_changed == 62
    assert hashlib.sha256(state.encode()).hexdigest() == PARENT_STATE_DIGEST
    assert State.decode(state.encode()).encode() == state.encode()
