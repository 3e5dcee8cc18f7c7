"""Replay across changes of the validator set: a generated chain whose set
members join, leave and are re-powered by `val:` transactions, on a schedule
(utils/factories.ValsetChurn: every so many heights, by turns a join and a
re-powering),
replayed batched and full, against the plain reference that evolves the set
itself and judges one commit lane by lane (benchmark/reference/
valset_replay.py: it shares no code with the program)."""

import functools
import hashlib
import json
import os
import re
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.reference import valset_replay as ref  # noqa: E402
from cometbft_tpu.abci.client import AppConns  # noqa: E402
from cometbft_tpu.abci.kvstore import KVStoreApp  # noqa: E402
from cometbft_tpu.blocksync import ReplayEngine  # noqa: E402
from cometbft_tpu.state.execution import (  # noqa: E402
    BlockExecutor,
    BlockValidationError,
)
from cometbft_tpu.storage import BlockStore, MemKV  # noqa: E402
from cometbft_tpu.types.validation import (  # noqa: E402
    CommitError,
    ErrInvalidSignature,
    ErrNotEnoughVotingPower,
    commit_lanes,
)
from cometbft_tpu.utils import factories as fx  # noqa: E402
from cometbft_tpu.utils import trace  # noqa: E402
from cometbft_tpu.types.validator_set import (  # noqa: E402
    MAX_TOTAL_VOTING_POWER,
)
from cometbft_tpu.utils.metrics import (  # noqa: E402
    blocksync_metrics,
    state_metrics,
)

CHAIN = "churn-chain"
N, BLOCKS, POWER = 8, 24, 1_000_000
SEEDS = (11, 12, 13)
EVERY = {11: 1, 12: 2, 13: 3}  # updates in every block, every 2nd, every 3rd


@functools.lru_cache(maxsize=None)
def churn_chain(seed: int, every: int | None = None):
    """(store, final state, genesis state, the churn drawn): powers of 30
    to 100, two members a re-powering."""
    spare = fx.make_signers(BLOCKS, seed=seed + 7)
    churn = fx.ValsetChurn(spare, seed=seed, every=every or EVERY[seed],
                           repowered=2, power_lo=30, power_hi=100)
    store, final, genesis, _ = fx.make_chain(
        BLOCKS, n_validators=N, chain_id=CHAIN, seed=seed, backend="cpu",
        powers=churn.genesis_powers(N), extra_txs=churn, spare_signers=spare)
    return store, final, genesis, churn


def members_of(vals):
    return [(v.pub_key.bytes(), v.voting_power) for v in vals.validators]


def slots_of(commit):
    return [(int(cs.block_id_flag), cs.validator_address,
             b"" if cs.is_absent() else commit.vote_sign_bytes(CHAIN, i),
             cs.signature) for i, cs in enumerate(commit.signatures)]


def reference_sets(store, genesis, first=1):
    tip = store.height()
    ups = {h: ref.val_updates(store.load_block(h).data.txs)
           for h in range(first, tip + 1)}
    g = members_of(genesis.validators)
    return ref.evolve(first, g, g, ups, tip), ref.last_changed(first, ups, tip)


def replay(store, genesis, mode, window=64, depth=None, to_height=None):
    app = KVStoreApp()
    engine = ReplayEngine(store, BlockExecutor(AppConns(app), backend="cpu"),
                          verify_mode=mode, window=window, backend="cpu",
                          depth=depth)
    state, stats = engine.run(genesis.copy(), to_height=to_height)
    return state, stats, app


# ---------------------------------------------------------------------
# the generator against the reference


@pytest.mark.parametrize("seed", SEEDS)
def test_generated_hashes_equal_the_references_evolved_set(seed):
    store, final, genesis, churn = churn_chain(seed)
    assert churn.joins >= 4 and churn.repowerings >= 4
    sets, changed = reference_sets(store, genesis)
    for h in range(1, BLOCKS + 1):
        hdr = store.load_block(h).header
        assert hdr.validators_hash == ref.set_hash(sets[h]), h
        assert hdr.next_validators_hash == ref.set_hash(sets[h + 1]), h
    assert final.validators.hash() == ref.set_hash(sets[BLOCKS + 1])
    assert final.next_validators.hash() == ref.set_hash(sets[BLOCKS + 2])
    assert final.last_height_validators_changed == changed
    assert all(len(s) == N for s in sets.values())
    # the order rule, and that a re-powering re-orders the set
    assert members_of(final.validators) == sets[BLOCKS + 1]
    assert [p for p, _ in sets[1]] != [p for p, _ in sets[BLOCKS + 1]]


def test_repower_leave_and_join_in_one_block_take_force_two_heights_on():
    spare = fx.make_signers(6, seed=28)

    def all_three(height, state):
        m = state.next_validators.validators
        return [fx.val_tx(m[0].pub_key.bytes(), m[0].voting_power + height),
                fx.val_tx(m[-1].pub_key.bytes(), 0),
                fx.val_tx(spare[height - 1].pub_bytes, POWER)]

    store, final, genesis, _ = fx.make_chain(
        6, n_validators=N, chain_id=CHAIN, seed=21, backend="cpu",
        powers=[POWER] * N, extra_txs=all_three, spare_signers=spare)
    sets, changed = reference_sets(store, genesis)
    assert sets[1] == sets[2] != sets[3]  # block 1's updates: in force at 3
    for h in range(1, 7):
        assert len(ref.val_updates(store.load_block(h).data.txs)) == 3
        keys_now = {p for p, _ in sets[h + 1]}
        keys_next = {p for p, _ in sets[h + 2]}
        assert len(keys_now - keys_next) == 1 == len(keys_next - keys_now)
        assert store.load_block(h).header.validators_hash == ref.set_hash(sets[h])
    assert changed == 8 == final.last_height_validators_changed
    state, _, _ = replay(store, genesis, "batched", window=4)
    assert state.validators.hash() == ref.set_hash(sets[7])


def test_make_chain_without_the_new_arguments_writes_the_same_bytes():
    """The digest of every key and value of the store, read on the tree
    before make_chain learnt of validator updates."""
    kv = MemKV()
    _, final, _, _ = fx.make_chain(
        6, n_validators=4, chain_id="pin-chain", seed=0,
        block_store=BlockStore(kv), nil_votes={3: {1}})
    h = hashlib.sha256()
    for k, v in sorted(kv._d.items()):
        h.update(len(k).to_bytes(4, "big") + k + len(v).to_bytes(4, "big") + v)
    assert h.hexdigest() == (
        "b6efd22a2edca56dec0859d2c39e2f0dc1ff8de327eca38a3b10c1730cb076a5")
    assert final.app_hash.hex() == (
        "3c5db0eb14dbf3a4abb8af3c1643d12c32638319f78e171088537cea53a27da1")


def test_the_seed_draws_who_and_how_much_and_the_schedule_stands():
    """Two seeds' chains change at the same heights, by turns a join (the
    lowest leaves) and a re-powering; one seed's chain is the same twice."""
    tables = {}
    for seed in (14, 12):
        store, _, _, churn = churn_chain(seed, 2)
        tables[seed] = {h: ref.val_updates(store.load_block(h).data.txs)
                        for h in range(1, BLOCKS + 1)}
        assert (churn.joins, churn.repowerings) == (6, 6)
    for h in range(1, BLOCKS + 1):
        a, b = tables[12][h], tables[14][h]
        assert len(a) == len(b) == (0 if h % 2 else 2), h
        if h % 4 == 2:  # a join: one leaves with power 0, one key is new
            assert a[0][1] == b[0][1] == 0
        assert all(30 <= power <= 100 for _, power in a + b if power)
    assert tables[12] != tables[14]
    twin = churn_chain.__wrapped__(12, 2)[0]
    assert all(twin.load_block(h).hash() == store.load_block(h).hash()
               for h in (1, BLOCKS))


# ---------------------------------------------------------------------
# batched = full = reference


@functools.lru_cache(maxsize=None)
def full_replay(seed: int):
    store, _, genesis, _ = churn_chain(seed)
    state, stats, _ = replay(store, genesis, "full")
    return state, stats


@pytest.mark.parametrize("depth", (1, 2, 5))
@pytest.mark.parametrize("window", (1, 4, 64))
@pytest.mark.parametrize("seed", SEEDS)
def test_batched_equals_full_equals_reference(seed, window, depth):
    store, final, genesis, _ = churn_chain(seed)
    sets, changed = reference_sets(store, genesis)
    state, stats, app = replay(store, genesis, "batched", window, depth)
    full, full_stats = full_replay(seed)
    assert stats.blocks == full_stats.blocks == BLOCKS == app.height
    assert state.app_hash == full.app_hash == final.app_hash
    assert (state.validators.hash() == full.validators.hash()
            == ref.set_hash(sets[BLOCKS + 1]))
    assert (state.next_validators.hash() == full.next_validators.hash()
            == ref.set_hash(sets[BLOCKS + 2]))
    assert (state.last_height_validators_changed == changed
            == full.last_height_validators_changed)
    # every signature of the chain at least once (the commits of heights
    # 1..tip-1 ride in the blocks, the tip's is stored); the serial mode
    # verifies each once
    assert full_stats.sigs_verified == BLOCKS * N <= stats.sigs_verified


@pytest.mark.parametrize("seed", SEEDS)
def test_the_reference_accepts_every_commit_against_its_own_set(seed):
    store, _, genesis, _ = churn_chain(seed)
    sets, _ = reference_sets(store, genesis)
    for h in range(1, BLOCKS):
        commit = store.load_block(h + 1).last_commit
        assert ref.judge(sets[h], slots_of(commit)) == ("accepted",), h
        # where the order moved, the next height's set does not do
        if [p for p, _ in sets[h + 1]] != [p for p, _ in sets[h]]:
            assert ref.judge(sets[h + 1], slots_of(commit))[0] == "address", h


# ---------------------------------------------------------------------
# the three refusals


A = 5  # the change rides in block A and is in force at A + 2
IDX_BAD = 1


def _at(height, make):
    return lambda h, state: make(state) if h == height else []


def _mid(state):
    return state.next_validators.validators[N // 2]


@functools.lru_cache(maxsize=None)
def refused_chain(kind: str):
    spare = fx.make_signers(2, seed=99)
    if kind == "flipped_signature":
        extra = _at(A, lambda st: [fx.val_tx(
            _mid(st).pub_key.bytes(), _mid(st).voting_power + POWER)])
        kw = {"corrupt_sig": (A + 2, IDX_BAD)}
    elif kind == "old_set_signs":
        extra = _at(A, lambda st: [
            fx.val_tx(st.next_validators.validators[-1].pub_key.bytes(), 0),
            fx.val_tx(spare[0].pub_bytes, POWER)])
        kw = {"stale_set_at": A + 2}
    else:  # one member at 600 x: it and one other of 8 vote nil
        extra = _at(A, lambda st: [fx.val_tx(
            _mid(st).pub_key.bytes(), 600 * POWER)])
        kw = {"nil_votes": {A + 2: {0, 1}}}
    store, _, genesis, _ = fx.make_chain(
        A + 5, n_validators=N, chain_id=CHAIN, seed=31, backend="cpu",
        powers=[POWER] * N, extra_txs=extra, spare_signers=spare,
        verify_last_commit=False, **kw)
    return store, genesis


@pytest.mark.parametrize("mode", ("batched", "full"))
@pytest.mark.parametrize("kind, error, verdict", [
    ("flipped_signature", ErrInvalidSignature, ("signature", IDX_BAD)),
    ("old_set_signs", ErrInvalidSignature, ("address",)),
    ("nil_votes_new_powers", ErrNotEnoughVotingPower, ("power",)),
])
def test_refusals_at_the_first_height_of_a_new_set(kind, error, verdict, mode):
    store, genesis = refused_chain(kind)
    sets, _ = reference_sets(store, genesis)
    assert sets[A + 2] != sets[A + 1] == sets[1]
    bad = store.load_block(A + 3).last_commit  # the commit of height A + 2
    got = ref.judge(sets[A + 2], slots_of(bad))
    assert got[:len(verdict)] == verdict
    if kind == "nil_votes_new_powers":
        old = dict(sets[A + 1])  # 6 of 8 would do under the old powers
        by_old = sum(old[p] for (p, _), s in zip(sets[A + 2], slots_of(bad))
                     if s[0] == ref.COMMIT)
        assert 3 * by_old > 2 * sum(old.values())
        assert 3 * got[1] < 2 * sum(p for _, p in sets[A + 2])
    app = KVStoreApp()
    engine = ReplayEngine(store, BlockExecutor(AppConns(app), backend="cpu"),
                          verify_mode=mode, window=4, backend="cpu")
    if (mode, kind) == ("full", "old_set_signs"):
        # upstream's serial loop: VerifyCommitLight stops at +2/3, before
        # the slot where the joiner stands; the full check of the same
        # commit refuses block A + 3, of which it is the LastCommit
        with pytest.raises(BlockValidationError, match="address mismatch"):
            engine.run(genesis.copy())
        assert app.height == A + 2
        return
    with pytest.raises(CommitError) as exc:
        engine.run(genesis.copy())
    assert type(exc.value) is error
    msg = str(exc.value)
    if mode == "batched" and kind == "flipped_signature":
        # the refused window starts at A + 2: block A + 2's LastCommit
        # (height A + 1) first, then one commit a height, N lanes each
        lane = int(re.search(r"lane (\d+)", msg).group(1))
        assert (A + 1 + lane // N, lane % N) == (A + 2, IDX_BAD)
    elif kind == "flipped_signature":
        assert f"index {IDX_BAD}" in msg
    elif mode == "batched":
        assert f"height {A + 2}" in msg
    # nothing of the refused window (batched) or at the refused height
    # (full) is applied; everything before it is
    assert app.height == A + 1


def test_a_member_that_left_cannot_sign_and_one_that_joined_must():
    """The honest twin of the rotation: replay accepts it, and the set of
    A + 2 holds the joiner where the one that left stood."""
    spare = fx.make_signers(2, seed=99)
    store, final, genesis, _ = fx.make_chain(
        A + 5, n_validators=N, chain_id=CHAIN, seed=31, backend="cpu",
        powers=[POWER] * N, spare_signers=spare,
        extra_txs=_at(A, lambda st: [
            fx.val_tx(st.next_validators.validators[-1].pub_key.bytes(), 0),
            fx.val_tx(spare[0].pub_bytes, POWER)]))
    sets, _ = reference_sets(store, genesis)
    joined = {p for p, _ in sets[A + 2]} - {p for p, _ in sets[A + 1]}
    assert joined == {spare[0].pub_bytes}
    for mode in ("batched", "full"):
        state, _, _ = replay(store, genesis, mode, window=4)
        assert state.app_hash == final.app_hash
    commit = store.load_block(A + 3).last_commit
    assert ref.judge(sets[A + 2], slots_of(commit)) == ("accepted",)
    assert ref.judge(sets[A + 1], slots_of(commit))[0] == "address"


# ---------------------------------------------------------------------
# the columnar entry, the spans and the counters


def _traced(tmp_path, fn):
    sink = str(tmp_path / "spans.jsonl")
    trace.configure(sink)
    try:
        out = fn()
        trace.flush()
        with open(sink, encoding="utf-8") as f:
            recs = [json.loads(line) for line in f]
    finally:
        trace.disable()
    return out, recs


def _of(recs, name):
    return [r for r in recs if r["name"] == name]


@pytest.mark.parametrize("depth", (1, 2))
def test_one_set_change_span_and_one_counter_step_a_boundary(tmp_path, depth):
    store, final, genesis, _ = churn_chain(SEEDS[0])
    sets, _ = reference_sets(store, genesis)
    boundaries = [h for h in range(2, BLOCKS + 1) if sets[h] != sets[h - 1]]
    assert len(boundaries) >= 5
    (state, stats, _), recs = _traced(
        tmp_path, lambda: replay(store, genesis, "batched", 4, depth))
    assert state.app_hash == final.app_hash
    spans = _of(recs, "blocksync.set_change")
    assert [r["height"] for r in spans] == boundaries
    assert {r["reason"] for r in spans} == {"set_change"}
    (root,) = _of(recs, "blocksync.replay")
    for r in spans:
        assert r["parent"] == root["id"] and r["dur_ms"] >= 0
        assert "self_ms" not in r  # open_span: off the thread's stack
    assert dict(blocksync_metrics().set_change_total.values()) == {
        ("set_change",): float(len(boundaries))}
    # the windows: each ends at a change, at the window's size or at the tip
    loads = [r for r in _of(recs, "blocksync.window_load") if r["blocks"]]
    assert sum(r["blocks"] for r in loads) == BLOCKS
    assert {r["end"] for r in _of(recs, "blocksync.window_load")} <= {
        "full", "set_change", "tip"}
    for r in loads:
        nxt = r["window"] + r["blocks"]
        if r["blocks"] < min(4, BLOCKS - r["window"] + 1):
            assert r["end"] == "set_change" and nxt in boundaries, r
        else:
            assert r["end"] == ("tip" if nxt > BLOCKS else "full"), r
    snap = blocksync_metrics().window_blocks.snapshot()
    assert sum(v["count"] for v in snap.values()) == len(loads)
    # the columnar entry holds on every re-ordered set
    fills = _of(recs, "blocksync.window_fill")
    assert all(r["columnar"] == r["commits"] for r in fills)
    assert sum(r["lanes"] for r in fills) == stats.sigs_verified
    # one state.valset_update a block that carries updates
    ups = _of(recs, "state.valset_update")
    assert [r["height"] for r in ups] == [
        h for h in range(1, BLOCKS + 1)
        if ref.val_updates(store.load_block(h).data.txs)]
    assert all(r["changes"] == 2 for r in ups)


STAGES = ("validate_ms", "finalize_ms", "update_state_ms", "commit_ms",
          "save_events_ms")


@pytest.mark.parametrize("mode,depth", (("batched", 1), ("batched", 2),
                                        ("full", None)))
def test_every_apply_block_span_says_how_the_proposer_rotated(
        tmp_path, mode, depth):
    """update_state_ms (the next state: validator updates, the rotation)
    stands beside the four older stages, the five within the span, and
    the rotations counter says the same as the spans."""
    store, final, genesis, _ = churn_chain(SEEDS[2])  # updates every 3rd block
    counter = state_metrics().valset_rotation_total
    before = counter.values().get(("column",), 0.0)  # making the chain counts
    (state, _, _), recs = _traced(
        tmp_path, lambda: replay(store, genesis, mode, 4, depth))
    assert state.app_hash == final.app_hash
    spans = _of(recs, "state.apply_block")
    assert [r["height"] for r in spans] == list(range(1, BLOCKS + 1))
    for r in spans:
        assert r["rotation"] == "column", r
        assert all(r[f] >= 0 for f in STAGES), r
        # each stage is rounded to a microsecond, as the span is
        assert sum(r[f] for f in STAGES) <= r["dur_ms"] + 0.003, r
    # blocks with and without validator updates alike
    changed = {r["parent"] for r in _of(recs, "state.valset_update")}
    assert changed and changed < {r["id"] for r in spans}
    assert counter.values() == {("column",): before + BLOCKS}


def test_the_rotations_counter_names_a_set_that_fell_back(tmp_path):
    """A set whose total power is at the cap has priorities that int64
    cannot sum: it rotates on Python ints, the chain replays to the same
    app hash, and state_metrics() shows the fallback without a trace."""
    store, final, genesis, _ = fx.make_chain(
        6, n_validators=8, chain_id=CHAIN, backend="cpu",
        powers=[MAX_TOTAL_VOTING_POWER // 8] * 8)
    before = state_metrics().valset_rotation_total.values()  # the generator's
    (state, _, _), recs = _traced(
        tmp_path, lambda: replay(store, genesis, "batched", 4, 2))
    assert state.app_hash == final.app_hash
    assert state.validators.hash() == final.validators.hash()
    assert state.encode() == final.encode()
    paths = [r["rotation"] for r in _of(recs, "state.apply_block")]
    assert len(paths) == 6 and "integer" in paths
    after = state_metrics().valset_rotation_total.values()
    assert {k: after[k] - before.get(k, 0.0) for k in after} == {
        (p,): float(paths.count(p)) for p in set(paths)}


@pytest.mark.parametrize("depth", (1, 2))
def test_no_set_change_span_on_a_constant_chain(tmp_path, depth):
    store, final, genesis, _ = fx.make_chain(
        8, n_validators=4, chain_id=CHAIN, backend="cpu")
    (state, _, _), recs = _traced(
        tmp_path, lambda: replay(store, genesis, "batched", 3, depth))
    assert state.app_hash == final.app_hash
    assert not _of(recs, "blocksync.set_change")
    assert not _of(recs, "state.valset_update")
    assert dict(blocksync_metrics().set_change_total.values()) == {}
    assert [r["end"] for r in _of(recs, "blocksync.window_load")] == [
        "full", "full", "tip"]


def test_a_window_cut_short_by_a_missing_block_says_so(tmp_path):
    store, _, genesis, _ = fx.make_chain(
        5, n_validators=4, chain_id=CHAIN, backend="cpu")
    engine = ReplayEngine(store, BlockExecutor(AppConns(KVStoreApp()),
                                               backend="cpu"),
                          window=4, backend="cpu")
    _, recs = _traced(tmp_path, lambda: engine._load_window(
        3, 9, genesis.validators.hash()))
    (r,) = _of(recs, "blocksync.window_load")
    assert (r["blocks"], r["end"]) == (3, "missing")


def test_commit_lanes_stays_columnar_on_a_reordered_set():
    store, _, genesis, _ = churn_chain(SEEDS[1])
    order = {}

    class Executor(BlockExecutor):
        def apply_block_preverified(self, state, block_id, block):
            order[block.header.height] = state.validators
            return super().apply_block_preverified(state, block_id, block)

    ReplayEngine(store, Executor(AppConns(KVStoreApp()), backend="cpu"),
                 window=4, backend="cpu").run(genesis.copy())
    first = [v.address for v in order[1].validators]
    seen = 0
    for h in range(1, BLOCKS):
        vals = order[h]
        commit = store.load_block(h + 1).last_commit
        lanes = commit_lanes(CHAIN, vals, commit, True)
        assert not isinstance(lanes, str), (h, lanes)
        assert lanes.n == N and lanes.power == vals.total_voting_power()
        seen += [v.address for v in vals.validators] != first
    assert seen >= BLOCKS // 2
