"""Replay of blocks that carry upstream's QA load (utils/factories.
LoadtimeTxs: `a=` + the hex of test/loadtime's Payload, 1,024 bytes a
transaction) through the node's own executor with its state store on sqlite,
against the plain reference that recomputes each block's data_hash, the
application's dict and the next header's last_results_hash from the
transactions alone (benchmark/reference/kvstore_replay.py: it shares no code
with the program)."""

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

import state_encoding_reference as enc_ref  # noqa: E402
from benchmark.reference import kvstore_replay as ref  # noqa: E402
from cometbft_tpu.abci import wire  # noqa: E402
from cometbft_tpu.abci.client import AppConns  # noqa: E402
from cometbft_tpu.abci.kvstore import KVStoreApp  # noqa: E402
from cometbft_tpu.blocksync import ReplayEngine  # noqa: E402
from cometbft_tpu.state.execution import (  # noqa: E402
    BlockExecutor,
    BlockValidationError,
    results_hash,
)
from cometbft_tpu.storage import BlockStore, MemKV, StateStore, open_kv  # noqa: E402
from cometbft_tpu.storage.blockstore import _key_block  # noqa: E402
from cometbft_tpu.types.block import block_id_for  # noqa: E402
from cometbft_tpu.types.validation import (  # noqa: E402
    CommitError,
    ErrInvalidBlockID,
    ErrInvalidSignature,
)
from cometbft_tpu.utils import factories as fx  # noqa: E402
from cometbft_tpu.utils import trace  # noqa: E402
from cometbft_tpu.utils.metrics import (  # noqa: E402
    blocksync_metrics,
    state_metrics,
)

CHAIN = "loaded-chain"
N, BLOCKS, WINDOW, TXS, SIZE = 8, 16, 4, 12, 1024
SEEDS = (41, 42, 43)


@functools.lru_cache(maxsize=None)
def loaded_chain(seed: int, **kw):
    """(key-value store of the blocks, final state, genesis state, the
    generator's app)."""
    kv, app = MemKV(), KVStoreApp()
    _, final, genesis, _ = fx.make_chain(
        BLOCKS, n_validators=N, chain_id=CHAIN, seed=seed, backend="cpu",
        txs_per_block=0, extra_txs=fx.LoadtimeTxs(seed, per_block=TXS,
                                                  size=SIZE),
        app=app, block_store=BlockStore(kv), **kw)
    return kv, final, genesis, app


class Recording(BlockExecutor):
    """Keeps the app's store, the state and the app's response after
    every applied height."""

    def __init__(self, app, **kw):
        super().__init__(AppConns(app), backend="cpu", **kw)
        self.kv_app, self.stores, self.states, self.resps = app, {}, {}, {}
        self.event_handlers.append(
            lambda block, resp: self.resps.__setitem__(
                block.header.height, resp))

    def apply_block_preverified(self, state, block_id, block):
        state = super().apply_block_preverified(state, block_id, block)
        self.stores[block.header.height] = dict(self.kv_app.store)
        self.states[block.header.height] = state
        return state


def replay(kv, genesis, path=None, mode="batched", depth=None, to_height=None):
    """Replays the blocks of `kv` as a node would: the executor holds a
    state store on sqlite at `path` (none where path is None). Returns
    (state, stats, app, executor, the state store's key-value handle)."""
    app = KVStoreApp()
    skv = open_kv(path) if path else None
    if skv is not None:
        # a fresh node's bootstrap (state/handshake.py): the genesis state
        # and the sets of heights 1 and 2
        StateStore(skv).save(genesis)
    ex = Recording(app, state_store=StateStore(skv) if skv else None)
    engine = ReplayEngine(BlockStore(kv), ex, verify_mode=mode, window=WINDOW,
                          backend="cpu", depth=depth)
    state, stats = engine.run(genesis.copy(), to_height=to_height)
    return state, stats, app, ex, skv


def reference_of(kv, keep=()):
    store, r = BlockStore(kv), ref.Replay(keep=keep)
    for h in range(1, store.height() + 1):
        blk = store.load_block(h)
        r.block(h, blk.data.txs, blk.header.data_hash,
                blk.header.last_results_hash)
    return r


# ---------------------------------------------------------------------
# the generator


@pytest.mark.parametrize("size", (256, 1024, 4096))
@pytest.mark.parametrize("seed", SEEDS)
def test_a_loadtime_transaction_has_its_size_and_parses_back(seed, size):
    gen = fx.LoadtimeTxs(seed, per_block=TXS, size=size, connections=2,
                         rate=5)
    txs = gen.txs(3)
    assert len(txs) == TXS and {len(tx) for tx in txs} == {size}
    assert len(set(txs)) == TXS
    for i, tx in enumerate(txs):
        assert tx.startswith(b"a=") and KVStoreApp._parse(tx)[0] == b"a"
        p = fx.LoadtimeTxs.parse(tx)
        assert (p["connections"], p["rate"], p["size"]) == (2, 5, size)
        assert len(p["id"]) == 16 and p["id"] == fx.LoadtimeTxs.parse(
            txs[0])["id"]
        # sent at 5 tx/s in the height's own second and the ones behind it
        assert p["time"].unix_ns() == (
            gen.GENESIS_S + 3 + i // 5) * 10**9 + (i % 5) * 200_000_000
        assert len(tx) == 2 + 2 * (len(tx) - 2) // 2  # hex of the payload
    later = fx.LoadtimeTxs.parse(gen.txs(4)[0])
    assert later["time"].seconds == fx.LoadtimeTxs.parse(
        txs[0])["time"].seconds + 1
    assert later["id"] != fx.LoadtimeTxs.parse(txs[0])["id"]


@pytest.mark.parametrize("seed", SEEDS)
def test_the_generator_is_a_function_of_its_seed(seed):
    a = fx.LoadtimeTxs(seed, per_block=TXS)
    assert a.txs(7) == fx.LoadtimeTxs(seed, per_block=TXS).txs(7)
    assert a(7, None) == a.txs(7) != a.txs(8)
    assert a.txs(7) != fx.LoadtimeTxs(seed + 1, per_block=TXS).txs(7)
    # a longer block starts with the shorter one's transactions but for
    # nothing: each draws its padding in turn from the height's stream
    assert fx.LoadtimeTxs(seed, per_block=TXS + 1).txs(7)[:TXS] == a.txs(7)


@pytest.mark.parametrize("size", (63, 64, 330))
def test_sizes_no_padding_reaches_are_refused(size):
    with pytest.raises(ValueError):
        fx.LoadtimeTxs(1, per_block=1, size=size).txs(1)


def test_a_loaded_chain_carries_only_the_load():
    kv, final, _, app = loaded_chain(SEEDS[0])
    store = BlockStore(kv)
    gen = fx.LoadtimeTxs(SEEDS[0], per_block=TXS, size=SIZE)
    for h in (1, 9, BLOCKS):
        assert store.load_block(h).data.txs == gen.txs(h)
    # one key, as loadtime writes it: the app's state does not grow
    assert app.store == {b"a": gen.txs(BLOCKS)[-1][2:]}
    assert final.app_hash == app.app_hash


# ---------------------------------------------------------------------
# batched replay, the state store on sqlite


@functools.lru_cache(maxsize=None)
def replayed(seed: int, tmp: str):
    kv, _, genesis, _ = loaded_chain(seed)
    path = os.path.join(tmp, f"state-{seed}.db")
    out = replay(kv, genesis, path)
    out[4].close()
    return out[:4] + (path,)


@pytest.fixture(scope="module")
def tmp(tmp_path_factory):
    return str(tmp_path_factory.mktemp("loaded"))


@pytest.mark.parametrize("mode,depth", (("batched", 1), ("batched", 2),
                                        ("batched", 5), ("full", None)))
@pytest.mark.parametrize("seed", SEEDS)
def test_replay_with_a_state_store_reaches_the_generators_state(
        tmp_path, seed, mode, depth):
    kv, final, genesis, gen_app = loaded_chain(seed)
    state, stats, app, _, skv = replay(
        kv, genesis, str(tmp_path / "state.db"), mode, depth)
    skv.close()
    assert stats.blocks == BLOCKS == app.height
    assert state.app_hash == final.app_hash == app.app_hash
    assert app.store == gen_app.store
    assert state.encode() == final.encode()
    want = BLOCKS * N if mode == "full" else (BLOCKS - 1 + BLOCKS // WINDOW) * N
    assert stats.sigs_verified == want


@pytest.mark.parametrize("what", ("store", "data_hash", "last_results_hash",
                                  "counts"))
@pytest.mark.parametrize("seed", SEEDS)
def test_the_reference_agrees_with_the_program_at_every_height(
        tmp, seed, what):
    kv, _, _, _ = loaded_chain(seed)
    _, _, _, ex, _ = replayed(seed, tmp)
    r = reference_of(kv, keep=range(1, BLOCKS + 1))
    store = BlockStore(kv)
    assert r.differs == [] and r.height == BLOCKS
    for h in range(1, BLOCKS + 1):
        hdr = store.load_block(h).header
        if what == "store":
            assert ex.stores[h] == r.snapshots[h], h
        elif what == "data_hash":
            assert hdr.data_hash == r.data_root[h], h
        elif what == "last_results_hash" and h > 1:
            assert hdr.last_results_hash == r.results_root[h - 1], h
    if what == "counts":
        assert (r.txs, r.tx_bytes) == (BLOCKS * TXS, BLOCKS * TXS * SIZE)


@pytest.mark.parametrize("what", ("state", "validators", "responses"))
@pytest.mark.parametrize("seed", SEEDS)
def test_the_state_store_read_from_a_new_connection(tmp, seed, what):
    kv, final, _, _ = loaded_chain(seed)
    path = replayed(seed, tmp)[4]
    r = reference_of(kv)
    store = BlockStore(kv)
    skv = open_kv(path)  # the replay's own connection is closed
    try:
        ss = StateStore(skv)
        if what == "state":
            got = ss.load()
            assert got.last_block_height == BLOCKS
            assert got.app_hash == final.app_hash
            assert got.encode() == final.encode()
        for h in range(1, BLOCKS + 1):
            hdr = store.load_block(h).header
            if what == "validators":
                assert ss.load_validators(h).hash() == hdr.validators_hash, h
            elif what == "responses":
                resp = wire.dec_finalize_resp(ss.load_abci_responses(h))
                assert len(resp.tx_results) == TXS
                assert all(t.code == 0 for t in resp.tx_results)
                root = results_hash(resp.tx_results)
                assert root == r.results_root[h] == ss.load_finalize_response(h)
                if h < BLOCKS:
                    assert resp.app_hash == store.load_block(
                        h + 1).header.app_hash
        if what == "validators":  # the sets of the two heights ahead, too
            assert ss.load_validators(BLOCKS + 1).hash() == (
                final.validators.hash())
            assert ss.load_validators(BLOCKS + 2).hash() == (
                final.next_validators.hash())
    finally:
        skv.close()


def _key(prefix: bytes, h: int) -> bytes:
    return prefix + h.to_bytes(8, "big")


def stored_as_the_reference_encodes(path, genesis, ex, results_root, tip):
    """Every key of the state store at `path`, read through a new
    connection, against what the plain encoder (tests/
    state_encoding_reference.py) gives for the states and responses the
    executor `ex` went through; `results_root[h]` is the reference's.
    Returns the keys by prefix."""
    states = dict(ex.states)
    states[0] = genesis
    want = {b"S:cur": enc_ref.state(states[tip])}
    for h in range(0, tip + 1):
        # the state after block h carries the sets of h+1 and h+2 and
        # the params that judge block h+1
        st = states[h]
        want[_key(b"SV:", h + 1)] = enc_ref.validator_set(st.validators)
        want[_key(b"SV:", h + 2)] = enc_ref.validator_set(
            st.next_validators)
        want[_key(b"SP:", h + 1)] = enc_ref.params(st.consensus_params)
    for h in range(1, tip + 1):
        want[_key(b"SA:", h)] = results_root[h]
        want[_key(b"AR:", h)] = enc_ref.finalize_resp(ex.resps[h])
    skv = open_kv(path)
    try:
        got = dict(skv.iterate_prefix(b""))
    finally:
        skv.close()
    assert sorted(got) == sorted(want)
    for k in want:
        assert got[k] == want[k], k
    return {p: sum(k.startswith(p) for k in got)
            for p in (b"S:cur", b"SV:", b"SP:", b"SA:", b"AR:")}


@pytest.mark.parametrize("seed", SEEDS)
def test_every_key_of_the_state_store_holds_the_reference_encoders_bytes(
        tmp, seed):
    kv, _, genesis, _ = loaded_chain(seed)
    _, _, _, ex, path = replayed(seed, tmp)
    r = reference_of(kv)
    assert stored_as_the_reference_encodes(
        path, genesis, ex, r.results_root, BLOCKS) == {
            b"S:cur": 1, b"SV:": BLOCKS + 2, b"SP:": BLOCKS + 1,
            b"SA:": BLOCKS, b"AR:": BLOCKS}


@pytest.mark.parametrize("with_store", (True, False))
def test_the_results_root_is_computed_once_a_block(
        tmp_path, monkeypatch, with_store):
    from cometbft_tpu.state import execution

    calls = []

    def counted(tx_results):
        calls.append(len(tx_results))
        return results_hash(tx_results)

    monkeypatch.setattr(execution, "results_hash", counted)
    kv, final, genesis, _ = loaded_chain(SEEDS[0])
    state, _, _, _, skv = replay(
        kv, genesis, str(tmp_path / "s.db") if with_store else None)
    assert state.last_results_hash == final.last_results_hash
    assert calls == [TXS] * BLOCKS
    if skv is not None:
        assert StateStore(skv).load_finalize_response(BLOCKS) == (
            final.last_results_hash)
        skv.close()


CHURN = {"validators": 8, "window": 4, "blocks": 24, "update_every": 3,
         "repowered_members": 2, "spare_keys": 5}  # the churn cell's rehearsal


@pytest.mark.parametrize("seed", SEEDS)
def test_a_chain_with_validator_updates_is_read_back_set_by_set(
        tmp_path, seed):
    """benchmark/configs/catchup-1000v-churn.json's rehearse schedule,
    replayed with a state store: a block that changes the set still
    encodes one set afresh (the changed one), and the store holds, for
    every height, the set that height's header names."""
    spare = fx.make_signers(CHURN["spare_keys"], seed=seed + 7)
    churn = fx.ValsetChurn(
        spare, seed=seed, every=CHURN["update_every"],
        repowered=CHURN["repowered_members"], power_lo=30, power_hi=100)
    tip, kv = CHURN["blocks"], MemKV()
    store, final, genesis, _ = fx.make_chain(
        tip, n_validators=CHURN["validators"], chain_id=CHAIN, seed=seed,
        backend="cpu", powers=churn.genesis_powers(CHURN["validators"]),
        extra_txs=churn, spare_signers=spare, block_store=BlockStore(kv))
    assert churn.joins >= 3 and churn.repowerings >= 3
    assert WINDOW == CHURN["window"]
    path = str(tmp_path / "state.db")
    (state, _, _, ex, skv), recs = _traced(
        tmp_path, lambda: replay(kv, genesis, path))
    skv.close()
    assert state.encode() == final.encode()
    blocks = _of(recs, "state.apply_block")
    assert [r["height"] for r in blocks] == list(range(1, tip + 1))
    assert [r["set_encodes"] for r in blocks] == [1] * tip
    assert len(_of(recs, "state.valset_update")) == tip // CHURN[
        "update_every"]
    roots = {h: results_hash(ex.resps[h].tx_results)
             for h in range(1, tip + 1)}
    stored_as_the_reference_encodes(path, genesis, ex, roots, tip)
    skv = open_kv(path)
    try:
        ss = StateStore(skv)
        hashes = set()
        for h in range(1, tip + 1):
            hdr = store.load_block(h).header
            vals = ss.load_validators(h)
            assert vals.hash() == hdr.validators_hash, h
            assert ss.load_validators(h + 1).hash() == (
                hdr.next_validators_hash), h
            assert enc_ref.validator_set(vals) == skv.get(_key(b"SV:", h))
            hashes.add(hdr.validators_hash)
        assert len(hashes) >= 6  # the set did change
        assert ss.load_validators(tip + 2).hash() == (
            final.next_validators.hash())
    finally:
        skv.close()


# ---------------------------------------------------------------------
# the two refusals


BAD_H = 10  # in the window of heights 9-12


def flipped_byte_store(seed: int, height: int) -> MemKV:
    """A copy of the chain's store with one byte of one transaction of
    block `height` flipped in the stored bytes."""
    kv, _, _, _ = loaded_chain(seed)
    bad = MemKV()
    bad._d = dict(kv._d)
    raw = bytearray(bad.get(_key_block(height)))
    tx = BlockStore(kv).load_block(height).data.txs[TXS // 2]
    at = bytes(raw).index(tx) + SIZE // 2
    raw[at] ^= 0x01
    bad.set(_key_block(height), bytes(raw))
    return bad


@pytest.mark.parametrize("mode,depth", (("batched", 1), ("batched", 2),
                                        ("full", None)))
@pytest.mark.parametrize("height", (9, BAD_H, 12))
def test_a_flipped_byte_of_a_stored_transaction_is_refused(
        tmp_path, height, mode, depth):
    """The block's id is no longer the one its successor's commit signs."""
    seed = SEEDS[0]
    bad = flipped_byte_store(seed, height)
    _, _, genesis, _ = loaded_chain(seed)
    app = KVStoreApp()
    skv = open_kv(str(tmp_path / "state.db"))
    StateStore(skv).save(genesis)
    engine = ReplayEngine(
        BlockStore(bad), BlockExecutor(AppConns(app), backend="cpu",
                                       state_store=StateStore(skv)),
        verify_mode=mode, window=WINDOW, backend="cpu", depth=depth)
    with pytest.raises(ErrInvalidBlockID):
        engine.run(genesis.copy())
    # nothing of the refused window (batched) or from the refused height
    # on (full) is applied, and the state store says the same
    assert app.height == (8 if mode == "batched" else height - 1)
    assert StateStore(skv).load().last_block_height == app.height
    skv.close()
    # the reference: the transactions no longer hash to the header's, nor
    # their results (a result carries the value) to the next header's
    r = reference_of(bad)
    assert r.differs == [(height, "data_hash"),
                         (height + 1, "last_results_hash")]


def test_a_block_whose_transactions_are_not_its_headers_is_not_applied():
    """validate_block itself: the decoded block altered, its bytes not."""
    kv, _, genesis, _ = loaded_chain(SEEDS[0])
    blk = BlockStore(kv).load_block(1)
    bid = block_id_for(blk)
    tx = bytearray(blk.data.txs[0])
    tx[-1] ^= 0x01
    blk.data.txs[0] = bytes(tx)
    app = KVStoreApp()
    with pytest.raises(BlockValidationError, match="wrong data_hash"):
        BlockExecutor(AppConns(app), backend="cpu").apply_block_preverified(
            genesis.copy(), bid, blk)
    assert app.height == 0 and app.store == {}


@pytest.mark.parametrize("depth", (1, 2))
def test_a_flipped_signature_is_refused_with_blame(tmp_path, depth):
    seed, idx = SEEDS[1], 5
    kv, _, genesis, _ = loaded_chain(
        seed, corrupt_sig=(BAD_H, idx), verify_last_commit=False)
    state, _, app, _, skv = replay(kv, genesis, str(tmp_path / "a.db"),
                                   to_height=8)
    assert app.height == 8
    skv.close()
    app = KVStoreApp()
    engine = ReplayEngine(BlockStore(kv),
                          BlockExecutor(AppConns(app), backend="cpu"),
                          window=WINDOW, backend="cpu", depth=depth)
    with pytest.raises(ErrInvalidSignature) as exc:
        engine.run(genesis.copy())
    assert isinstance(exc.value, CommitError)
    # the refused window starts at 9: block 9's LastCommit (height 8)
    # first, then one commit a height, N lanes each
    lane = int(re.search(r"lane (\d+)", str(exc.value)).group(1))
    assert (8 + lane // N, lane % N) == (BAD_H, idx)
    assert app.height == 8
    # the transactions are the honest chain's: the reference has no quarrel
    assert reference_of(kv).differs == []


# ---------------------------------------------------------------------
# spans and counters


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


@pytest.mark.parametrize("with_store", (True, False))
@pytest.mark.parametrize("depth", (1, 2))
def test_the_new_span_fields_and_counters_add_up(tmp_path, depth, with_store):
    kv, final, genesis, _ = loaded_chain(SEEDS[2])
    path = str(tmp_path / "state.db") if with_store else None
    (state, _, _, _, skv), recs = _traced(
        tmp_path, lambda: replay(kv, genesis, path, depth=depth))
    assert state.app_hash == final.app_hash
    raw = {h: len(kv.get(_key_block(h))) for h in range(1, BLOCKS + 1)}

    loads = [r for r in _of(recs, "blocksync.window_load") if r["blocks"]]
    assert [r["window"] for r in loads] == [1, 5, 9, 13]
    for r in loads:
        assert r["bytes"] == sum(raw[h] for h in range(
            r["window"], r["window"] + r["blocks"]))
        assert r["read_ms"] >= 0 and r["decode_ms"] > 0
        assert r["read_ms"] + r["decode_ms"] <= r["dur_ms"] + 0.002, r
    assert all(r["bytes"] > WINDOW * TXS * SIZE for r in loads)

    applies = _of(recs, "blocksync.window_apply")
    assert [(r["txs"], r["tx_bytes"]) for r in applies] == [
        (WINDOW * TXS, WINDOW * TXS * SIZE)] * (BLOCKS // WINDOW)

    blocks = _of(recs, "state.apply_block")
    assert [r["height"] for r in blocks] == list(range(1, BLOCKS + 1))
    assert sum(r["tx_bytes"] for r in blocks) == TXS * SIZE * BLOCKS
    for r in blocks:
        assert (r["txs"], r["tx_bytes"]) == (TXS, TXS * SIZE)
        assert 0 < r["data_hash_ms"] <= r["validate_ms"], r
        assert 0 <= r["state_save_ms"] <= r["save_events_ms"], r
        assert (r["state_save_ms"] > 0) == with_store, r
        # the save's two parts, and the mechanism's counter: of the five
        # sets a block's records hold, one (next_validators) is new
        assert (r["state_encode_ms"] > 0) == with_store == (
            r["state_write_ms"] > 0), r
        assert r["state_encode_ms"] + r["state_write_ms"] <= (
            r["state_save_ms"] + 0.002), r
        assert r["set_encodes"] == (1 if with_store else 0), r
        # the five stages still sum to the span
        assert sum(r[f] for f in (
            "validate_ms", "finalize_ms", "update_state_ms", "commit_ms",
            "save_events_ms")) <= r["dur_ms"] + 0.003, r

    m = blocksync_metrics()
    snap = m.window_bytes.snapshot()
    assert sum(v["count"] for v in snap.values()) == len(loads)
    assert sum(v["sum"] for v in snap.values()) == sum(raw.values())
    assert m.txs_applied_total.values() == {(): float(BLOCKS * TXS)}
    saves = state_metrics().state_save_seconds.snapshot()
    assert sum(v["count"] for v in saves.values()) == (
        BLOCKS if with_store else 0)
    if skv is not None:
        skv.close()
        # untraced nodes read the same count: a block asks for five
        # encodings and one is new; the bootstrap's save of the genesis
        # state asks for four, of two sets (new unless an earlier test
        # has had this cached genesis encoded)
        encodes = state_metrics().valset_encode_total.values()
        assert encodes[("miss",)] + encodes[("hit",)] == 4 + 5 * BLOCKS
        assert BLOCKS <= encodes[("miss",)] <= BLOCKS + 2


def test_untraced_replay_counts_and_takes_no_span_time(tmp_path):
    """With the tracer off the counters still run."""
    kv, final, genesis, _ = loaded_chain(SEEDS[2])
    state, _, _, _, skv = replay(kv, genesis, str(tmp_path / "s.db"))
    skv.close()
    assert state.app_hash == final.app_hash
    m = blocksync_metrics()
    assert m.txs_applied_total.values() == {(): float(BLOCKS * TXS)}
    assert sum(v["count"] for v in m.window_bytes.snapshot().values()) == 4
    assert sum(v["count"] for v in state_metrics().state_save_seconds
               .snapshot().values()) == BLOCKS


def test_the_references_merkle_root_is_rfc_6962():
    leaves = [hashlib.sha256(b"\x00" + x).digest() for x in (b"a", b"b", b"c")]
    two = hashlib.sha256(b"\x01" + leaves[0] + leaves[1]).digest()
    assert ref.merkle_root([]) == hashlib.sha256(b"").digest()
    assert ref.merkle_root([b"a"]) == leaves[0]
    assert ref.merkle_root([b"a", b"b"]) == two
    assert ref.merkle_root([b"a", b"b", b"c"]) == hashlib.sha256(
        b"\x01" + two + leaves[2]).digest()
    assert ref.execute({}, b"novalue") == (1, b"")
    assert ref.execute({}, b"=v") == (1, b"")
    assert ref.execute({}, b"val:zz=1") == (1, b"")
    d = {}
    assert ref.execute(d, b"k=v=w") == (0, b"v=w") and d == {b"k": b"v=w"}
    assert ref.result_bytes(0, b"") == b""
    assert ref.result_bytes(1, b"") == b"\x08\x01"
    assert ref.result_bytes(0, b"x" * 300) == b"\x12\xac\x02" + b"x" * 300
