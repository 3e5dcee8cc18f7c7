"""End-to-end: generate a signed chain, store it, replay it through ABCI."""

import pytest

from cometbft_tpu.abci.client import AppConns
from cometbft_tpu.abci.kvstore import KVStoreApp
from cometbft_tpu.blocksync import ReplayEngine
from cometbft_tpu.state.execution import BlockExecutor, BlockValidationError
from cometbft_tpu.state.types import State
from cometbft_tpu.storage import BlockStore, MemKV, SqliteKV, StateStore
from cometbft_tpu.utils import factories as fx

CHAIN = "replay-chain"


@pytest.fixture(scope="module")
def chain():
    return fx.make_chain(n_blocks=8, n_validators=4, chain_id=CHAIN, backend="cpu")


def test_chain_generation_consistency(chain):
    store, final_state, genesis, signers = chain
    assert store.height() == 8
    assert store.base() == 1
    blk = store.load_block(5)
    assert blk.header.height == 5
    assert blk.header.chain_id == CHAIN
    commit5 = store.load_block_commit(5)
    assert commit5.height == 5  # stored from block 6's LastCommit
    assert final_state.last_block_height == 8


def test_replay_full_mode(chain):
    store, final_state, genesis, _ = chain
    app = KVStoreApp()
    executor = BlockExecutor(AppConns(app), backend="cpu")
    engine = ReplayEngine(store, executor, verify_mode="full", backend="cpu")
    state, stats = engine.run(genesis.copy())
    assert stats.blocks == 8
    assert state.last_block_height == 8
    assert state.app_hash == final_state.app_hash
    assert state.validators.hash() == final_state.validators.hash()


def test_replay_batched_mode_matches_full(chain):
    store, final_state, genesis, _ = chain
    app = KVStoreApp()
    executor = BlockExecutor(AppConns(app), backend="cpu")
    engine = ReplayEngine(store, executor, verify_mode="batched", window=3, backend="cpu")
    state, stats = engine.run(genesis.copy())
    assert stats.blocks == 8
    # Per window: every embedded LastCommit (full VerifyCommit semantics)
    # plus the stored tip commit. Windows of 3 over 8 blocks: [1-3] LC2,LC3
    # + tip3 = 12 sigs; [4-6] LC4..LC6 + tip6 = 16; [7-8] LC7,LC8 + tip8
    # = 12 -> 40 with 4 validators.
    assert stats.sigs_verified == 40
    assert state.app_hash == final_state.app_hash


def test_replay_detects_tampered_block(chain):
    store, _, genesis, _ = chain
    # copy the store and corrupt one tx in block 4
    from cometbft_tpu.types import Block

    tampered = BlockStore(MemKV())
    for h in range(1, 9):
        blk = store.load_block(h)
        if h == 4:
            blk.data.txs[0] = b"evil=1"
        tampered.save_block(blk, store.load_seen_commit(h))
    app = KVStoreApp()
    executor = BlockExecutor(AppConns(app), backend="cpu")
    engine = ReplayEngine(tampered, executor, verify_mode="batched", backend="cpu")
    with pytest.raises(Exception):  # data_hash mismatch or commit failure
        engine.run(genesis.copy())


def test_state_store_roundtrip(chain):
    _, final_state, _, _ = chain
    ss = StateStore(MemKV())
    ss.save(final_state)
    loaded = ss.load()
    assert loaded.chain_id == final_state.chain_id
    assert loaded.last_block_height == final_state.last_block_height
    assert loaded.app_hash == final_state.app_hash
    assert loaded.validators.hash() == final_state.validators.hash()
    assert loaded.next_validators.hash() == final_state.next_validators.hash()
    # proposer restored exactly
    assert loaded.validators.get_proposer().address == final_state.validators.get_proposer().address


def test_sqlite_kv_roundtrip(tmp_path):
    db = SqliteKV(str(tmp_path / "kv.db"))
    db.set(b"a", b"1")
    db.write_batch([(b"b", b"2"), (b"c", b"3")], deletes=[b"a"])
    assert db.get(b"a") is None
    assert db.get(b"b") == b"2"
    assert [k for k, _ in db.iterate_prefix(b"")] == [b"b", b"c"]
    db.close()


def test_block_store_prune(chain):
    store, *_ = chain
    clone = BlockStore(MemKV())
    for h in range(1, 9):
        clone.save_block(store.load_block(h), store.load_seen_commit(h))
    assert clone.prune(5) == 4
    assert clone.base() == 5
    assert clone.load_block(4) is None
    assert clone.load_block(5) is not None
    with pytest.raises(ValueError):
        clone.prune(100)


def test_kvstore_app_query_and_validator_txs():
    app = KVStoreApp()
    from cometbft_tpu.abci.types import FinalizeBlockRequest

    resp = app.finalize_block(FinalizeBlockRequest(txs=[b"x=1", b"bad"], height=1))
    assert resp.tx_results[0].is_ok() and not resp.tx_results[1].is_ok()
    app.commit()
    assert app.query("/key", b"x").value == b"1"
    pk_hex = "aa" * 32
    resp = app.finalize_block(
        FinalizeBlockRequest(txs=[b"val:" + pk_hex.encode() + b"=7"], height=2)
    )
    assert resp.validator_updates and resp.validator_updates[0].power == 7


def test_pipeline_depth_policy(monkeypatch):
    """Depth auto-selection: 2 on a single device, 1 + n_devices on a
    mesh (every chip holds a window), explicit depth always wins."""
    from cometbft_tpu.crypto import ed25519 as e

    store = BlockStore(MemKV())
    executor = BlockExecutor(AppConns(KVStoreApp()), backend="cpu")
    engine = ReplayEngine(store, executor, backend="cpu")
    monkeypatch.setattr(e, "_mesh_engine", lambda: None)
    assert engine._pipeline_depth() == 2

    class _Stub:
        n_devices = 8

    monkeypatch.setattr(e, "_mesh_engine", lambda: _Stub())
    assert engine._pipeline_depth() == 9
    deep = ReplayEngine(store, executor, backend="cpu", depth=4)
    assert deep._pipeline_depth() == 4
    monkeypatch.setattr(e, "_mesh_engine", lambda: None)
    assert deep._pipeline_depth() == 4


def test_replay_deep_pipeline_matches(chain):
    """Depth-4 over 2-block windows: the speculative fill walks several
    windows ahead of the apply loop and past the tip; the final state
    must be byte-identical to the depth-1 (serial) run."""
    store, final_state, genesis, _ = chain
    runs = []
    for depth in (1, 4):
        executor = BlockExecutor(AppConns(KVStoreApp()), backend="cpu")
        engine = ReplayEngine(
            store, executor, verify_mode="batched", window=2,
            backend="cpu", depth=depth,
        )
        state, stats = engine.run(genesis.copy())
        assert stats.blocks == 8
        runs.append((state, stats))
    (a, sa), (b, sb) = runs
    assert sa.sigs_verified == sb.sigs_verified > 0  # depth never changes lanes
    assert a.app_hash == b.app_hash == final_state.app_hash
    assert a.last_block_height == b.last_block_height == 8


# ----------------------------------------------------------------------
# ISSUE 24: the span tree of one ReplayEngine.run
# ----------------------------------------------------------------------
def test_traced_replay_gives_one_tree_with_per_window_spans(
        chain, tmp_path, monkeypatch):
    """Batched replay of the 8-block chain, windows of 3, with tracing on
    and the batches on the device path (NATIVE_MAX 0: XLA:CPU ladder at
    bucket 64), so each window's verdict is really waited for."""
    import json
    import os

    from cometbft_tpu.crypto import ed25519
    from cometbft_tpu.utils import trace

    store, final_state, genesis, _ = chain
    monkeypatch.setattr(ed25519, "NATIVE_MAX", 0)
    sink = os.path.join(str(tmp_path), "replay.jsonl")
    executor = BlockExecutor(AppConns(KVStoreApp()))
    engine = ReplayEngine(store, executor, verify_mode="batched", window=3)
    trace.configure(sink)
    try:
        state, stats = engine.run(genesis.copy())
        trace.flush()
        with open(sink, encoding="utf-8") as f:
            recs = [json.loads(line) for line in f]
    finally:
        trace.disable()
    assert state.app_hash == final_state.app_hash and stats.blocks == 8
    recs = [r for r in recs
            if r["name"] not in ("trace.clock", "trace.thread",
                                 "runtime.gc_pause")]
    by_id = {r["id"]: r for r in recs if "id" in r}
    (root,) = [r for r in recs if r["name"] == "blocksync.replay"]
    assert root["parent"] is None
    assert (root["from"], root["to"], root["depth"]) == (1, 8, 2)
    assert {r["root"] for r in recs} == {root["id"]}  # one tree

    def of(name):
        return [r for r in recs if r["name"] == name]

    # every window has its five spans, tied by `window` = first height
    for name in ("blocksync.window_load", "blocksync.window_queue",
                 "blocksync.window_fill", "blocksync.window_resolve",
                 "blocksync.window_apply"):
        assert sorted(r["window"] for r in of(name)) == [1, 4, 7], name
    for r in of("blocksync.window_fill"):
        assert by_id[r["parent"]]["name"] == "blocksync.window_queue"
        assert by_id[r["parent"]]["window"] == r["window"]
        assert r["columnar"] == r["commits"]  # decoded ed25519 commits
    assert {r["window"]: (r["commits"], r["lanes"])
            for r in of("blocksync.window_fill")} == {
        1: (3, 12), 4: (4, 16), 7: (3, 12)}
    assert {r["window"]: (r["blocks"], r["txs"])
            for r in of("blocksync.window_apply")} == {
        1: (3, 6), 4: (3, 6), 7: (2, 4)}
    # apply: one state.apply_block per block, under its window
    applies = of("state.apply_block")
    assert sorted(r["height"] for r in applies) == list(range(1, 9))
    for r in applies:
        win = by_id[r["parent"]]
        assert win["name"] == "blocksync.window_apply"
        assert win["window"] <= r["height"] < win["window"] + 3
        assert {"validate_ms", "finalize_ms", "commit_ms",
                "save_events_ms"} <= r.keys()
    # one dispatch per window, with its children, and a verdict wait
    # whose `batch` is the submit that it resolves
    submits = of("crypto.batch_verify")
    assert [(r["path"], r["n"], r["bucket"]) for r in submits] == [
        ("ladder", 12, 64), ("ladder", 16, 64), ("ladder", 12, 64)]
    for r in submits:
        kids = {k["name"] for k in recs if k.get("parent") == r["id"]}
        assert kids == {"crypto.pack", "crypto.device_launch"}
        assert by_id[r["parent"]]["name"] == "blocksync.window_queue"
    waits = of("crypto.verdict_wait")
    assert [w["batch"] for w in waits] == [r["id"] for r in submits]
    for w in waits:
        resolve = by_id[w["parent"]]
        assert resolve["name"] == "blocksync.window_resolve"
        queue = by_id[by_id[w["batch"]]["parent"]]
        assert queue["window"] == resolve["window"]
        assert w["path"] == "ladder" and not w["blame_rerun"]
        assert w["since_submit_ms"] >= w["dur_ms"]
    # per window, never per lane: 4 validators here, and the same
    # spans a window whatever the validator count
    per_window = [r for r in recs if r.get("window") == 4
                  or by_id.get(r.get("parent"), {}).get("window") == 4]
    assert len([r for r in per_window
                if r["name"] != "state.apply_block"]) == 7


def test_untraced_replay_emits_nothing(chain):
    from cometbft_tpu.utils import trace

    store, final_state, genesis, _ = chain
    assert not trace.enabled
    executor = BlockExecutor(AppConns(KVStoreApp()), backend="cpu")
    engine = ReplayEngine(store, executor, window=3, backend="cpu")
    state, _stats = engine.run(genesis.copy())
    assert state.app_hash == final_state.app_hash
    assert trace.tail() == [] and trace.path() is None
    assert trace.span("blocksync.replay") is trace.span("state.apply_block")
