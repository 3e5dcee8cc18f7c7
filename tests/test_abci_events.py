"""ABCI events from the application to the index: the kvstore's events
(abci/kvstore.py, `events=True`) through the wire encoding that the socket
and gRPC transports frame and the state store keeps (abci/wire.py), the
executor, the bus and the kv indexer, and back out through /block_results,
/tx_search and reindex-event. Held to the two plain references
benchmark/reference/kvstore_events.py (a transaction's events from its
bytes) and benchmark/reference/tx_index.py fed those events; neither shares
code with the program. Events are no part of consensus: every header of a
chain is the same with and without them."""

import functools
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.reference import kvstore_events as ref_events  # noqa: E402
from benchmark.reference import kvstore_replay as ref_replay  # noqa: E402
from benchmark.reference import tx_index as ref  # noqa: E402
from cometbft_tpu.abci import types as T  # noqa: E402
from cometbft_tpu.abci import wire  # noqa: E402
from cometbft_tpu.abci.client import AppConns  # noqa: E402
from cometbft_tpu.abci.kvstore import KVStoreApp  # noqa: E402
from cometbft_tpu.abci.socket import SocketClient, SocketServer  # noqa: E402
from cometbft_tpu.blocksync import ReplayEngine  # noqa: E402
from cometbft_tpu.config import Config  # noqa: E402
from cometbft_tpu.state.execution import (  # noqa: E402
    BlockExecutor,
    results_hash,
)
from cometbft_tpu.storage import BlockStore, MemKV, StateStore, open_kv  # noqa: E402
from cometbft_tpu.storage import indexer as ix  # noqa: E402
from cometbft_tpu.types import Timestamp  # noqa: E402
from cometbft_tpu.utils import factories as fx  # noqa: E402
from cometbft_tpu.utils import trace  # noqa: E402
from cometbft_tpu.utils.metrics import indexer_metrics, state_metrics  # noqa: E402

CHAIN = "events-chain"
N, BLOCKS, WINDOW, TXS, SIZE = 8, 16, 4, 12, 1024
SEEDS = (61, 62, 63)
ATTR = T.EventAttribute


# -- (a) a response without events is the bytes it was -----------------

# enc_finalize_resp of the tree before events were carried (04b85a5)
PINNED = {
    "results": (
        T.FinalizeBlockResponse(
            tx_results=[T.ExecTxResult(data=b"v1"),
                        T.ExecTxResult(code=1, log="malformed tx")],
            app_hash=bytes(range(32))),
        "0a04120276310a1008011a0c6d616c666f726d65642074781a20000102030405"
        "060708090a0b0c0d0e0f101112131415161718191a1b1c1d1e1f"),
    "validator_update": (
        T.FinalizeBlockResponse(
            tx_results=[T.ExecTxResult(data=b"7", gas_wanted=3, gas_used=2)],
            validator_updates=[T.ValidatorUpdate(b"\x05" * 32, "ed25519", 7)],
            app_hash=b"\xaa" * 32),
        "0a0712013728033002122d0a2005050505050505050505050505050505050505"
        "0505050505050505050505050512076564323535313918071a20aaaaaaaaaaaa"
        "aaaaaaaaaaaaaaaaaaaaaaaaaaaaaaaaaaaaaaaaaaaaaaaaaaaa"),
    "empty": (T.FinalizeBlockResponse(), ""),
}


@pytest.mark.parametrize("case", sorted(PINNED))
def test_a_response_without_events_encodes_to_the_bytes_it_did(case):
    resp, pinned = PINNED[case]
    assert wire.enc_finalize_resp(resp).hex() == pinned
    assert wire.dec_finalize_resp(bytes.fromhex(pinned)) == resp


@pytest.mark.parametrize("seed", SEEDS)
def test_the_eventless_applications_response_has_no_events(seed):
    txs = fx.LoadtimeTxs(seed, per_block=TXS, size=SIZE).txs(3)
    resp = KVStoreApp().finalize_block(
        T.FinalizeBlockRequest(txs=txs, height=3))
    assert resp.events == [] and all(tr.events == [] for tr in
                                     resp.tx_results)
    # field 7 (a result's events) and field 4 (the block's) are nowhere
    enc = wire.enc_finalize_resp(resp)
    assert wire.dec_finalize_resp(enc) == resp
    assert len(enc) < TXS * (SIZE + 8) + 40


# -- (b) events round-trip through the one encoder ----------------------

def _ev(*attrs, etype="app"):
    return T.Event(etype, [ATTR(*a) for a in attrs])


ROUND_TRIPS = {
    "a_results_events": T.FinalizeBlockResponse(tx_results=[
        T.ExecTxResult(data=b"v", events=[
            _ev(("creator", "Cosmoshi Netowoko", True), ("key", "k", True),
                ("noindex_key", "index is working", False)),
            _ev(("key", "v", True))]),
        T.ExecTxResult(code=1, log="malformed tx")]),
    "the_blocks_own_events": T.FinalizeBlockResponse(
        events=[_ev(("proposer", "ab01", True), etype="block"),
                _ev(("n", "2", False), etype="block")],
        tx_results=[T.ExecTxResult(data=b"v")], app_hash=b"\x07" * 32),
    "both_with_a_validator_update": T.FinalizeBlockResponse(
        events=[_ev(("k", "v", True), etype="begin")],
        tx_results=[T.ExecTxResult(data=b"1", gas_used=9,
                                   events=[_ev(("key", "val:00", True))])],
        validator_updates=[T.ValidatorUpdate(b"\x09" * 32, "ed25519", 3)],
        app_hash=b"\x01" * 32),
    "text_beyond_ascii_and_empty_values": T.FinalizeBlockResponse(
        tx_results=[T.ExecTxResult(events=[
            _ev(("kéy", "välue ✓", True), ("empty", "", False),
                etype="év"),
            T.Event("bare", [])])]),
    "a_value_of_a_kilobyte": T.FinalizeBlockResponse(
        tx_results=[T.ExecTxResult(data=b"x" * 1022, events=[
            _ev(("key", "ab" * 511, True))])]),
}


@pytest.mark.parametrize("case", sorted(ROUND_TRIPS))
def test_events_round_trip_through_enc_and_dec_finalize_resp(case):
    resp = ROUND_TRIPS[case]
    got = wire.dec_finalize_resp(wire.enc_finalize_resp(resp))
    assert got == resp
    for tr in got.tx_results:
        for ev in tr.events:
            assert isinstance(ev, T.Event)
            assert all(isinstance(a, T.EventAttribute)
                       and isinstance(a.index, bool) for a in ev.attributes)
    # without its events the response is the bytes of the one without
    bare = T.FinalizeBlockResponse(
        tx_results=[T.ExecTxResult(tr.code, tr.data, tr.log, tr.gas_wanted,
                                   tr.gas_used) for tr in resp.tx_results],
        validator_updates=resp.validator_updates, app_hash=resp.app_hash)
    assert len(wire.enc_finalize_resp(bare)) < len(
        wire.enc_finalize_resp(resp))


def test_a_results_events_are_upstreams_field_7():
    from cometbft_tpu.encoding import proto as pb

    resp = ROUND_TRIPS["a_results_events"]
    fields = pb.parse_fields(wire.enc_finalize_resp(resp))
    first = pb.parse_fields(fields[0][2])
    events = [v for f, _, v in first if f == 7]
    assert len(events) == 2 and [f for f, _, _ in first] == [2, 7, 7]
    ev = pb.parse_fields(events[0])
    assert ev[0] == (1, pb.WT_LEN, b"app")
    assert [pb.fields_to_dict(v) for f, _, v in ev[1:]] == [
        {1: b"creator", 2: b"Cosmoshi Netowoko", 3: 1},
        {1: b"key", 2: b"k", 3: 1},
        {1: b"noindex_key", 2: b"index is working"}]  # index false: left out
    # the block's own events take a field the response did not use
    own = pb.parse_fields(
        wire.enc_finalize_resp(ROUND_TRIPS["the_blocks_own_events"]))
    assert [f for f, _, _ in own] == [1, 3, 4, 4]


# -- (c) the application against the reference --------------------------

def _txs(kind: str, seed: int) -> list[bytes]:
    if kind == "loadtime":
        return fx.LoadtimeTxs(seed, per_block=TXS, size=SIZE).txs(2)
    import numpy as np

    rng = np.random.default_rng(seed)
    return [b"k%d=%s" % (int(rng.integers(5)), rng.bytes(5).hex().encode())
            for _ in range(TXS)] + [b"val:%s=7" % (b"\x03" * 32).hex().encode()]


@pytest.mark.parametrize("kind", ("loadtime", "key_value"))
@pytest.mark.parametrize("seed", SEEDS)
def test_the_application_emits_the_references_events(seed, kind):
    txs = _txs(kind, seed)
    req = T.FinalizeBlockRequest(txs=txs + [b"no equals sign"], height=1)
    with_events, without = KVStoreApp(events=True), KVStoreApp()
    resp, plain = with_events.finalize_block(req), without.finalize_block(req)
    assert resp.events == []  # the kvstore emits none of the block's own
    for tx, tr in zip(txs, resp.tx_results):
        assert tr.code == 0 and tr.events == ref_events.events(tx)
        assert [(f"{e.type}.{a.key}", a.value) for e in tr.events
                for a in e.attributes if a.index] == ref_events.indexed(tx)
    assert resp.tx_results[-1].code == 1  # refused: answered without events
    assert resp.tx_results[-1].events == []
    # code, data, the results' root and the app hash are the eventless
    # application's: events are no part of consensus
    assert [(t.code, t.data, t.log) for t in resp.tx_results] == [
        (t.code, t.data, t.log) for t in plain.tx_results]
    assert results_hash(resp.tx_results) == results_hash(plain.tx_results)
    assert results_hash(resp.tx_results) == ref_replay.merkle_root(
        [ref_replay.result_bytes(t.code, t.data) for t in resp.tx_results])
    assert (resp.app_hash, resp.validator_updates) == (
        plain.app_hash, plain.validator_updates)
    with_events.commit(), without.commit()
    assert with_events.app_hash == without.app_hash
    assert with_events.store == without.store


def test_the_reference_of_a_transaction_without_an_equals_sign():
    assert ref_events.events(b"lonely") == [
        ("app", [("creator", "Cosmoshi Netowoko", True),
                 ("key", "lonely", True),
                 ("index_key", "index is working", True),
                 ("noindex_key", "index is working", False)]),
        ("app", [("creator", "Cosmoshi", True), ("key", "lonely", True),
                 ("index_key", "index is working", True),
                 ("noindex_key", "index is working", False)])]
    with open(os.path.join(ROOT, "benchmark", "reference",
                           "kvstore_events.py")) as f:
        code = f.read().split('"""', 2)[2]  # behind the module's docstring
    assert "cometbft_tpu" not in code
    assert [ln for ln in code.splitlines()
            if ln.startswith(("import ", "from "))] == [
        "from __future__ import annotations"]


def test_the_nodes_built_in_application_emits_and_the_default_does_not(
        tmp_path, monkeypatch):
    from cometbft_tpu import cli, node

    assert KVStoreApp().events is False
    built = []

    class Stop(Exception):
        pass

    class FakeNode:
        def __init__(self, cfg, app=None):
            built.append(app)

        def start(self):
            raise Stop

    home = str(tmp_path / "n0")
    assert cli.main(["--home", home, "init", "--chain-id", "ev"]) == 0
    monkeypatch.setattr(node, "Node", FakeNode)
    with pytest.raises(Stop):
        cli.main(["--home", home, "start"])
    assert isinstance(built[0], KVStoreApp) and built[0].events is True


# -- (d) the socket transport shares the encoder ------------------------

@pytest.mark.parametrize("seed", SEEDS)
def test_the_socket_transport_carries_events(tmp_path, seed):
    txs = _txs("key_value", seed) + _txs("loadtime", seed)[:2]
    srv = SocketServer(KVStoreApp(events=True),
                       f"unix://{tmp_path}/abci.sock")
    srv.start()
    c = SocketClient(f"unix://{tmp_path}/abci.sock")
    try:
        resp = c.finalize_block(T.FinalizeBlockRequest(
            txs=txs, height=1, time=Timestamp(1, 0), hash=b"\x01" * 32))
    finally:
        c.close()
        srv.stop()
    assert [tr.events for tr in resp.tx_results] == [
        ref_events.events(tx) for tx in txs]
    assert resp == KVStoreApp(events=True).finalize_block(
        T.FinalizeBlockRequest(txs=txs, height=1))


def test_the_grpc_transport_carries_events():
    pytest.importorskip("grpc")
    from cometbft_tpu.abci.grpc_transport import GrpcClient, GrpcServer

    txs = _txs("key_value", SEEDS[0])
    srv = GrpcServer(KVStoreApp(events=True), "127.0.0.1:0")
    srv.start()
    try:
        cli = GrpcClient(srv.addr)
        resp = cli.finalize_block(T.FinalizeBlockRequest(
            height=1, txs=txs, hash=b"\x01" * 32))
        cli.close()
    finally:
        srv.stop()
    assert [tr.events for tr in resp.tx_results] == [
        ref_events.events(tx) for tx in txs]


# -- (e) a replay with the kv indexer against the references ------------

@functools.lru_cache(maxsize=None)
def chain(seed: int, events: bool = False):
    """A 16-block chain of 8 validators whose blocks carry 12 loadtime
    transactions, built by the application with or without events:
    (key-value store of the blocks, final state, genesis state)."""
    kv = MemKV()
    _, final, genesis, _ = fx.make_chain(
        BLOCKS, n_validators=N, chain_id=CHAIN, seed=seed, backend="cpu",
        txs_per_block=0,
        extra_txs=fx.LoadtimeTxs(seed, per_block=TXS, size=SIZE),
        app=KVStoreApp(events=events), block_store=BlockStore(kv))
    return kv, final, genesis


@functools.lru_cache(maxsize=None)
def replayed(seed: int, tmp: str):
    """The eventless chain replayed by the eventful application as a node
    does: state store on sqlite, the indexing open_indexing builds on
    files. Everything is closed on return: (final state, state store's
    path, index directory, the spans' sink, what the two new counters
    moved by)."""
    kv, _, genesis = chain(seed)
    state_path = os.path.join(tmp, f"state-{seed}.db")
    index_dir = os.path.join(tmp, f"index-{seed}")
    sink = os.path.join(tmp, f"spans-{seed}.jsonl")
    os.makedirs(index_dir)
    skv = open_kv(state_path)
    StateStore(skv).save(genesis)
    made = ix.open_indexing("kv", index_dir)
    m_keys = indexer_metrics().attr_keys_total
    m_events = state_metrics().abci_events_total
    before = (sum(m_keys.values().values()), sum(m_events.values().values()))
    trace.configure(sink)
    try:
        ex = BlockExecutor(AppConns(KVStoreApp(events=True)), backend="cpu",
                           state_store=StateStore(skv),
                           event_bus=made.event_bus)
        state, _ = ReplayEngine(BlockStore(kv), ex, verify_mode="batched",
                                window=WINDOW, backend="cpu").run(
            genesis.copy())
        made.service.wait(BLOCKS)
    finally:
        trace.disable()
        made.stop()
        skv.close()
    moved = (sum(m_keys.values().values()) - before[0],
             sum(m_events.values().values()) - before[1])
    return state, state_path, index_dir, sink, moved


@pytest.fixture(scope="module")
def tmp(tmp_path_factory):
    return str(tmp_path_factory.mktemp("events"))


def reference_of(kv) -> ref.Index:
    store, want = BlockStore(kv), ref.Index()
    for h in range(1, store.height() + 1):
        txs = store.load_block(h).data.txs
        want.block(h, txs, [ref_events.events(tx) for tx in txs])
    return want


@pytest.mark.parametrize("seed", SEEDS)
def test_events_change_no_header(seed):
    plain, with_events = chain(seed), chain(seed, events=True)
    a, b = BlockStore(plain[0]), BlockStore(with_events[0])
    for h in range(1, BLOCKS + 1):
        ha, hb = a.load_block(h).header, b.load_block(h).header
        assert ha.hash() == hb.hash(), h
        assert (ha.app_hash, ha.last_results_hash, ha.data_hash) == (
            hb.app_hash, hb.last_results_hash, hb.data_hash)
    assert plain[1].encode() == with_events[1].encode()


@pytest.mark.parametrize("seed", SEEDS)
def test_the_eventless_chain_replays_under_the_eventful_application(
        tmp, seed):
    _, final, _ = chain(seed)
    state = replayed(seed, tmp)[0]
    assert state.encode() == final.encode()


@pytest.mark.parametrize("seed", SEEDS)
def test_the_stored_responses_hold_the_references_events(tmp, seed):
    kv, _, _ = chain(seed)
    store = BlockStore(kv)
    skv = open_kv(replayed(seed, tmp)[1])  # the replay's is closed
    try:
        ss = StateStore(skv)
        for h in range(1, BLOCKS + 1):
            txs = store.load_block(h).data.txs
            resp = wire.dec_finalize_resp(ss.load_abci_responses(h))
            assert resp.events == []
            assert [tr.events for tr in resp.tx_results] == [
                ref_events.events(tx) for tx in txs], h
            assert results_hash(resp.tx_results) == (
                ss.load_finalize_response(h))
            if h < BLOCKS:
                assert ss.load_finalize_response(h) == store.load_block(
                    h + 1).header.last_results_hash
    finally:
        skv.close()


@pytest.mark.parametrize("seed", SEEDS)
def test_every_key_of_the_index_is_the_references(tmp, seed):
    want = reference_of(chain(seed)[0])
    txi, _, dbs = ix.open_indexers(replayed(seed, tmp)[2])
    try:
        keys = {k.decode(): v for k, v in dbs[0].iterate_prefix(b"")
                if not k.startswith(b"TX:")}
        assert keys == want.keys
        # 5 attribute keys a transaction: the two events' index_key share one
        attr = [k for k in keys if k.startswith("app.")]
        assert len(attr) == 5 * TXS * BLOCKS
        assert not any("noindex_key" in k for k in keys)
        for tx_hash, (height, index, tx, code, data) in want.records.items():
            rec = txi.get(tx_hash)
            assert (rec["height"], rec["index"], rec["tx"], rec["code"],
                    rec["data"]) == (height, index, tx, code, data)
            grouped: dict = {}
            for k, v in ref_events.indexed(tx):
                grouped.setdefault(k, []).append(v)
            assert rec["events"] == grouped
    finally:
        for db in dbs:
            db.close()


@pytest.mark.parametrize("seed", SEEDS)
def test_find_by_attribute_gives_the_references_hashes_in_order(tmp, seed):
    want = reference_of(chain(seed)[0])
    txi, _, dbs = ix.open_indexers(replayed(seed, tmp)[2])

    def found(query, limit=1000):
        return [ref.tx_hash(r["tx"]) for r in txi.search(query, limit=limit)]

    try:
        everything = [h for height in range(1, BLOCKS + 1)
                      for h in want.by_height[height]]
        assert found("app.key = 'a'") == want.find("app.key", "a")
        assert want.find("app.key", "a") == everything
        assert found("app.creator = 'Cosmoshi'") == everything
        assert found("app.index_key = 'index is working'") == everything
        for height in (1, 7, BLOCKS):
            assert found(f"app.key = 'a' AND tx.height = {height}") == (
                want.by_height[height])
            tx = want.records[want.by_height[height][5]][2]
            value = tx[2:].decode()
            assert found(f"app.key = '{value}'") == [ref.tx_hash(tx)]
            assert want.find("app.key", value) == [ref.tx_hash(tx)]
        # an attribute not marked for indexing wrote no key: nothing found
        assert found("app.noindex_key = 'index is working'") == []
        assert want.find("app.noindex_key", "index is working") == []
        assert found("app.key = 'nobody sent this'") == []
    finally:
        for db in dbs:
            db.close()


@pytest.mark.parametrize("seed", SEEDS)
def test_the_span_fields_and_counters_say_what_was_written(tmp, seed):
    import json

    _, state_path, _, sink, moved = replayed(seed, tmp)
    with open(sink) as f:
        recs = [json.loads(line) for line in f]
    blocks = [r for r in recs if r.get("name") == "index.block"]
    applies = [r for r in recs if r.get("name") == "state.apply_block"]
    assert len(blocks) == len(applies) == BLOCKS
    assert {r["attr_keys"] for r in blocks} == {5 * TXS}
    assert {r["events"] for r in applies} == {2 * TXS}
    assert moved == (5 * TXS * BLOCKS, 2 * TXS * BLOCKS)
    skv = open_kv(state_path)
    try:
        stored = {h: len(StateStore(skv).load_abci_responses(h))
                  for h in range(1, BLOCKS + 1)}
    finally:
        skv.close()
    assert {r["height"]: r["response_bytes"] for r in applies} == stored
    for r in blocks:
        # the attribute keys with their hashes, and the records' field 6
        assert 0 < r["attr_bytes"] < r["bytes"]
        assert r["attr_bytes"] > TXS * (SIZE + 5 * 32)
        assert r["keys"] == TXS * (2 + 5) + 1


def test_the_eventless_replay_reads_zero_in_the_new_fields(tmp_path):
    import json

    kv, _, genesis = chain(SEEDS[0])
    made = ix.open_indexing("kv", None)
    sink = str(tmp_path / "spans.jsonl")
    trace.configure(sink)
    try:
        ex = BlockExecutor(AppConns(KVStoreApp()), backend="cpu",
                           state_store=StateStore(MemKV()),
                           event_bus=made.event_bus)
        ex.state_store.save(genesis)
        ReplayEngine(BlockStore(kv), ex, verify_mode="batched",
                     window=WINDOW, backend="cpu").run(genesis.copy(),
                                                       to_height=WINDOW)
    finally:
        trace.disable()
        made.stop()
    with open(sink) as f:
        recs = [json.loads(line) for line in f]
    blocks = [r for r in recs if r.get("name") == "index.block"]
    applies = [r for r in recs if r.get("name") == "state.apply_block"]
    assert len(blocks) == len(applies) == WINDOW
    assert {(r["attr_keys"], r["attr_bytes"]) for r in blocks} == {(0, 0)}
    assert {r["events"] for r in applies} == {0}
    assert all(r["response_bytes"] > TXS * SIZE for r in applies)
    assert all(r["keys"] == 2 * TXS + 1 for r in blocks)


# -- (f) a node: /block_results, /tx_search, reindex-event --------------

def _home(tmp_path):
    from cometbft_tpu.cli import main

    home = str(tmp_path / "n0")
    assert main(["--home", home, "init", "--chain-id", "ev-node"]) == 0
    cfg = Config.load(os.path.join(home, "config/config.toml"))
    cfg.base.home = home
    cfg.base.db_backend = "sqlite"
    cfg.base.crypto_backend = "cpu"
    cfg.p2p.laddr = "tcp://127.0.0.1:0"
    cfg.rpc.laddr = "tcp://127.0.0.1:0"
    cfg.consensus.timeout_commit = 0.05
    cfg.save(os.path.join(home, "config/config.toml"))
    return home, cfg


@pytest.fixture(scope="module")
def node_home(tmp_path_factory):
    """A node on files, run with the application the CLI builds, that
    committed three transactions and stopped: (home, cfg, {tx: height})."""
    from cometbft_tpu.node import Node
    from cometbft_tpu.rpc.routes import broadcast_tx_commit

    home, cfg = _home(tmp_path_factory.mktemp("node"))
    node = Node(cfg, app=KVStoreApp(events=True))
    node.start()
    try:
        heights = {tx: int(broadcast_tx_commit(
            node.rpc_env, {"tx": tx.hex()})["height"])
            for tx in (b"name=satoshi", b"colour=blue", b"name=hal")}
    finally:
        node.stop()
    return home, cfg, heights


def _with_node(cfg, fn):
    from cometbft_tpu.node import Node

    node = Node(cfg, app=KVStoreApp(events=True))
    node.start()
    try:
        return fn(node)
    finally:
        node.stop()


def _json_events(tx: bytes) -> list:
    return [{"type": etype,
             "attributes": [{"key": k, "value": v, "index": i}
                            for k, v, i in attrs]}
            for etype, attrs in ref_events.events(tx)]


def test_block_results_shows_the_stored_events(node_home):
    from cometbft_tpu.rpc.routes import block_results

    _, cfg, heights = node_home

    def read(node):
        return {tx: block_results(node.rpc_env, {"height": str(h)})
                for tx, h in heights.items()}

    for tx, got in _with_node(cfg, read).items():
        assert got["finalize_block_events"] == []
        assert [r["events"] for r in got["txs_results"]] == [_json_events(tx)]
        assert got["txs_results"][0]["data"].lower() == (
            tx.partition(b"=")[2].hex())


@pytest.mark.parametrize("query,want", [
    ("app.key = 'name'", [b"name=satoshi", b"name=hal"]),
    ("app.key = 'blue'", [b"colour=blue"]),  # the second event: the VALUE
    ("app.creator = 'Cosmoshi Netowoko'",
     [b"name=satoshi", b"colour=blue", b"name=hal"]),
    ("app.key = 'name' AND app.key = 'hal'", [b"name=hal"]),
    ("app.noindex_key = 'index is working'", []),
    ("app.key = 'nobody'", []),
])
def test_tx_search_finds_by_attribute(node_home, query, want):
    from cometbft_tpu.rpc.routes import tx_search

    _, cfg, _ = node_home
    got = _with_node(cfg, lambda node: tx_search(
        node.rpc_env, {"query": query}))
    assert [t["hash"].lower() for t in got["txs"]] == [
        ref.tx_hash(tx).hex() for tx in want]
    assert got["total_count"] == str(len(want))


def test_reindex_event_rebuilds_every_attribute_key(node_home):
    from cometbft_tpu.cli import main

    home, cfg, heights = node_home
    data = os.path.join(home, "data")

    def keys():
        _, _, dbs = ix.open_indexers(data)
        try:
            return {k: v for k, v in dbs[0].iterate_prefix(b"")}
        finally:
            for db in dbs:
                db.close()

    before = keys()
    # the reference's attribute keys of the three transactions are there
    for tx, h in heights.items():
        for composite, value in ref_events.indexed(tx):
            assert before[f"{composite}/{value}/{h}/0".encode()] == (
                ref.tx_hash(tx))
    assert sum(k.startswith(b"app.") for k in before) == 5 * len(heights)
    for name in (ix.TX_INDEX_FILE, ix.BLOCK_INDEX_FILE):
        for suffix in ("", "-wal", "-shm"):
            if os.path.exists(os.path.join(data, name + suffix)):
                os.remove(os.path.join(data, name + suffix))
    assert main(["--home", home, "reindex-event"]) == 0
    assert keys() == before
