"""The kv indexer behind `[tx_index] indexer = "kv"` (storage/indexer.py):
the service takes a block's events as one batch, loses nothing, holds its
publisher back, drains on stop, fails loudly; the node builds it on files
that outlive it. Held to the plain reference benchmark/reference/tx_index.py,
which knows no bus, batch, thread or store."""

import json
import os
import sys
import threading
import time
from types import SimpleNamespace as NS

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.reference import tx_index as ref  # noqa: E402
from cometbft_tpu.abci.types import (  # noqa: E402
    ExecTxResult,
    FinalizeBlockResponse,
)
from cometbft_tpu.config import Config, TxIndexConfig  # noqa: E402
from cometbft_tpu.storage import MemKV, open_kv  # noqa: E402
from cometbft_tpu.storage import indexer as ix  # noqa: E402
from cometbft_tpu.types.event_bus import EventBus  # noqa: E402
from cometbft_tpu.utils import trace  # noqa: E402
from cometbft_tpu.utils.metrics import indexer_metrics  # noqa: E402
from cometbft_tpu.utils.pubsub import HeldSubscription  # noqa: E402

SEEDS = (51, 52, 53)
K = ix.MAX_BLOCKS_HELD


def _block(height: int, txs: list[bytes], events=None):
    """What apply_block hands the bus: a block and its response."""
    results = [ExecTxResult(code=0 if b"=" in tx else 1,
                            data=tx.partition(b"=")[2],
                            events=list(events[i]) if events else [])
               for i, tx in enumerate(txs)]
    return (NS(header=NS(height=height), data=NS(txs=txs)),
            FinalizeBlockResponse(tx_results=results))


def _seeded_blocks(seed: int, heights: int = 6, per: int = 9):
    """Blocks of an application that DOES emit events: two `app` events a
    transaction, as upstream's kvstore, some attributes marked for
    indexing and some not; one transaction of every block repeats one of
    the block before."""
    rng = np.random.default_rng(seed)
    out, last = [], None
    for h in range(1, heights + 1):
        txs = [b"k%d=%s" % (int(rng.integers(4)), rng.bytes(6).hex().encode())
               for _ in range(per)]
        txs[int(rng.integers(per))] = b"no-equals-sign-%d" % h
        if last:
            txs[int(rng.integers(per))] = last[int(rng.integers(per))]
        events = []
        for tx in txs:
            key = tx.partition(b"=")[0].decode()
            events.append([
                ("app", [("creator", "Cosmoshi Netowoko", True),
                         ("key", key, True),
                         ("index_key", "index is working", True),
                         ("noindex_key", "index is not working", False)]),
                ("app", [("key", "second-" + key, True),
                         ("value", tx.hex(), False)]),
            ])
        out.append((h, txs, events))
        last = txs
    return out


def _counter(c) -> float:
    return sum(c.values().values())


@pytest.fixture
def service():
    made = []

    def make(tx_db=None, block_db=None):
        bus = EventBus()
        txi, bli = ix.TxIndexer(tx_db), ix.BlockIndexer(block_db)
        svc = ix.IndexerService(bus, txi, bli)
        made.append(svc)
        return bus, svc

    yield make
    for svc in made:
        svc._sub.fail(RuntimeError("test over"))  # frees any waiter
        svc.stop()


# -- (a) the service against the reference ------------------------------

@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("backend", ("mem", "sqlite"))
def test_the_index_is_the_references(service, tmp_path, seed, backend):
    db = MemKV() if backend == "mem" else open_kv(str(tmp_path / "tx.db"))
    bus, svc = service(db)
    want = ref.Index()
    for h, txs, events in _seeded_blocks(seed):
        bus.publish_block(*_block(h, txs, events))
        want.block(h, txs, events)
    svc.wait(want.height)
    txi = svc.tx_indexer
    # every record, by one read; a repeated transaction holds its LAST place
    for tx_hash, (height, index, tx, code, data) in want.records.items():
        rec = txi.get(tx_hash)
        assert (rec["height"], rec["index"], rec["tx"], rec["code"],
                rec["data"]) == (height, index, tx, code, data)
    assert txi.count() == len(want.records) < 6 * 9  # some repeated
    assert txi.get(ref.tx_hash(b"never sent")) is None
    # the keys beside the records are upstream's, each -> its hash
    keys = {k.decode(): v for k, v in db.iterate_prefix(b"")
            if not k.startswith(b"TX:")}
    assert keys == want.keys
    assert any(k.startswith("app.index_key/index is working/") for k in keys)
    assert not any("noindex" in k or k.startswith("app.value/") for k in keys)


@pytest.mark.parametrize("seed", SEEDS)
def test_search_by_key_gives_the_references_hashes_in_order(service, seed):
    bus, svc = service()
    want = ref.Index()
    for h, txs, events in _seeded_blocks(seed):
        bus.publish_block(*_block(h, txs, events))
        want.block(h, txs, events)
    svc.wait(want.height)
    txi = svc.tx_indexer

    def found(query):
        return [ref.tx_hash(r["tx"]) for r in txi.search(query, limit=1000)]

    def still_there(hashes, height=None):
        # a repeated transaction's record moved to its later block
        return [h for h in hashes
                if height is None or want.records[h][0] == height]

    for h in range(1, want.height + 1):
        assert found(f"tx.height = {h}") == still_there(want.by_height[h], h)
        assert found(f"tm.event = 'Tx' AND tx.height = {h}") == found(
            f"tx.height = {h}")
    for key in ("k0", "k1", "second-k2"):
        assert found(f"app.key = '{key}'") == want.find("app.key", key)
        assert found(f"app.key = '{key}' AND tx.height = 3") == [
            h for h in want.find("app.key", key)
            if want.records[h][0] == 3]
    # hits come in the order of the keys that found them, each hash once
    everything = []
    for h in range(1, want.height + 1):
        everything += [x for x in want.by_height[h] if x not in everything]
    assert found("app.creator = 'Cosmoshi Netowoko'") == everything
    # another operator walks the height keys, and says the same
    assert found("tx.height >= 1") == everything
    assert found("tx.height > 2 AND tx.height <= 4") == [
        h for h in everything if 2 < want.records[h][0] <= 4]
    assert found("app.key CONTAINS 'second-'") == everything
    # an attribute the application did not mark finds nothing
    assert found("app.noindex_key = 'index is not working'") == []
    assert found("app.noindex_key EXISTS") == []
    one = everything[0]
    assert found(f"tx.hash = '{one.hex().upper()}'") == [one]
    assert found("tx.hash = 'zz'") == []
    assert len(txi.search("tx.height >= 1", limit=5)) == 5
    assert svc.block_indexer.search("block.height >= 1") == list(
        range(1, want.height + 1))


def test_one_block_is_one_batch_a_store(service):
    class Counting(MemKV):
        batches = sets = 0

        def write_batch(self, sets, deletes=()):
            Counting.batches += 1
            super().write_batch(sets, deletes)

        def set(self, key, value):
            Counting.sets += 1
            super().set(key, value)

    bus, svc = service(Counting(), Counting())
    before = _counter(indexer_metrics().txs_indexed_total)
    for h in range(1, 6):
        bus.publish_block(*_block(h, [b"a%d=%d" % (h, i)
                                      for i in range(40)]))
    svc.wait(5)
    assert (Counting.batches, Counting.sets) == (5, 5)
    assert _counter(indexer_metrics().txs_indexed_total) - before == 200


# -- (b) nothing is lost, memory is bounded, the publisher is held ------

class _SlowKV(MemKV):
    def __init__(self, delay):
        super().__init__()
        self.delay = delay

    def write_batch(self, sets, deletes=()):
        time.sleep(self.delay)
        super().write_batch(sets, deletes)


def test_a_fast_publisher_loses_nothing_and_is_held_back(service):
    """Eight blocks of 400 published as fast as the bus takes them into a
    store that takes 30 ms a batch: the poll this replaced found 1 of the
    3,200 and never recovered."""
    bus, svc = service(_SlowKV(0.03))
    dropped = _counter(indexer_metrics().events_dropped_total)
    most, waited = 0, 0.0
    t0 = time.perf_counter()
    for h in range(1, 9):
        waited += bus.publish_block(*_block(
            h, [b"h%d.%d=v" % (h, i) for i in range(400)]))
        most = max(most, svc._sub.held)
        assert h - svc.height <= K  # the index trails by at most K blocks
    took = time.perf_counter() - t0
    svc.wait(8)
    assert svc.tx_indexer.count() == 3200
    assert all(len(svc.tx_indexer.search(f"tx.height = {h}", limit=500))
               == 400 for h in range(1, 9))
    assert most == svc.max_held == K
    assert _counter(indexer_metrics().events_dropped_total) == dropped
    # eight batches of 30 ms, K of them still unwritten when the last
    # publish returned: the publisher was held for the other six
    assert took >= (8 - K) * 0.03 and waited >= 0.8 * (8 - K) * 0.03


def test_other_subscribers_keep_the_buffer_and_its_cancellation(service):
    bus, svc = service()
    sub = bus.subscribe("ws", "tm.event = 'Tx'")
    blocks = bus.subscribe("ws", "tm.event = 'NewBlock'")
    bus.publish_block(*_block(1, [b"a=%d" % i for i in range(200)]))
    assert len(sub.drain()) == 200 and not sub.cancelled
    bus.publish_block(*_block(2, [b"b=%d" % i for i in range(300)]))
    assert sub.cancelled  # 256 buffered messages: cancelled, as before
    assert [m.data["block"].header.height for m in blocks.drain()] == [1, 2]
    svc.wait(2)
    assert svc.tx_indexer.count() == 500  # the indexer lost none of them


def test_nobody_subscribed_no_message_is_built(monkeypatch):
    bus = EventBus()
    built = []
    monkeypatch.setattr(bus, "publish_tx", lambda *a, **k: built.append(a))
    assert bus.publish_block(*_block(1, [b"a=1", b"b=2"])) == 0.0
    assert built == []
    bus.subscribe("ws", "tm.event = 'NewBlock'")
    bus.publish_block(*_block(2, [b"a=1", b"b=2"]))
    assert len(built) == 2


# -- (c) stop drains; a failing write surfaces --------------------------

def test_stop_drains_what_was_published(service):
    bus, svc = service(_SlowKV(0.05))
    for h in range(1, K + 1):
        bus.publish_block(*_block(h, [b"s%d=1" % h]))
    svc.stop()
    assert svc.height == K and svc.tx_indexer.count() == K
    assert not svc._thread.is_alive()
    # a block published to a stopped service is nobody's: the bus no
    # longer knows it
    assert bus.publish_block(*_block(K + 1, [b"late=1"])) == 0.0
    assert svc.tx_indexer.count() == K


def test_a_failing_write_stops_the_service_loudly(service):
    class Failing(MemKV):
        def write_batch(self, sets, deletes=()):
            if any(b"boom" in v for _, v in sets):
                raise OSError("disk full")
            super().write_batch(sets, deletes)

    bus, svc = service(Failing())
    dropped = _counter(indexer_metrics().events_dropped_total)
    bus.publish_block(*_block(1, [b"fine=1"]))
    bus.publish_block(*_block(2, [b"boom=1", b"boom=2"]))
    with pytest.raises(ix.IndexerError, match="height 2.*disk full"):
        svc.wait(2)
    with pytest.raises(ix.IndexerError):  # the publisher hears of it
        bus.publish_block(*_block(3, [b"after=1"]))
    with pytest.raises(ix.IndexerError):
        bus.join()
    assert svc.height == 1 and svc.tx_indexer.count() == 1
    # the failed block's three events and the refused block's two
    assert _counter(indexer_metrics().events_dropped_total) - dropped == 5
    assert not svc._thread.is_alive()


def test_wait_refuses_a_height_the_index_does_not_hold(service):
    bus, svc = service()
    bus.publish_block(*_block(1, [b"a=1"]))
    svc.wait(1)
    with pytest.raises(ix.IndexerError, match="holds height 1, not 2"):
        svc.wait(2)


def test_a_held_subscription_wakes_its_publisher_when_it_fails():
    sub = HeldSubscription(1)
    sub.publish("a")
    raised = []

    def publish():
        try:
            sub.publish("b")
        except RuntimeError as e:
            raised.append(e)

    t = threading.Thread(target=publish)
    t.start()
    time.sleep(0.05)
    assert t.is_alive()  # held back: one item unfinished
    sub.fail(RuntimeError("gone"))
    t.join(2)
    assert not t.is_alive() and len(raised) == 1
    with pytest.raises(ValueError):
        HeldSubscription(0)


# -- (d) the replay says the tip is indexed when it is ------------------

def _replay(tmp_path, seed, indexer="kv", db=None):
    from cometbft_tpu.abci.client import AppConns
    from cometbft_tpu.abci.kvstore import KVStoreApp
    from cometbft_tpu.blocksync import ReplayEngine
    from cometbft_tpu.state.execution import BlockExecutor
    from cometbft_tpu.storage import BlockStore
    from cometbft_tpu.utils import factories as fx

    store = BlockStore(MemKV())
    _, final, genesis, _ = fx.make_chain(
        12, n_validators=4, chain_id="ix-chain", seed=seed, backend="cpu",
        txs_per_block=0, app=KVStoreApp(), block_store=store,
        extra_txs=fx.LoadtimeTxs(seed, per_block=5, size=256))
    os.makedirs(tmp_path / "data", exist_ok=True)
    made = ix.open_indexing(indexer, str(tmp_path / "data"))
    if db is not None:
        made.tx_indexer._db = db
    engine = ReplayEngine(
        store, BlockExecutor(AppConns(KVStoreApp()), backend="cpu",
                             event_bus=made.event_bus),
        verify_mode="batched", window=4, backend="cpu")
    return store, genesis, final, made, engine


@pytest.mark.parametrize("seed", SEEDS)
def test_replay_returns_with_the_tip_indexed(tmp_path, seed):
    store, genesis, final, made, engine = _replay(tmp_path, seed)
    slow = made.tx_indexer._db.write_batch

    def write_batch(sets, deletes=()):
        time.sleep(0.01)
        slow(sets, deletes)

    made.tx_indexer._db.write_batch = write_batch
    state, _ = engine.run(genesis.copy())
    # no wait, no stop: run() returned, so the index holds the tip
    assert made.service.height == state.last_block_height == 12
    assert made.service.max_held <= K
    made.stop()
    want = ref.Index()
    for h in range(1, 13):
        want.block(h, store.load_block(h).data.txs)
    txi, bli, dbs = ix.open_indexers(str(tmp_path / "data"))  # anew
    for tx_hash, (height, index, tx, code, data) in want.records.items():
        rec = txi.get(tx_hash)
        assert (rec["height"], rec["index"], rec["tx"], rec["code"],
                rec["data"]) == (height, index, tx, code, data)
    assert txi.count() == 60
    assert bli.search("block.height >= 1") == list(range(1, 13))
    for db in dbs:
        db.close()


def test_a_failing_index_write_fails_the_replay(tmp_path):
    store, genesis, final, made, engine = _replay(tmp_path, 54)
    real = made.tx_indexer._db.write_batch
    calls = []

    def write_batch(sets, deletes=()):
        calls.append(1)
        if len(calls) == 7:
            raise OSError("disk full")
        real(sets, deletes)

    made.tx_indexer._db.write_batch = write_batch
    with pytest.raises(ix.IndexerError, match="height 7"):
        engine.run(genesis.copy())
    assert made.service.height == 6
    made.stop()


def test_apply_block_says_what_the_bus_and_the_indexer_cost(tmp_path):
    store, genesis, final, made, engine = _replay(tmp_path, 55)
    path = str(tmp_path / "spans.jsonl")
    trace.configure(path)
    try:
        engine.run(genesis.copy())
        trace.flush()
    finally:
        trace.disable()
        made.stop()
    with open(path) as f:
        recs = [json.loads(line) for line in f]
    applies = [r for r in recs if r["name"] == "state.apply_block"]
    blocks = [r for r in recs if r["name"] == "index.block"]
    assert len(applies) == len(blocks) == 12
    for r in applies:
        assert r["publish_ms"] >= 0 and r["index_wait_ms"] >= 0
        assert (r["state_save_ms"] + r["publish_ms"] + r["index_wait_ms"]
                <= r["save_events_ms"] + 0.01)
        stages = (r["validate_ms"] + r["finalize_ms"] + r["update_state_ms"]
                  + r["commit_ms"] + r["save_events_ms"])
        assert stages <= r["dur_ms"] + 0.01
    assert [r["height"] for r in blocks] == list(range(1, 13))
    tid = {r["tid"] for r in blocks}
    assert len(tid) == 1 and tid != {applies[0]["tid"]}  # its own thread
    for r in blocks:
        assert r["txs"] == 5 and r["keys"] == 11 and 1 <= r["behind"] <= K
        assert r["tx_bytes"] == 5 * 256 and r["bytes"] > 2 * r["tx_bytes"]
        assert r["encode_ms"] + r["write_ms"] <= r["dur_ms"] + 0.01


# -- (e) the node builds what [tx_index] says ---------------------------

def test_the_section_round_trips_and_refuses_anything_else():
    assert TxIndexConfig().indexer == "kv"
    cfg = Config()
    assert '[tx_index]\nindexer = "kv"' in cfg.to_toml()
    cfg.tx_index.indexer = "null"
    assert Config.from_toml(cfg.to_toml()).tx_index.indexer == "null"
    no_section = "\n\n".join(p for p in Config().to_toml().split("\n\n")
                             if not p.startswith("[tx_index]"))
    assert Config.from_toml(no_section).tx_index.indexer == "kv"
    with pytest.raises(ValueError, match="tx_index.indexer"):
        Config.from_toml(Config().to_toml().replace(
            'indexer = "kv"', 'indexer = "psql"'))
    with pytest.raises(ValueError):
        ix.open_indexing("psql", None)


def test_null_builds_no_indexer_and_no_service():
    made = ix.open_indexing("null", None)
    assert (made.tx_indexer, made.block_indexer, made.service) == (
        None, None, None)
    assert made.event_bus.publish_block(*_block(1, [b"a=1"])) == 0.0
    made.stop()
    kv = ix.open_indexing("kv", None)
    assert isinstance(kv.tx_indexer._db, MemKV)
    assert kv.service._thread.is_alive()
    kv.stop()
    assert not kv.service._thread.is_alive()


def test_the_deployments_file_states_the_programs_defaults():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "catchup-1000v-1ktx-kvindex.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "catchup-1000v-1ktx.json")) as f:
        sibling = json.load(f)
    assert cfg["shapes"]["indexer"] == TxIndexConfig().indexer == "kv"
    assert cfg["shapes"] == dict(sibling["shapes"], indexer="kv")
    assert sibling["shapes"]["indexer"] == "null"
    assert cfg["assumed"]["K"].startswith(f"{ix.MAX_BLOCKS_HELD} = ")
    assert cfg["guarantees"][:8] == sibling["guarantees"]
    assert len(cfg["guarantees"]) == 13
    assert list(cfg["reduced"]) == ["blocks"]
    assert cfg["rehearse"] == sibling["rehearse"]


def _home(tmp_path, indexer="kv", backend="sqlite"):
    from cometbft_tpu.cli import main

    home = str(tmp_path / "n0")
    assert main(["--home", home, "init", "--chain-id", "ix-node"]) == 0
    cfg = Config.load(os.path.join(home, "config/config.toml"))
    assert cfg.tx_index.indexer == "kv"  # what `init` writes
    cfg.base.home = home
    cfg.base.db_backend = backend
    cfg.base.crypto_backend = "cpu"
    cfg.p2p.laddr = "tcp://127.0.0.1:0"
    cfg.rpc.laddr = "tcp://127.0.0.1:0"
    cfg.consensus.timeout_commit = 0.05
    cfg.tx_index.indexer = indexer
    cfg.save(os.path.join(home, "config/config.toml"))
    return home, cfg


def _node(cfg):
    from cometbft_tpu.abci.kvstore import KVStoreApp
    from cometbft_tpu.node import Node

    node = Node(cfg, app=KVStoreApp())
    node.start()
    return node


def _committed(node, tx: bytes) -> int:
    """Sends `tx` and returns the height it was committed at."""
    from cometbft_tpu.rpc.routes import broadcast_tx_commit

    return int(broadcast_tx_commit(node.rpc_env, {"tx": tx.hex()})["height"])


def _tx_route(node, tx: bytes):
    from cometbft_tpu.rpc.routes import tx as route

    return route(node.rpc_env, {"hash": ref.tx_hash(tx).hex()})


def test_a_restarted_node_finds_what_it_indexed_before(tmp_path):
    from cometbft_tpu.cli import main
    from cometbft_tpu.rpc.routes import tx_search

    home, cfg = _home(tmp_path)
    node = _node(cfg)
    try:
        height = _committed(node, b"before=restart")
    finally:
        node.stop()  # drains the indexer: no poll, no deadline
    for name in (ix.TX_INDEX_FILE, ix.BLOCK_INDEX_FILE):
        assert os.path.getsize(os.path.join(home, "data", name)) > 0
    node = _node(cfg)
    try:
        got = _tx_route(node, b"before=restart")
        assert (int(got["height"]), got["tx"].lower()) == (
            height, b"before=restart".hex())
        found = tx_search(node.rpc_env, {"query": f"tx.height = {height}"})
        assert found["total_count"] == "1"
    finally:
        node.stop()
    # reindex-event rebuilds the same files, and the node reads them
    os.remove(os.path.join(home, "data", ix.TX_INDEX_FILE))
    assert main(["--home", home, "reindex-event"]) == 0
    assert _page_size(os.path.join(
        home, "data", ix.TX_INDEX_FILE)) == ix.TX_INDEX_PAGE_BYTES
    node = _node(cfg)
    try:
        assert int(_tx_route(node, b"before=restart")["height"]) == height
    finally:
        node.stop()


def test_a_null_node_starts_no_indexer_thread(tmp_path):
    from cometbft_tpu.cli import main
    from cometbft_tpu.rpc.routes import RPCError, block_search, tx_search

    home, cfg = _home(tmp_path, indexer="null")
    node = _node(cfg)
    try:
        assert node.indexer_service is None and node.tx_indexer is None
        assert not [t for t in threading.enumerate() if t.name == "indexer"]
        height = _committed(node, b"nobody=indexes")
        with pytest.raises(RPCError, match="tx not found"):
            _tx_route(node, b"nobody=indexes")
        assert tx_search(node.rpc_env, {"query": f"tx.height = {height}"}) == {
            "txs": [], "total_count": "0"}
        assert block_search(node.rpc_env, {"query": "block.height >= 1"}) == {
            "blocks": [], "total_count": "0"}
    finally:
        node.stop()
    assert not os.path.exists(os.path.join(home, "data", ix.TX_INDEX_FILE))
    assert main(["--home", home, "reindex-event"]) == 1  # nothing to rebuild


# -- (f) the tx index's file is laid out for its records (PR 43) --------

SQLITE_DEFAULT = 4096
PAGES = (SQLITE_DEFAULT, ix.TX_INDEX_PAGE_BYTES)
LOADED_BLOCKS, LOADED_TXS = 64, 400


def _page_size(path) -> int:
    import sqlite3

    conn = sqlite3.connect(str(path))
    try:
        return conn.execute("PRAGMA page_size").fetchone()[0]
    finally:
        conn.close()


def _loaded_txs(height: int) -> list[bytes]:
    """400 transactions of 1,024 bytes whose value the kvstore hands back
    as the result's data: records of 2.1 KB, as the QA load's."""
    rng = np.random.default_rng(4300 + height)
    return [b"k=" + rng.bytes(511).hex().encode() for _ in range(LOADED_TXS)]


def _open(dir_, page):
    """The indexers on `dir_`; for sqlite's page the tx index's file is
    made first, as a node did before PR 43."""
    from cometbft_tpu.storage.kv import SqliteKV

    os.makedirs(dir_, exist_ok=True)
    if page == SQLITE_DEFAULT:
        SqliteKV(os.path.join(dir_, ix.TX_INDEX_FILE)).close()
    return ix.open_indexers(str(dir_))


@pytest.fixture(scope="module")
def two_histories(tmp_path_factory):
    """The same 64 loaded blocks indexed on a file of each page size, the
    files closed; page -> (directory, reference)."""
    out = {}
    for page in PAGES:
        dir_ = tmp_path_factory.mktemp(f"ix{page}")
        txi, bli, dbs = _open(dir_, page)
        want = ref.Index()
        for h in range(1, LOADED_BLOCKS + 1):
            block, resp = _block(h, _loaded_txs(h))
            txi.add_batch(h, block.data.txs, resp.tx_results)
            bli.index(h)
            want.block(h, block.data.txs)
        for db in dbs:
            db.close()
        out[page] = (dir_, want)
    return out


def test_a_new_tx_index_has_pages_for_its_records(tmp_path):
    txi, bli, dbs = ix.open_indexers(str(tmp_path))
    plain = open_kv(str(tmp_path / "plain.db"))
    try:
        assert txi.page_bytes == dbs[0].page_bytes == ix.TX_INDEX_PAGE_BYTES
        assert dbs[1].page_bytes == plain.page_bytes == SQLITE_DEFAULT
    finally:
        for db in dbs + (plain,):
            db.close()
    assert _page_size(tmp_path / ix.TX_INDEX_FILE) == ix.TX_INDEX_PAGE_BYTES
    assert _page_size(tmp_path / ix.BLOCK_INDEX_FILE) == SQLITE_DEFAULT
    assert _page_size(tmp_path / "plain.db") == SQLITE_DEFAULT
    # in memory there is no file and no page
    txi, _, _ = ix.open_indexers(None)
    assert txi.page_bytes == 0


def test_an_index_made_before_keeps_its_pages_and_its_records(tmp_path):
    txi, bli, dbs = _open(tmp_path, SQLITE_DEFAULT)
    want = ref.Index()
    for h, txs, events in _seeded_blocks(51):
        block, resp = _block(h, txs, events)
        txi.add_batch(h, txs, resp.tx_results)
        want.block(h, txs, events)
    for db in dbs:
        db.close()
    txi, bli, dbs = ix.open_indexers(str(tmp_path))  # the node, restarted
    try:
        assert txi.page_bytes == SQLITE_DEFAULT
        for tx_hash, (height, index, tx, code, data) in want.records.items():
            rec = txi.get(tx_hash)
            assert (rec["height"], rec["index"], rec["tx"]) == (
                height, index, tx)
        h = want.height + 1
        block, resp = _block(h, _loaded_txs(h))
        st = txi.add_batch(h, block.data.txs, resp.tx_results)
        want.block(h, block.data.txs)
        assert st.txs == LOADED_TXS
        assert txi.count() == len(want.records)
        assert [r["index"] for r in txi.search(
            f"tx.height = {h}", limit=LOADED_TXS)] == list(range(LOADED_TXS))
    finally:
        for db in dbs:
            db.close()
    assert _page_size(tmp_path / ix.TX_INDEX_FILE) == SQLITE_DEFAULT


def _frames_of_one_more_batch(dir_, page, tmp_path) -> float:
    """Frames that block 65's batch leaves in the write-ahead log of a COPY
    of the history: one a page it dirtied, whatever the host."""
    import shutil

    copy = tmp_path / f"copy{page}"
    shutil.copytree(dir_, copy)
    txi, bli, dbs = ix.open_indexers(str(copy))
    try:
        assert txi.page_bytes == page
        conn = dbs[0]._conn
        conn.execute("PRAGMA wal_autocheckpoint=0")
        conn.execute("PRAGMA wal_checkpoint(TRUNCATE)").fetchall()
        h = LOADED_BLOCKS + 1
        block, resp = _block(h, _loaded_txs(h))
        st = txi.add_batch(h, block.data.txs, resp.tx_results)
        assert 2000 < st.bytes / LOADED_TXS < 2300  # a record and its key
        wal = os.path.getsize(copy / (ix.TX_INDEX_FILE + "-wal"))
    finally:
        for db in dbs:
            db.close()
    return (wal - 32) / (24 + page)


def test_a_blocks_batch_dirties_under_half_the_pages(two_histories,
                                                     tmp_path):
    """The regression PR 43 removes, held by count and not by time: on a
    4,096-byte file every 2.1 KB record takes a page of its own."""
    frames = {page: _frames_of_one_more_batch(two_histories[page][0], page,
                                              tmp_path)
              for page in PAGES}
    assert frames[SQLITE_DEFAULT] > LOADED_TXS * 1.5  # a page a record, and
    # a leaf of the key's b-tree for most of them
    assert frames[ix.TX_INDEX_PAGE_BYTES] < frames[SQLITE_DEFAULT] / 2
    assert frames[ix.TX_INDEX_PAGE_BYTES] < LOADED_TXS


@pytest.mark.parametrize("page", PAGES)
def test_both_page_sizes_answer_as_the_reference(two_histories, page):
    dir_, want = two_histories[page]
    txi, bli, dbs = ix.open_indexers(str(dir_))  # a new connection
    try:
        assert txi.page_bytes == page
        assert txi.count() == len(want.records) == LOADED_BLOCKS * LOADED_TXS
        for tx_hash, (height, index, tx, code, data) in want.records.items():
            rec = txi.get(tx_hash)
            assert (rec["height"], rec["index"], rec["tx"], rec["code"],
                    rec["data"]) == (height, index, tx, code, data)
        assert txi.get(ref.tx_hash(b"never sent")) is None
        for h in (1, 17, LOADED_BLOCKS):
            found = txi.search(f"tx.height = {h}", limit=2 * LOADED_TXS)
            assert [ref.tx_hash(r["tx"]) for r in found] == want.by_height[h]
        assert [r["height"] for r in txi.search(
            f"tx.height > {LOADED_BLOCKS - 1}", limit=3)] == [LOADED_BLOCKS] * 3
        one = want.by_height[9][5]
        assert [r["index"] for r in txi.search(
            f"tx.hash = '{one.hex().upper()}'")] == [5]
        assert bli.search("block.height >= 1", limit=100) == list(
            range(1, LOADED_BLOCKS + 1))
    finally:
        for db in dbs:
            db.close()


@pytest.mark.parametrize("page", (0,) + PAGES)
def test_index_block_says_the_files_page_size(tmp_path, page):
    if page:
        txi, bli, dbs = _open(tmp_path, page)
    else:
        txi, bli, dbs = ix.open_indexers(None)
    bus = EventBus()
    svc = ix.IndexerService(bus, txi, bli)
    path = str(tmp_path / "spans.jsonl")
    trace.configure(path)
    try:
        for h in (1, 2):
            bus.publish_block(*_block(h, [b"a%d=%d" % (h, i)
                                          for i in range(7)]))
        svc.wait(2)
        trace.flush()
    finally:
        trace.disable()
        svc.stop()
        for db in dbs:
            db.close()
    with open(path) as f:
        spans = [r for r in map(json.loads, f) if r["name"] == "index.block"]
    assert [r["page_bytes"] for r in spans] == [page, page]
