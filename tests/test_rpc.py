"""RPC + pubsub + indexer tests (reference rpc/jsonrpc tests, pubsub query
tests, kv indexer tests)."""

import json
import os
import time

import pytest

from cometbft_tpu.utils.pubsub import PubSubServer, Query


# ------------------------------------------------------------- query ----
def test_query_language():
    q = Query("tm.event = 'NewBlock' AND tx.height > 5")
    assert q.matches({"tm.event": ["NewBlock"], "tx.height": ["6"]})
    assert not q.matches({"tm.event": ["NewBlock"], "tx.height": ["5"]})
    assert not q.matches({"tm.event": ["Tx"], "tx.height": ["9"]})
    assert Query("tx.hash EXISTS").matches({"tx.hash": ["AB"]})
    assert not Query("tx.hash EXISTS").matches({})
    assert Query("app.key CONTAINS 'ell'").matches({"app.key": ["hello"]})
    assert Query("x.y != 'a'").matches({"x.y": ["b"]})
    with pytest.raises(ValueError):
        Query("")
    with pytest.raises(ValueError):
        Query("tm.event ~ 'x'")


def test_dump_trace_name_and_kind_filters(tmp_path):
    """dump_trace honors `name` (substring) and `kind` (exact) filter
    params — the GET-URI dispatch hands them over as strings, so this
    drives the handler exactly as /dump_trace?name=...&kind=... does."""
    from cometbft_tpu.rpc.routes import dump_trace
    from cometbft_tpu.utils import trace

    trace.configure(os.path.join(str(tmp_path), "trace.jsonl"))
    try:
        for h in range(3):
            trace.event("p2p.recv", msg="vote", height=h)
            trace.event("p2p.send", msg="vote", height=h)
            trace.emit("state.apply_block", "span", height=h, dur_ms=1.0)
        res = dump_trace(None, {"n": "50"})
        # configure() wrote trace.clock first, behind its thread's name
        assert [r["name"] for r in res["records"]][:2] == [
            "trace.thread", "trace.clock"]
        assert len(res["records"]) == 2 + 9
        res = dump_trace(None, {"n": "50", "name": "p2p.recv"})
        assert [r["name"] for r in res["records"]] == ["p2p.recv"] * 3
        # substring match catches both directions of the wire hooks
        res = dump_trace(None, {"n": "50", "name": "p2p."})
        assert len(res["records"]) == 6
        # kind narrows to spans; combined filters intersect
        res = dump_trace(None, {"n": "50", "kind": "span"})
        assert [r["name"] for r in res["records"]] == (
            ["state.apply_block"] * 3
        )
        res = dump_trace(None, {"n": "1", "name": "p2p.", "kind": "event"})
        assert len(res["records"]) == 1
        assert res["records"][0]["height"] == 2
        # no matches -> empty, not an error
        assert dump_trace(None, {"name": "nope"})["records"] == []
    finally:
        trace.disable()
    assert dump_trace(None, {})["enabled"] is False


def test_pubsub_routing():
    srv = PubSubServer()
    sub_blocks = srv.subscribe("c1", "tm.event = 'NewBlock'")
    sub_all_tx = srv.subscribe("c1", "tm.event = 'Tx' AND tx.height >= 2")
    srv.publish("blk1", {"tm.event": ["NewBlock"]})
    srv.publish("tx1", {"tm.event": ["Tx"], "tx.height": ["1"]})
    srv.publish("tx2", {"tm.event": ["Tx"], "tx.height": ["2"]})
    assert [m.data for m in sub_blocks.drain()] == ["blk1"]
    assert [m.data for m in sub_all_tx.drain()] == ["tx2"]
    srv.unsubscribe_all("c1")
    srv.publish("blk2", {"tm.event": ["NewBlock"]})
    assert sub_blocks.drain() == []


# --------------------------------------------------------- full node ----
@pytest.fixture(scope="module")
def rpc_node(tmp_path_factory):
    from cometbft_tpu.abci.kvstore import KVStoreApp
    from cometbft_tpu.config import Config
    from cometbft_tpu.node import Node
    from cometbft_tpu.privval import FilePV
    from cometbft_tpu.types import Timestamp
    from cometbft_tpu.types.genesis import GenesisDoc, GenesisValidator

    home = str(tmp_path_factory.mktemp("rpcnode"))
    os.makedirs(os.path.join(home, "config"), exist_ok=True)
    pv = FilePV.generate(None, None)
    genesis = GenesisDoc(
        chain_id="rpc-chain", genesis_time=Timestamp(1_700_000_000, 0),
        validators=[GenesisValidator(pv.pub_key().bytes(), 10, "v0")],
    )
    cfg = Config()
    cfg.base.home = home
    cfg.base.db_backend = "mem"
    cfg.base.crypto_backend = "cpu"
    cfg.p2p.laddr = "tcp://127.0.0.1:0"
    cfg.rpc.laddr = "tcp://127.0.0.1:0"
    cfg.consensus.timeout_propose = 0.5
    cfg.consensus.timeout_commit = 0.05
    genesis.save(os.path.join(home, "config/genesis.json"))
    with open(os.path.join(home, "config/priv_validator_key.json"), "w") as f:
        json.dump({
            "address": pv.pub_key().address().hex(),
            "pub_key": pv.pub_key().bytes().hex(),
            "priv_key": pv._priv.bytes().hex(),
        }, f)
    node = Node(cfg, app=KVStoreApp())
    node.start()
    deadline = time.monotonic() + 30
    while node.consensus.sm_state.last_block_height < 2:
        assert time.monotonic() < deadline, "single-node chain stalled"
        time.sleep(0.1)
    yield node
    node.stop()


def test_rpc_http_roundtrip(rpc_node):
    from cometbft_tpu.rpc import HTTPClient

    host, port = rpc_node.rpc_addr
    c = HTTPClient(f"http://{host}:{port}")
    assert c.health() == {}
    st = c.status()
    assert st["node_info"]["network"] == "rpc-chain"
    assert int(st["sync_info"]["latest_block_height"]) >= 2
    blk = c.block(height=1)
    assert blk["block"]["header"]["height"] == "1"
    hdr = c.header(height=1)
    assert hdr["header"]["chain_id"] == "rpc-chain"
    cm = c.commit(height=1)
    assert cm["signed_header"]["commit"]["height"] == "1"
    vals = c.validators(height=1)
    assert vals["count"] == "1"
    gen = c.genesis()
    assert gen["genesis"]["chain_id"] == "rpc-chain"
    ni = c.net_info()
    assert ni["n_peers"] == "0"
    cs = c.consensus_state()
    assert int(cs["round_state"]["height"]) >= 2
    ai = c.abci_info()
    assert int(ai["response"]["last_block_height"]) >= 1
    # URI style GET
    import urllib.request

    with urllib.request.urlopen(f"http://{host}:{port}/health") as resp:
        out = json.loads(resp.read())
    assert out["result"] == {}


def test_rpc_broadcast_and_tx_search(rpc_node):
    from cometbft_tpu.rpc import HTTPClient

    host, port = rpc_node.rpc_addr
    c = HTTPClient(f"http://{host}:{port}")
    tx = b"rpc-test=42"
    res = c.broadcast_tx_commit(tx=tx.hex())
    assert res["tx_result"]["code"] == 0
    height = int(res["height"])
    # indexer catches up async
    deadline = time.monotonic() + 10
    rec = None
    while time.monotonic() < deadline:
        try:
            rec = c.tx(hash=res["hash"].lower())
            break
        except RuntimeError:
            time.sleep(0.1)
    assert rec is not None and int(rec["height"]) == height
    found = c.tx_search(query=f"tx.height = {height}")
    assert int(found["total_count"]) >= 1
    # abci query sees the key
    q = c.abci_query(path="/store", data=b"rpc-test".hex())
    assert bytes.fromhex(q["response"]["value"]) == b"42"


def test_rpc_info_routes(rpc_node):
    """blockchain / header_by_hash / check_tx / dump_consensus_state
    (reference rpc/core/routes.go:23-62)."""
    from cometbft_tpu.rpc import HTTPClient

    host, port = rpc_node.rpc_addr
    c = HTTPClient(f"http://{host}:{port}")
    latest = int(c.status()["sync_info"]["latest_block_height"])

    bc = c.blockchain()
    assert int(bc["last_height"]) >= latest
    # the node keeps committing between the two RPCs: compare against
    # the height THIS response reports, not the earlier status call
    assert len(bc["block_metas"]) == min(int(bc["last_height"]), 20)
    hs = [int(m["header"]["height"]) for m in bc["block_metas"]]
    assert hs == sorted(hs, reverse=True), "newest first"
    assert int(bc["block_metas"][0]["block_size"]) > 0
    # explicit window + the reference's min>max error
    bc2 = c.blockchain(min_height=1, max_height=2)
    assert [int(m["header"]["height"]) for m in bc2["block_metas"]] == [2, 1]
    with pytest.raises(RuntimeError):
        c.blockchain(min_height=5, max_height=2)

    want = bc2["block_metas"][0]["block_id"]["hash"]
    hdr = c.header_by_hash(hash=want.lower())
    assert hdr["header"]["height"] == "2"
    assert c.header_by_hash(hash="ab" * 32)["header"] is None

    ct = c.check_tx(tx=b"ct-key=1".hex())
    assert ct["code"] == 0
    # check_tx must NOT enqueue: the mempool is untouched
    assert c.num_unconfirmed_txs()["n_txs"] == "0"

    dump = c.dump_consensus_state()
    assert int(dump["round_state"]["height"]) >= latest
    hvs = dump["round_state"]["height_vote_set"]
    assert isinstance(hvs, list) and hvs, "rounds present"
    assert "votes_bit_array" in (hvs[0]["prevotes"] or hvs[0]["precommits"])
    assert dump["peers"] == []  # single node


def test_rpc_unsafe_flush_mempool(rpc_node):
    from cometbft_tpu.rpc.routes import Env, unsafe_flush_mempool

    rpc_node.mempool.check_tx(b"flush-me=1")
    assert rpc_node.mempool.size() == 1
    env = Env(mempool=rpc_node.mempool)
    assert unsafe_flush_mempool(env, {}) == {}
    assert rpc_node.mempool.size() == 0


def test_rpc_websocket_subscribe(rpc_node):
    import base64
    import socket

    host, port = rpc_node.rpc_addr
    s = socket.create_connection((host, port), timeout=10)
    key = base64.b64encode(os.urandom(16)).decode()
    s.sendall(
        f"GET /websocket HTTP/1.1\r\nHost: {host}\r\nUpgrade: websocket\r\n"
        f"Connection: Upgrade\r\nSec-WebSocket-Key: {key}\r\n"
        f"Sec-WebSocket-Version: 13\r\n\r\n".encode()
    )
    resp = s.recv(4096)
    assert b"101" in resp.split(b"\r\n")[0]

    def send_text(payload: str):
        data = payload.encode()
        mask = os.urandom(4)
        frame = bytearray([0x81, 0x80 | len(data)])
        frame += mask
        frame += bytes(b ^ mask[i % 4] for i, b in enumerate(data))
        s.sendall(frame)

    def read_text():
        head = s.recv(2)
        n = head[1] & 0x7F
        if n == 126:
            import struct as st

            n = st.unpack(">H", s.recv(2))[0]
        buf = b""
        while len(buf) < n:
            buf += s.recv(n - len(buf))
        return json.loads(buf)

    send_text(json.dumps({
        "jsonrpc": "2.0", "id": 1, "method": "subscribe",
        "params": {"query": "tm.event = 'NewBlock'"},
    }))
    ack = read_text()
    assert ack["id"] == 1 and "result" in ack
    s.settimeout(20)
    evt = read_text()
    assert evt["result"]["data"]["type"] == "NewBlock"
    s.close()


def test_rpc_tx_prove_and_pagination(rpc_node):
    """tx?prove=true returns a verifying merkle inclusion proof, and the
    search routes honor page/per_page/order_by (reference rpc/core/tx.go
    + types/tx.go:79)."""
    import base64

    from cometbft_tpu.crypto.merkle import Proof
    from cometbft_tpu.rpc import HTTPClient

    host, port = rpc_node.rpc_addr
    c = HTTPClient(f"http://{host}:{port}")
    txs = [b"prove-%d=%d" % (i, i) for i in range(3)]
    heights = []
    res = None
    for tx in txs:
        res = c.broadcast_tx_commit(tx=tx.hex())
        assert res["tx_result"]["code"] == 0
        heights.append(int(res["height"]))
    deadline = time.monotonic() + 10
    rec = None
    while time.monotonic() < deadline:
        try:
            rec = c.tx(hash=res["hash"].lower(), prove=True)
            break
        except RuntimeError:
            time.sleep(0.1)
    assert rec is not None and "proof" in rec, rec
    pf = rec["proof"]
    proof = Proof(
        total=int(pf["proof"]["total"]),
        index=int(pf["proof"]["index"]),
        leaf_hash=base64.b64decode(pf["proof"]["leaf_hash"]),
        aunts=[base64.b64decode(a) for a in pf["proof"]["aunts"]],
    )
    from cometbft_tpu.types.block import tx_hash

    # proof leaves are tx hashes (reference types/tx.go Txs.Proof)
    assert proof.verify(bytes.fromhex(pf["root_hash"]), tx_hash(txs[-1]))
    # the proven root is the block's data hash
    blk = c.block(height=str(heights[-1]))
    assert (
        blk["block"]["header"]["data_hash"].lower()
        == pf["root_hash"].lower()
    )

    # pagination + ordering over everything indexed so far
    all_res = c.tx_search(query=f"tx.height > 0", per_page=2, page=1)
    total = int(all_res["total_count"])
    assert total >= 3 and len(all_res["txs"]) == 2
    asc = c.tx_search(query="tx.height > 0", per_page=100, order_by="asc")
    desc = c.tx_search(query="tx.height > 0", per_page=100, order_by="desc")
    ah = [int(t["height"]) for t in asc["txs"]]
    dh = [int(t["height"]) for t in desc["txs"]]
    assert ah == sorted(ah) and dh == sorted(dh, reverse=True)
    # out-of-range page errors
    try:
        c.tx_search(query="tx.height > 0", per_page=2, page=9999)
        raise AssertionError("expected out-of-range page error")
    except RuntimeError:
        pass
    # block_search paginates too
    bs = c.block_search(query="block.height >= 1", per_page=1, page=1,
                        order_by="desc")
    assert len(bs["blocks"]) == 1 and int(bs["total_count"]) >= 1


def test_dump_trace_limit_param_and_cap(tmp_path):
    """dump_trace `limit` (alias of the older `n`): defaults to the
    last 100 records, serves the newest ones, and clamps to the
    documented [1, 1000] bounds instead of erroring."""
    from cometbft_tpu.rpc.routes import dump_trace
    from cometbft_tpu.utils import trace

    trace.configure(os.path.join(str(tmp_path), "trace.jsonl"))
    try:
        for h in range(150):
            trace.event("p2p.recv", msg="vote", height=h)
        assert len(dump_trace(None, {})["records"]) == 100
        res = dump_trace(None, {"limit": "5"})
        assert len(res["records"]) == 5
        assert res["records"][-1]["height"] == 149  # newest tail
        assert len(dump_trace(None, {"n": "7"})["records"]) == 7
        # explicit limit wins over the legacy alias
        assert len(dump_trace(None, {"limit": "3", "n": "9"})["records"]) == 3
        # clamped, not an error (configure()'s trace.thread and
        # trace.clock lead; the module's live node may add records of its
        # own threads, the indexer's among them, so count this thread's)
        got = dump_trace(None, {"limit": "100000"})["records"]
        assert len([r for r in got if r["name"] in (
            "p2p.recv", "trace.clock")]) == 151 and len(got) >= 152
        assert len(dump_trace(None, {"limit": "0"})["records"]) == 1
    finally:
        trace.disable()
