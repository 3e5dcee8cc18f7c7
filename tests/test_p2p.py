"""P2P stack tests: secret connection, mconnection, switch
(reference p2p/conn/secret_connection_test.go, connection_test.go,
switch_test.go)."""

import socket
import threading
import time

import pytest

from cometbft_tpu.p2p import (
    ChannelDescriptor,
    MConnection,
    NodeInfo,
    NodeKey,
    Reactor,
    SecretConnection,
    Switch,
    Transport,
)
from cometbft_tpu.p2p.secret_connection import AuthError


def _sock_pair():
    a, b = socket.socketpair()
    return a, b


def _sc_pair():
    a, b = _sock_pair()
    ka, kb = NodeKey.generate(), NodeKey.generate()
    out = {}

    def side(name, sock, key):
        out[name] = SecretConnection(sock, key.priv_key)

    ta = threading.Thread(target=side, args=("a", a, ka))
    tb = threading.Thread(target=side, args=("b", b, kb))
    ta.start(); tb.start(); ta.join(5); tb.join(5)
    return out["a"], out["b"], ka, kb


def test_secret_connection_roundtrip_and_identity():
    sca, scb, ka, kb = _sc_pair()
    assert sca.remote_pub_key.bytes() == kb.priv_key.pub_key().bytes()
    assert scb.remote_pub_key.bytes() == ka.priv_key.pub_key().bytes()
    sca.write_msg(b"hello over encrypted channel")
    assert scb.read_msg() == b"hello over encrypted channel"
    big = bytes(range(256)) * 40  # > one frame
    scb.write_msg(big)
    assert sca.read_msg() == big


def test_secret_connection_detects_corruption():
    """Flipping sealed bytes must break AEAD decryption (fuzz one frame)."""
    a, b = _sock_pair()
    ka, kb = NodeKey.generate(), NodeKey.generate()
    out = {}
    t = threading.Thread(
        target=lambda: out.setdefault("b", SecretConnection(b, kb.priv_key))
    )
    t.start()
    sca = SecretConnection(a, ka.priv_key)
    t.join(5)
    scb = out["b"]
    # corrupt ciphertext in transit: write a sealed frame, tamper mid-socket
    raw_a, raw_b = _sock_pair()
    sca._sock = raw_a  # route future frames through a tap

    def tamper():
        data = raw_b.recv(65536)
        data = bytes([data[0] ^ 0xFF]) + data[1:]
        scb._sock = _FakeSock(data)

    sca.write_msg(b"payload")
    tamper()
    with pytest.raises(Exception):
        scb.read_msg()


class _FakeSock:
    def __init__(self, data):
        self._data = data

    def recv(self, n):
        out, self._data = self._data[:n], self._data[n:]
        return out

    def close(self):
        pass


def test_mconnection_channels_and_priorities():
    sca, scb, _, _ = _sc_pair()
    got = []
    done = threading.Event()

    def on_recv(chan, msg):
        got.append((chan, msg))
        if len(got) >= 3:
            done.set()

    descs = [ChannelDescriptor(0x20, priority=5), ChannelDescriptor(0x21, priority=1)]
    ma = MConnection(sca, descs, lambda c, m: None)
    mb = MConnection(scb, descs, on_recv)
    ma.start(); mb.start()
    try:
        assert ma.send(0x20, b"votes")
        assert ma.send(0x21, b"x" * 5000)  # multi-packet
        assert ma.send(0x20, b"more-votes")
        assert not ma.send(0x99, b"no such channel")
        assert done.wait(5), f"got {got}"
        by_chan = {}
        for c, m in got:
            by_chan.setdefault(c, []).append(m)
        assert by_chan[0x20] == [b"votes", b"more-votes"]
        assert by_chan[0x21] == [b"x" * 5000]
    finally:
        ma.stop(); mb.stop()


class EchoReactor(Reactor):
    def __init__(self, chan=0x30):
        self.chan = chan
        self.received = []
        self.peers = []
        self.event = threading.Event()

    def channels(self):
        return [ChannelDescriptor(self.chan, priority=3)]

    def receive(self, chan_id, peer, msg):
        self.received.append((peer.id, msg))
        self.event.set()

    def add_peer(self, peer):
        self.peers.append(peer)


def _make_switch(chain="p2p-chain"):
    nk = NodeKey.generate()
    info = NodeInfo(node_id=nk.node_id(), network=chain, moniker="t")
    tr = Transport(nk, info)
    sw = Switch(tr)
    r = EchoReactor()
    sw.add_reactor(r)
    tr.listen()
    sw.start()
    return sw, r, tr


def test_switch_dial_and_broadcast():
    sw1, r1, t1 = _make_switch()
    sw2, r2, t2 = _make_switch()
    try:
        host, port = t1.node_info.listen_addr.split(":")
        peer = sw2.dial_peer(host, int(port))
        assert peer.id == t1.node_info.node_id
        # wait for sw1 to register the inbound peer
        deadline = time.monotonic() + 20
        while not sw1.peers() and time.monotonic() < deadline:
            time.sleep(0.02)
        assert len(sw1.peers()) == 1
        sw2.broadcast(0x30, b"gossip")
        assert r1.event.wait(5)
        assert r1.received[0][1] == b"gossip"
        # and back
        sw1.broadcast(0x30, b"reply")
        assert r2.event.wait(5)
        assert r2.received[0][1] == b"reply"
    finally:
        sw1.stop(); sw2.stop()


def test_switch_rejects_wrong_network():
    sw1, r1, t1 = _make_switch(chain="chain-A")
    sw2, r2, t2 = _make_switch(chain="chain-B")
    try:
        host, port = t1.node_info.listen_addr.split(":")
        with pytest.raises(Exception):
            sw2.dial_peer(host, int(port))
    finally:
        sw1.stop(); sw2.stop()


# ------------------------------------------------------------------ pex --
def test_addrbook_groups_and_persistence(tmp_path):
    from cometbft_tpu.p2p.pex import AddrBook, NetAddress

    path = str(tmp_path / "addrbook.json")
    book = AddrBook(path)
    a1 = NetAddress("id1", "10.0.0.1", 26656)
    a2 = NetAddress("id2", "10.0.0.2", 26656)
    assert book.add_address(a1, "src") and book.add_address(a2, "src")
    assert not book.add_address(a1, "src")  # dup
    assert not book.add_address(NetAddress("", "x", 1))  # invalid
    book.mark_good("id1")
    assert book.size() == 2
    book.mark_bad("id2")
    assert book.size() == 1
    assert not book.add_address(a2, "src")  # banned stays out
    book.save()
    book2 = AddrBook(path)
    assert book2.has("id1") and not book2.has("id2")
    assert book2.pick_address().node_id == "id1"


def test_pex_wire_roundtrip():
    from cometbft_tpu.p2p.pex import (
        NetAddress,
        decode_pex_message,
        encode_pex_addrs,
        encode_pex_request,
    )

    kind, _ = decode_pex_message(encode_pex_request())
    assert kind == "request"
    addrs = [NetAddress("n1", "1.2.3.4", 1000), NetAddress("n2", "::1", 2)]
    kind, got = decode_pex_message(encode_pex_addrs(addrs))
    assert kind == "addrs" and got == addrs


def test_pex_gossip_and_dial(tmp_path):
    """Three nodes: C knows only B; B knows A's address. After PEX
    gossip + ensure_peers, C dials A (reference pex_reactor flow)."""
    from cometbft_tpu.p2p.pex import AddrBook, PexReactor

    def make(name):
        nk = NodeKey.generate()
        info = NodeInfo(node_id=nk.node_id(), network="pex-chain", moniker=name)
        tr = Transport(nk, info)
        sw = Switch(tr)
        book = AddrBook(str(tmp_path / f"{name}.json"))
        pex = PexReactor(book, target_outbound=4)
        pex.set_switch(sw)
        sw.add_reactor(pex)
        tr.listen()
        sw.start()
        return sw, tr, book, pex

    sw_a, t_a, book_a, _ = make("a")
    sw_b, t_b, book_b, pex_b = make("b")
    sw_c, t_c, book_c, pex_c = make("c")
    try:
        host_a, port_a = t_a.node_info.listen_addr.split(":")
        host_b, port_b = t_b.node_info.listen_addr.split(":")
        # B learns A by dialing it
        sw_b.dial_peer(host_a, int(port_a))
        book_b.add_address(
            __import__("cometbft_tpu.p2p.pex", fromlist=["NetAddress"]
                       ).NetAddress(t_a.node_info.node_id, host_a, int(port_a)),
            "manual",
        )
        # C dials B; pex request/response should teach C about A.
        # Load-adaptive: under a full-suite run the one-shot request can
        # race reactor startup, so re-ask periodically instead of
        # sleeping a fixed schedule (it flaked in round 3).
        from cometbft_tpu.p2p.pex import PEX_CHANNEL, encode_pex_request

        peer_b = sw_c.dial_peer(host_b, int(port_b))
        deadline = time.monotonic() + 30
        last_ask = time.monotonic()
        while not book_c.has(t_a.node_info.node_id) and time.monotonic() < deadline:
            if time.monotonic() - last_ask > 2.0:
                peer_b.send(PEX_CHANNEL, encode_pex_request())
                last_ask = time.monotonic()
            time.sleep(0.05)
        assert book_c.has(t_a.node_info.node_id), "C never learned A via PEX"
        deadline = time.monotonic() + 30
        while len(sw_c.peers()) < 2 and time.monotonic() < deadline:
            pex_c.ensure_peers()
            time.sleep(0.25)
        assert any(p.id == t_a.node_info.node_id for p in sw_c.peers())
    finally:
        sw_a.stop(); sw_b.stop(); sw_c.stop()


def test_addrbook_restart_roundtrip(tmp_path):
    """Entries, bucket placement, the old/new split, attempt counters,
    and bans must all survive save -> load -> save -> load (reference
    addrbook.go saveToFile/loadFromFile)."""
    from cometbft_tpu.p2p.pex import AddrBook, NetAddress

    path = str(tmp_path / "book.json")
    book = AddrBook(path)
    for i in range(12):
        assert book.add_address(
            NetAddress(f"id{i}", f"10.{i}.0.1", 26656), source=f"src{i % 3}"
        )
    for i in range(4):  # promote a third of them
        book.mark_good(f"id{i}")
    for i in range(4, 9):
        book.mark_attempt(f"id{i}")
    book.mark_bad("id11")
    book.save()

    for _restart in range(2):  # two restarts, not just one round trip
        book = AddrBook(path)
        book.save()
    assert book.counts() == (7, 4)  # id11 removed; 4 old, 7 new
    for i in range(12):
        ka, orig_old = book.known(f"id{i}"), i < 4
        if i == 11:
            assert ka is None
            assert not book.add_address(
                NetAddress("id11", "10.11.0.1", 26656)
            )  # still banned
            continue
        assert ka is not None
        assert ka.is_old == orig_old
        assert ka.attempts == (1 if 4 <= i < 9 else 0)
    # bucket assignment is stable across reloads (same persisted key)
    fresh = AddrBook(path)
    for nid, ka in fresh._addrs.items():
        assert book.known(nid).bucket == ka.bucket


def test_addrbook_promotion_eviction_and_demotion():
    """One (addr-group, src-group) pair maps to ONE new bucket, so 65+
    same-group adds exercise eviction; mass promotion within one /16
    overflows its <= 4 old buckets and demotes back to new (reference
    expireNew / moveToOld displacement)."""
    from cometbft_tpu.p2p.addrbook import BUCKET_SIZE, AddrBook, NetAddress

    book = AddrBook()
    # stale entries go first when the bucket is full
    for i in range(BUCKET_SIZE):
        assert book.add_address(
            NetAddress(f"n{i}", f"10.1.{i // 256}.{i % 256}", 1000 + i),
            source="gossiper",
        )
    for i in range(3):  # 3 stale: repeated failures, never a success
        for _ in range(3):
            book.mark_attempt(f"n{i}")
    assert book.size() == BUCKET_SIZE
    assert book.add_address(
        NetAddress("overflow0", "10.1.200.200", 2000), source="gossiper"
    )
    assert book.size() == BUCKET_SIZE  # someone was evicted...
    assert not book.has("n0")  # ...and it was the stale entry

    # promotion flips the counts
    book.mark_good("n10")
    new_n, old_n = book.counts()
    assert (new_n, old_n) == (BUCKET_SIZE - 1, 1)
    assert book.known("n10").is_old

    # old-bucket overflow demotes (never silently drops) entries
    book2 = AddrBook()
    total = 280  # > OLD_BUCKETS_PER_GROUP * BUCKET_SIZE = 256
    for i in range(total):
        assert book2.add_address(
            NetAddress(f"v{i}", f"44.44.{i // 256}.{i % 256}", 3000 + i),
            source=f"s{i % 7}",
        )
        book2.mark_good(f"v{i}")
    new_n, old_n = book2.counts()
    assert old_n <= 4 * BUCKET_SIZE
    assert new_n + old_n == total  # demoted, not lost
    assert new_n >= total - 4 * BUCKET_SIZE


def test_addrbook_biased_selection_distribution():
    """pick_address draws from the old group ~70% of the time when both
    groups are populated (reference PickAddress newBias)."""
    from cometbft_tpu.p2p.pex import AddrBook, NetAddress

    book = AddrBook()
    old_ids = set()
    for i in range(10):
        book.add_address(
            NetAddress(f"old{i}", f"20.{i}.0.1", 26656), source="a"
        )
        book.mark_good(f"old{i}")
        old_ids.add(f"old{i}")
    for i in range(30):
        book.add_address(
            NetAddress(f"new{i}", f"30.{i}.0.1", 26656), source="b"
        )
    n = 600
    hits_old = sum(
        1 for _ in range(n) if book.pick_address().node_id in old_ids
    )
    # binomial(600, 0.7): sigma ~ 11, so (0.55, 0.85) is ~8 sigma wide
    assert 0.55 < hits_old / n < 0.85, f"old fraction {hits_old / n}"
    # the bias knob is respected at the extremes
    assert all(
        book.pick_address(bias_old_pct=100).node_id in old_ids
        for _ in range(50)
    )
    assert all(
        book.pick_address(bias_old_pct=0).node_id not in old_ids
        for _ in range(50)
    )


def test_pex_seed_crawler_serves_and_hangs_up(tmp_path):
    """Seed-mode reactor: an inbound peer gets an addrs reply, then the
    seed hangs up (sweep past the deadline); a later dialer learns the
    first peer's address through the seed (reference pex_reactor.go
    seedMode/crawlPeers)."""
    from cometbft_tpu.p2p.pex import AddrBook, PexReactor

    def make(name, seed_mode=False):
        nk = NodeKey.generate()
        info = NodeInfo(node_id=nk.node_id(), network="seed-chain",
                        moniker=name)
        tr = Transport(nk, info)
        sw = Switch(tr)
        book = AddrBook(str(tmp_path / f"{name}.json"))
        pex = PexReactor(book, target_outbound=4, seed_mode=seed_mode,
                         seed_disconnect_s=0.3)
        pex.set_switch(sw)
        sw.add_reactor(pex)
        tr.listen()
        sw.start()
        return sw, tr, book, pex

    sw_s, t_s, book_s, pex_s = make("seed", seed_mode=True)
    sw_a, t_a, book_a, _ = make("a")
    sw_b, t_b, book_b, _ = make("b")
    try:
        host_s, port_s = t_s.node_info.listen_addr.split(":")
        sw_a.dial_peer(host_s, int(port_s))
        # the seed learns A's listen addr from the inbound handshake
        deadline = time.monotonic() + 10
        while not book_s.has(t_a.node_info.node_id) \
                and time.monotonic() < deadline:
            time.sleep(0.02)
        assert book_s.has(t_a.node_info.node_id)
        # past the disconnect deadline the sweep must drop the peer:
        # a seed never holds persistent full-peer connections
        time.sleep(0.4)
        pex_s.sweep_hangups()
        deadline = time.monotonic() + 5
        while sw_s.peers() and time.monotonic() < deadline:
            time.sleep(0.02)
        assert not sw_s.peers(), "seed kept a full peer"

        # B bootstraps through the seed and learns A
        sw_b.dial_peer(host_s, int(port_s))
        deadline = time.monotonic() + 10
        while not book_b.has(t_a.node_info.node_id) \
                and time.monotonic() < deadline:
            time.sleep(0.02)
        assert book_b.has(t_a.node_info.node_id), "B never learned A"

        # a crawl round dials from the seed's book and harvests; the
        # connections are transient (hangup deadlines get set)
        pex_s.crawl()
        time.sleep(0.4)
        pex_s.sweep_hangups()
        deadline = time.monotonic() + 5
        while sw_s.peers() and time.monotonic() < deadline:
            time.sleep(0.02)
        assert not sw_s.peers(), "crawl connections were not hung up"
    finally:
        sw_s.stop(); sw_a.stop(); sw_b.stop()


# --------------------------------------------- zero-copy framing (ISSUE 11)
def test_write_views_wire_equals_write_msg():
    """write_views(a, b, c) must be byte-identical on the wire to
    write_msg(a + b + c) — including empty views, frame-boundary
    straddles, and the empty-message single-frame case."""
    cases = [
        (b"abc", b"defg", b""),
        (b"",),
        (b"", b"", b""),
        (b"x" * 1020, b"y" * 8),            # straddles the first frame
        (b"h" * 4, b"z" * 3000, b"tail"),   # multi-frame
        (bytes(range(256)) * 17,),
    ]
    for bufs in cases:
        sca, scb, _, _ = _sc_pair()
        joined = b"".join(bufs)
        sca.write_views(*[memoryview(b) for b in bufs])
        assert scb.read_msg() == joined, f"views path broke for {bufs!r}"
        scb.write_msg(joined)
        assert sca.read_msg() == joined
        sca.close(); scb.close()


def test_mconnection_mixed_packet_sizes_interop():
    """Peers running different max_packet_payload_size must interop:
    the receive path is frame-size-agnostic (one read_msg = one packet)."""
    sca, scb, _, _ = _sc_pair()
    got_a, got_b = [], []
    done_a, done_b = threading.Event(), threading.Event()
    descs = [ChannelDescriptor(0x40)]
    big = bytes(range(256)) * 120  # 30720 B, multi-packet on both sides
    ma = MConnection(sca, descs,
                     lambda c, m: (got_a.append(m), done_a.set()),
                     max_packet_payload_size=8192)
    mb = MConnection(scb, descs,
                     lambda c, m: (got_b.append(m), done_b.set()),
                     max_packet_payload_size=1024)
    ma.start(); mb.start()
    try:
        assert ma.send(0x40, big)       # 8 KiB packets -> 1 KiB receiver
        assert done_b.wait(5)
        assert got_b == [big]
        assert mb.send(0x40, big[::-1])  # 1 KiB packets -> 8 KiB receiver
        assert done_a.wait(5)
        assert got_a == [big[::-1]]
    finally:
        ma.stop(); mb.stop()


def test_mconnection_per_channel_payload_override():
    sca, scb, _, _ = _sc_pair()
    got = []
    done = threading.Event()
    descs = [ChannelDescriptor(0x41, packet_payload_size=4096)]
    ma = MConnection(sca, descs, lambda c, m: None)
    mb = MConnection(scb, descs,
                     lambda c, m: (got.append(m), done.set()))
    assert ma._channels[0x41].payload_cap == 4096
    msg = b"p" * 10_000
    ma.start(); mb.start()
    try:
        assert ma.send(0x41, msg)
        assert done.wait(5)
        assert got == [msg]
    finally:
        ma.stop(); mb.stop()


def test_mconnection_large_message_reassembly_reuses_buffer():
    """A message far larger than one packet reassembles correctly into
    the persistent per-channel buffer, twice in a row (buffer reuse)."""
    sca, scb, _, _ = _sc_pair()
    got = []
    done = threading.Event()

    def on_recv(c, m):
        got.append(m)
        if len(got) == 2:
            done.set()

    descs = [ChannelDescriptor(0x42)]
    ma = MConnection(sca, descs, lambda c, m: None)
    mb = MConnection(scb, descs, on_recv)
    m1 = bytes(range(256)) * 1200   # ~300 KiB
    m2 = m1[::-1][:100_000]
    ma.start(); mb.start()
    try:
        assert ma.send(0x42, m1)
        assert ma.send(0x42, m2)
        assert done.wait(10)
        assert got == [m1, m2]
    finally:
        ma.stop(); mb.stop()


def test_mconnection_recv_capacity_enforced_single_packet():
    """The single-packet fast path must still enforce the channel's
    recv_message_capacity."""
    sca, scb, _, _ = _sc_pair()
    errs = []
    done = threading.Event()
    descs_small = [ChannelDescriptor(0x43, recv_message_capacity=64)]
    descs_big = [ChannelDescriptor(0x43)]
    ma = MConnection(sca, descs_big, lambda c, m: None,
                     max_packet_payload_size=512)
    mb = MConnection(scb, descs_small, lambda c, m: None,
                     on_error=lambda e: (errs.append(e), done.set()))
    ma.start(); mb.start()
    try:
        assert ma.send(0x43, b"o" * 400)  # one 400 B packet > 64 B cap
        assert done.wait(5), "oversized single-packet message not rejected"
        assert any("capacity" in str(e) for e in errs)
    finally:
        ma.stop(); mb.stop()


def test_packet_payload_size_validation():
    from cometbft_tpu.config import P2PConfig

    assert P2PConfig().max_packet_payload_size == 1024
    with pytest.raises(ValueError):
        P2PConfig(max_packet_payload_size=0).validate()
    with pytest.raises(ValueError):
        MConnection(None, [], lambda c, m: None, max_packet_payload_size=0)
