"""Tx + block indexers over the KV store, and the service that feeds them.

Behavior parity: reference state/txindex/kv (tx results by hash, one key
per indexed attribute) + state/indexer/block/kv (block events by height),
fed by an IndexerService subscribed to the event bus
(state/txindex/indexer_service.go): it takes a block's events as ONE batch
and writes it in one `write_batch` a store. Its subscription is never
cancelled for being slow: when MAX_BLOCKS_HELD blocks are published and
not yet written, ApplyBlock waits (the reference subscribes unbuffered).

Keys of the tx index, as kv.go lays them out (text, `/`-separated):
    TX:<hash>                         -> the record (height, index, tx,
                                         the result's code and data, the
                                         indexed attributes)
    tx.height/<h>/<h>/<i>             -> hash
    <type.key>/<value>/<h>/<i>        -> hash, one per event attribute the
                                         application marked for indexing
The record sits behind `TX:` where the reference uses the bare hash, so
that one table keeps records and keys apart by prefix. Heights and indexes
are decimal text, so a prefix scan is ordered as text and `search` orders
its hits by number.
"""

from __future__ import annotations

import hashlib
import os
import threading
import time
from dataclasses import dataclass

from ..encoding import proto as pb
from ..types.event_bus import (
    EVENT_TX,
    TYPE_KEY,
    BlockEvents,
    EventBus,
    abci_attributes,
)
from ..utils import trace
from ..utils.log import logger
from ..utils.metrics import indexer_metrics
from ..utils.pubsub import Query
from .kv import KVStore, MemKV, open_kv

# Blocks published to the service and not yet written, at most: the one
# in the writer's hands and one at the hand-off. The reference's
# unbuffered channel holds one EVENT, which leaves its index the same two
# blocks behind the state: the batch being written, and the block whose
# first event the publisher is blocked on. A block is this program's
# grain: a rendezvous a transaction would cost two Python threads an
# interpreter switch each, 400 a block.
MAX_BLOCKS_HELD = 2

TX_HEIGHT = "tx.height"
TX_HASH = "tx.hash"
BLOCK_HEIGHT = "block.height"


def _key_tx(tx_hash: bytes) -> bytes:
    return b"TX:" + tx_hash


def _key_attr(composite: str, value: str, height: int, index: int) -> bytes:
    return f"{composite}/{value}/{height}/{index}".encode()


def _key_block_events(height: int) -> bytes:
    return b"BE:" + height.to_bytes(8, "big")


def indexed_attributes(abci_events) -> list[tuple[str, str]]:
    """(type.key, value) of every attribute the application marked for
    indexing, in the order it emitted them."""
    return [(k, v) for k, v, marked in abci_attributes(abci_events)
            if marked]


@dataclass
class BatchStats:
    """What one add_batch did."""

    txs: int = 0
    keys: int = 0
    bytes: int = 0  # keys and values handed to the store
    attr_keys: int = 0  # of `keys`, those of indexed attributes
    attr_bytes: int = 0  # of `bytes`, theirs and the records' field 6
    encode_s: float = 0.0
    write_s: float = 0.0


class TxIndexer:
    """reference state/txindex/kv/kv.go."""

    def __init__(self, db: KVStore | None = None):
        self._db = db or MemKV()

    @property
    def page_bytes(self) -> int:
        """The size of the index file's pages; 0 in memory."""
        return self._db.page_bytes

    def add_batch(self, height: int, txs, results,
                  hashes=None) -> BatchStats:
        """One block's transactions with their results, as ONE write_batch
        (kv.go AddBatch). A transaction seen before overwrites its record,
        as the reference's does."""
        t0 = time.perf_counter()
        sets = []
        attr_keys = attr_bytes = 0
        for i, tx in enumerate(txs):
            tx = bytes(tx)
            h = hashes[i] if hashes else hashlib.sha256(tx).digest()
            res = results[i] if i < len(results) else None
            attrs = indexed_attributes(getattr(res, "events", None))
            stored = pb.f_bytes(6, _encode_attrs(attrs))
            sets.append((_key_tx(h), (
                pb.f_varint(1, height)
                + pb.f_varint(2, i)
                + pb.f_bytes(3, tx)
                + pb.f_varint(4, getattr(res, "code", 0))
                + pb.f_bytes(5, getattr(res, "data", b""))
                + stored
            )))
            sets.append((_key_attr(TX_HEIGHT, str(height), height, i), h))
            if attrs:
                # two events that carry the same attribute share its key
                attr_bytes += len(stored)
                for composite, value in dict.fromkeys(attrs):
                    key = _key_attr(composite, value, height, i)
                    sets.append((key, h))
                    attr_keys += 1
                    attr_bytes += len(key) + len(h)
        t1 = time.perf_counter()
        self._db.write_batch(sets)
        return BatchStats(
            txs=len(txs), keys=len(sets),
            bytes=sum(len(k) + len(v) for k, v in sets),
            attr_keys=attr_keys, attr_bytes=attr_bytes,
            encode_s=t1 - t0, write_s=time.perf_counter() - t1)

    def get(self, tx_hash: bytes):
        raw = self._db.get(_key_tx(tx_hash))
        if raw is None:
            return None
        d = pb.fields_to_dict(raw)
        return {
            "height": pb.to_i64(d.get(1, 0)),
            "index": pb.to_i64(d.get(2, 0)),
            "tx": pb.as_bytes(d.get(3, b"")),
            "code": int(d.get(4, 0)),
            "data": pb.as_bytes(d.get(5, b"")),
            "events": _decode_events(pb.as_bytes(d.get(6, b""))),
        }

    def count(self) -> int:
        """Records held (one a distinct transaction)."""
        return sum(1 for _ in self._db.iterate_prefix(b"TX:"))

    def _hashes_under(self, prefix: str) -> list[bytes]:
        """The hashes the keys under `prefix` point to, ordered by the
        (height, index) their keys end in."""
        hits = []
        for key, tx_hash in self._db.iterate_prefix(prefix.encode()):
            _, h, i = key.rsplit(b"/", 2)
            hits.append((int(h), int(i), tx_hash))
        hits.sort()
        return [tx_hash for _, _, tx_hash in hits]

    def search(self, query_str: str, limit: int = 100) -> list[dict]:
        """The records a query matches, each once, in the order of the
        keys that found them: by (height, index). `tx.hash = X` is one
        read; `tx.height = h` and equality on an indexed attribute walk
        that key's prefix alone; any other query walks the height keys.
        Every candidate is then held to the whole query."""
        q = Query(query_str)
        eq = {c.key: c.value for c in q.conditions
              if c.op == "=" and c.key != TYPE_KEY}
        if TX_HASH in eq:
            try:
                hashes = [bytes.fromhex(eq[TX_HASH])]
            except ValueError:
                return []
        elif eq.get(TX_HEIGHT, "").isdigit():
            h = int(eq[TX_HEIGHT])
            hashes = self._hashes_under(f"{TX_HEIGHT}/{h}/{h}/")
        elif eq:
            key, value = next(iter(eq.items()))
            hashes = self._hashes_under(f"{key}/{value}/")
        else:
            hashes = self._hashes_under(f"{TX_HEIGHT}/")
        out, seen = [], set()
        for tx_hash in hashes:
            if tx_hash in seen:
                continue
            seen.add(tx_hash)
            rec = self.get(tx_hash)
            if rec is None:
                continue
            events = dict(rec["events"])
            events[TX_HEIGHT] = [str(rec["height"])]
            events[TX_HASH] = [tx_hash.hex().upper()]
            events[TYPE_KEY] = [EVENT_TX]
            if q.matches(events):
                out.append(rec)
                if len(out) >= limit:
                    break
        return out


class BlockIndexer:
    """reference state/indexer/block/kv."""

    def __init__(self, db: KVStore | None = None):
        self._db = db or MemKV()

    def index(self, height: int, abci_events=None) -> None:
        """One record a height: the block's indexed attributes."""
        self._db.set(_key_block_events(height),
                     _encode_attrs(indexed_attributes(abci_events)))

    def search(self, query_str: str, limit: int = 100) -> list[int]:
        q = Query(query_str)
        out = []
        for key, raw in self._db.iterate_prefix(b"BE:"):
            h = int.from_bytes(key[3:11], "big")
            events = _decode_events(raw)
            events[BLOCK_HEIGHT] = [str(h)]
            if q.matches(events):
                out.append(h)
                if len(out) >= limit:
                    break
        return out


class IndexerError(RuntimeError):
    """The indexer service failed, or its index does not hold what it
    was asked to wait for."""


class IndexerService:
    """Subscribes to the event bus at a block's grain and feeds both
    indexers (reference state/txindex/indexer_service.go): one BlockEvents
    is one `add_batch` and one block record. A failing write ends the
    service: the error is logged, raised to the publisher on its next
    block and raised by wait()."""

    CLIENT = "indexer"

    def __init__(self, event_bus, tx_indexer: TxIndexer,
                 block_indexer: BlockIndexer):
        self.tx_indexer = tx_indexer
        self.block_indexer = block_indexer
        self.height = 0  # the last height both indexes hold
        self._bus = event_bus
        self._sub = event_bus.subscribe_blocks(
            self.CLIENT, MAX_BLOCKS_HELD, on_lost=self._lost)
        self._thread = threading.Thread(
            target=self._run, name="indexer", daemon=True)
        self._thread.start()

    @property
    def max_held(self) -> int:
        """The most blocks the service ever held unwritten."""
        return self._sub.max_held

    @staticmethod
    def _lost(ev: BlockEvents) -> None:
        indexer_metrics().events_dropped_total.inc(
            1 + len(ev.block.data.txs))

    def _run(self) -> None:
        m = indexer_metrics()
        while True:
            ev = self._sub.next()
            if ev is None:
                return
            m.blocks_held.set(self._sub.held)
            try:
                self._index(ev)
            except Exception as e:  # noqa: BLE001 - surfaced below
                logger("indexer").error(
                    "indexer service stopped: a write failed",
                    height=ev.height, err=repr(e))
                self._lost(ev)
                err = IndexerError(
                    f"indexing height {ev.height} failed: {e!r}")
                err.__cause__ = e
                self._sub.fail(err)
                return
            self.height = ev.height
            self._sub.done()
            m.blocks_held.set(self._sub.held)

    def _index(self, ev: BlockEvents) -> None:
        txs = ev.block.data.txs
        with trace.span("index.block", height=ev.height,
                        txs=len(txs)) as sp:
            st = self.tx_indexer.add_batch(
                ev.height, txs, ev.result.tx_results, ev.tx_hashes)
            t0 = time.perf_counter()
            self.block_indexer.index(ev.height, ev.result.events)
            st.write_s += time.perf_counter() - t0
            if trace.enabled:
                sp.add(tx_bytes=sum(map(len, txs)), keys=st.keys + 1,
                       bytes=st.bytes, attr_keys=st.attr_keys,
                       attr_bytes=st.attr_bytes,
                       page_bytes=self.tx_indexer.page_bytes,
                       encode_ms=round(st.encode_s * 1e3, 3),
                       write_ms=round(st.write_s * 1e3, 3),
                       # as the write ends: this block and what was
                       # published while it was written
                       behind=self._sub.held)
        m = indexer_metrics()
        m.txs_indexed_total.inc(len(txs))
        m.blocks_indexed_total.inc()
        m.attr_keys_total.inc(st.attr_keys)

    def wait(self, height: int | None = None) -> None:
        """Returns once everything published so far is written, and
        `height` with it; raises IndexerError otherwise."""
        self._sub.join()
        if height is not None and self.height < height:
            raise IndexerError(
                f"the index holds height {self.height}, not {height}")

    def stop(self) -> None:
        """Takes no more blocks, writes what was published, ends the
        thread."""
        self._bus.unsubscribe_blocks(self.CLIENT)  # closes the subscription
        self._thread.join()


@dataclass
class Indexing:
    """What `[tx_index]` builds: the event bus, and for "kv" the two
    indexers with the service that feeds them."""

    event_bus: EventBus
    tx_indexer: TxIndexer | None = None
    block_indexer: BlockIndexer | None = None
    service: IndexerService | None = None
    _dbs: tuple = ()

    def stop(self) -> None:
        """Drains the service and closes the index files."""
        if self.service is not None:
            self.service.stop()
        for db in self._dbs:
            db.close()


TX_INDEX_FILE = "tx_index.db"
BLOCK_INDEX_FILE = "block_index.db"

# A record carries the transaction and its result: a 1 KB transaction makes
# a 2.1 KB record, of which a 4 KB page holds one and a 16 KB page seven.
# Larger pages rewrite more of the key's b-tree for every random key.
# 16 KB was chosen (PR 43) for THAT record, of an application that emits no
# events. Under the reference kvstore's two events a transaction the record
# is 3.3 KB (field 6 holds the indexed attributes, one of them the 1 KB
# value), a 16 KB page holds four, and each transaction adds a key of about
# 1,040 bytes (`app.key/<value>/<h>/<i>`), which sqlite keeps in the row
# and in the key's own b-tree; the size was not measured again for it.
TX_INDEX_PAGE_BYTES = 16384


def open_indexers(data_dir: str | None) -> tuple[TxIndexer, BlockIndexer,
                                                  tuple]:
    """The two indexers on their files under `data_dir` (None: in
    memory), and the stores to close. A new tx index is made with pages
    of TX_INDEX_PAGE_BYTES; one that exists keeps the size it has."""
    def path(name):
        return data_dir and os.path.join(data_dir, name)

    dbs = (open_kv(path(TX_INDEX_FILE), TX_INDEX_PAGE_BYTES),
           open_kv(path(BLOCK_INDEX_FILE)))
    if data_dir:
        logger("indexer").info("tx index opened", path=path(TX_INDEX_FILE),
                               page_bytes=dbs[0].page_bytes)
    return TxIndexer(dbs[0]), BlockIndexer(dbs[1]), dbs


def open_indexing(indexer: str, data_dir: str | None) -> Indexing:
    """The node's indexing as `[tx_index] indexer` says: "kv" is a
    TxIndexer on <data_dir>/tx_index.db, a BlockIndexer on
    <data_dir>/block_index.db (both in memory where `data_dir` is None)
    and the service on a new bus; "null" is the bus alone."""
    bus = EventBus()
    if indexer == "null":
        return Indexing(bus)
    if indexer != "kv":
        raise ValueError(f"unknown tx_index.indexer {indexer!r}")
    txi, bli, dbs = open_indexers(data_dir)
    return Indexing(bus, txi, bli, IndexerService(bus, txi, bli), dbs)


def _encode_attrs(attrs: list[tuple[str, str]]) -> bytes:
    return b"".join(
        pb.f_embedded(1, pb.f_string(1, k) + pb.f_string(2, v))
        for k, v in attrs)


def _decode_events(buf: bytes) -> dict[str, list[str]]:
    out: dict[str, list[str]] = {}
    for f, _, v in pb.parse_fields(buf):
        if f == 1:
            d = pb.fields_to_dict(pb.as_bytes(v))
            k = pb.as_bytes(d.get(1, b"")).decode("utf-8", "replace")
            val = pb.as_bytes(d.get(2, b"")).decode("utf-8", "replace")
            out.setdefault(k, []).append(val)
    return out
