"""The plain reference of the configuration `catchup-1000v-1ktx-kvindex`:
what a transaction index holds after a chain's blocks.

What the deployment promises is that every transaction of every applied
block is found by its hash, with its height, its index in the block, its
bytes and its result's code and data; that `tx.height = h` gives block h's
transactions in block order; and that an attribute the application marked
for indexing finds the transactions that carried it. So the reference is
handed, height by height, a block's transactions as bytes (and, for an
application that emits events, each transaction's events), and keeps

  records[SHA-256(tx)] = (height, index, tx, code, data), the result from
      the kvstore reference (kvstore_replay.execute) applied in order; a
      transaction seen again overwrites its record, as upstream's
      state/txindex/kv does;
  by_height[h] = the hashes of height h's transactions, in block order;
  keys = the composite keys upstream's kv.go writes beside the record,
      each pointing at a hash: `tx.height/<h>/<h>/<i>`, and
      `<type>.<key>/<value>/<h>/<i>` for every attribute marked for
      indexing (an attribute is (key, value, index); an event is (type,
      [attributes])).

It knows no bus, no batch, no thread and no store, and imports nothing of
the program: only hashlib and its neighbour.
"""

from __future__ import annotations

import hashlib

from benchmark.reference import kvstore_replay


def tx_hash(tx: bytes) -> bytes:
    return hashlib.sha256(bytes(tx)).digest()


class Index:
    def __init__(self):
        self.store: dict[bytes, bytes] = {}  # the application's state
        self.records: dict[bytes, tuple] = {}
        self.by_height: dict[int, list[bytes]] = {}
        self.keys: dict[str, bytes] = {}
        self.height = 0

    def block(self, height: int, txs: list[bytes], events=None) -> None:
        """`events[i]` are transaction i's events, if the application
        emits any."""
        if self.height and height != self.height + 1:
            raise ValueError(f"height {height} after {self.height}")
        hashes = []
        for i, tx in enumerate(txs):
            tx = bytes(tx)
            h = tx_hash(tx)
            code, data = kvstore_replay.execute(self.store, tx)
            self.records[h] = (height, i, tx, code, data)
            self.keys[f"tx.height/{height}/{height}/{i}"] = h
            for etype, attrs in (events[i] if events else ()):
                for key, value, index in attrs:
                    if index:
                        self.keys[f"{etype}.{key}/{value}/{height}/{i}"] = h
            hashes.append(h)
        self.by_height[height] = hashes
        self.height = height

    def find(self, composite: str, value: str) -> list[bytes]:
        """The hashes of the transactions that carried the indexed
        attribute `composite` = `value`, by (height, index), each once."""
        prefix = f"{composite}/{value}/"
        hits = []
        for k, h in self.keys.items():
            if k.startswith(prefix):
                height, index = k[len(prefix):].split("/")
                hits.append((int(height), int(index), h))
        out = []
        for _, _, h in sorted(hits):
            if h not in out:
                out.append(h)
        return out
