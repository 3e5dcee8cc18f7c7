"""The plain reference of the configuration `catchup-1000v-1ktx`: what a
block's transactions commit to, and what the kvstore application holds after
them.

What the deployment promises is that every block's `data_hash` is recomputed
from its transactions before the block is applied, that every transaction
reaches the application once, in the block's order, and that the results'
root is the next header's `last_results_hash`. So the reference is handed,
height by height, a block's transactions and the two hashes its header
carries, as bytes, and from those alone it

  (a) recomputes `data_hash`: the RFC 6962 Merkle root (spec/core: leaf =
      SHA-256(0x00 || item), inner = SHA-256(0x01 || left || right), the
      left subtree the largest power of two under the count, the empty
      tree SHA-256 of nothing) over SHA-256(tx);
  (b) applies every transaction to a plain dict as the kvstore application
      does (abci/example/kvstore: `key=value`; a transaction without `=` or
      with an empty key is refused with code 1; a `val:<hex pubkey>=<power>`
      transaction that does not parse is refused with code 1), in order;
  (c) recomputes the `last_results_hash` of the NEXT height: the same root
      over each result's deterministic fields in protobuf (types/results.go:
      code = 1, data = 2, gas_wanted = 5, gas_used = 6; zero fields left
      out). This application answers an accepted transaction with code 0 and
      the value as data, and uses no gas;
  (d) counts transactions and their bytes.

It knows no window, no application hash and no store on disk, and imports
nothing of the program: only hashlib.
"""

from __future__ import annotations

import hashlib

VAL_PREFIX = b"val:"


def _sha(b: bytes) -> bytes:
    return hashlib.sha256(b).digest()


def merkle_root(items: list[bytes]) -> bytes:
    """RFC 6962 section 2.1 over `items`."""
    n = len(items)
    if n == 0:
        return _sha(b"")
    if n == 1:
        return _sha(b"\x00" + items[0])
    k = 1 << ((n - 1).bit_length() - 1)  # the largest power of two under n
    return _sha(b"\x01" + merkle_root(items[:k]) + merkle_root(items[k:]))


def data_hash(txs: list[bytes]) -> bytes:
    return merkle_root([_sha(bytes(tx)) for tx in txs])


def _uvarint(v: int) -> bytes:
    out = bytearray()
    while v >= 0x80:
        out.append((v & 0x7F) | 0x80)
        v >>= 7
    out.append(v)
    return bytes(out)


def execute(store: dict, tx: bytes) -> tuple[int, bytes]:
    """One transaction applied to `store`: (code, data) of its result."""
    key, eq, value = bytes(tx).partition(b"=")
    if not eq or not key:
        return 1, b""
    if key.startswith(VAL_PREFIX):
        try:
            bytes.fromhex(key[len(VAL_PREFIX):].decode())
            int(value)
        except ValueError:
            return 1, b""
    store[key] = value
    return 0, value


def result_bytes(code: int, data: bytes) -> bytes:
    """The deterministic fields of one result, as protobuf."""
    out = b""
    if code:
        out += b"\x08" + _uvarint(code)
    if data:
        out += b"\x12" + _uvarint(len(data)) + data
    return out


class Replay:
    """The chain replayed from its transactions alone. After block(h, ...):
    `store` is the application's state of height h; `differs` lists every
    (height, which hash) whose recomputed value is not the header's;
    `snapshots[h]` is a copy of the store for every h in `keep`;
    `data_root[h]` and `results_root[h]` (the root over block h's results,
    which header h + 1 carries) are kept for every height."""

    def __init__(self, keep=()):
        self.store: dict[bytes, bytes] = {}
        self.keep = set(keep)
        self.snapshots: dict[int, dict] = {}
        self.data_root: dict[int, bytes] = {}
        self.results_root: dict[int, bytes] = {}
        self.differs: list[tuple[int, str]] = []
        self.height = 0
        self.txs = 0
        self.tx_bytes = 0

    def block(self, height: int, txs: list[bytes], header_data_hash: bytes,
              header_last_results_hash: bytes) -> None:
        if self.height and height != self.height + 1:
            raise ValueError(f"height {height} after {self.height}")
        self.data_root[height] = data_hash(txs)
        if self.data_root[height] != header_data_hash:
            self.differs.append((height, "data_hash"))
        prev = self.results_root.get(height - 1)
        if prev is not None and prev != header_last_results_hash:
            self.differs.append((height, "last_results_hash"))
        results = []
        for tx in txs:
            results.append(result_bytes(*execute(self.store, tx)))
            self.tx_bytes += len(tx)
        self.txs += len(txs)
        self.results_root[height] = merkle_root(results)
        self.height = height
        if height in self.keep:
            self.snapshots[height] = dict(self.store)
