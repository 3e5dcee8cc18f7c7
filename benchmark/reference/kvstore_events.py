"""The plain reference of the configuration `catchup-1000v-1ktx-kvevents`:
the events upstream's kvstore application answers a transaction with.

CometBFT abci/example/kvstore/kvstore.go FinalizeBlock, as known from
v0.38.x: a transaction is `key=value` (one without `=` is its own key and
its own value; one with several is split at the first here, as
reference/kvstore_replay.py splits it: the load holds none), and EVERY
transaction's result carries the same two events of type `app`, of four
attributes each:

    creator      "Cosmoshi Netowoko"     index: true
    key          <the transaction's key>  index: true
    index_key    "index is working"      index: true
    noindex_key  "index is working"      index: false

and again with creator "Cosmoshi" and `key` = <the transaction's VALUE>.
The events are a function of the transaction's bytes alone; they are no
part of the result's deterministic fields (types/results.go: code, data,
gas wanted, gas used), so no header depends on them. The application emits
no event of the block's own.

`events(tx)` has the shape reference/tx_index.py Index.block takes for a
transaction: [(type, [(key, value, index), ...]), ...]. bytes and str only;
this file imports nothing, of the program or otherwise.
"""

from __future__ import annotations

TYPE = "app"
CREATORS = ("Cosmoshi Netowoko", "Cosmoshi")
WORKING = "index is working"


def events(tx: bytes) -> list:
    tx = bytes(tx)
    key, eq, value = tx.partition(b"=")
    if not eq:
        key = value = tx
    return [
        (TYPE, [("creator", creator, True),
                ("key", text.decode("utf-8", "replace"), True),
                ("index_key", WORKING, True),
                ("noindex_key", WORKING, False)])
        for creator, text in zip(CREATORS, (key, value))]


def indexed(tx: bytes) -> list:
    """(type.key, value) of the attributes marked for indexing, in the
    order emitted: what an index keeps of the transaction beside its keys."""
    return [(f"{etype}.{key}", value) for etype, attrs in events(tx)
            for key, value, index in attrs if index]
