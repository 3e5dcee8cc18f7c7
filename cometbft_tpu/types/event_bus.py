"""EventBus: typed pub/sub facade over the pubsub server.

Behavior parity: reference types/event_bus.go (:34) + types/events.go —
publishes EventNewBlock, EventNewBlockHeader, EventTx, EventVote,
EventValidatorSetUpdates with the standard composite keys
(`tm.event='NewBlock'`, `tx.height`, `tx.hash`) that subscribers and
indexers filter on.
"""

from __future__ import annotations

from dataclasses import dataclass

from ..crypto.keys import tmhash
from ..utils.pubsub import HeldSubscription, PubSubServer, Subscription

EVENT_NEW_BLOCK = "NewBlock"
EVENT_NEW_BLOCK_HEADER = "NewBlockHeader"
EVENT_TX = "Tx"
EVENT_VOTE = "Vote"
EVENT_VALIDATOR_SET_UPDATES = "ValidatorSetUpdates"

TYPE_KEY = "tm.event"


@dataclass
class BlockEvents:
    """Everything ApplyBlock fires for one block, as ONE item: what a
    block subscriber (the indexer service) is handed where the reference
    sends it EventDataNewBlockEvents and then NumTxs EventDataTx.
    `tx_hashes` is there when the publisher had to hash the transactions
    for its buffered subscribers anyway; a consumer hashes them itself
    otherwise, on its own thread."""

    height: int
    block: object
    result: object  # the FinalizeBlockResponse: tx_results, events
    tx_hashes: list | None = None


class EventBus:
    def __init__(self):
        self._server = PubSubServer()
        self._block_subs: dict[str, HeldSubscription] = {}

    def subscribe(self, client_id: str, query: str) -> Subscription:
        return self._server.subscribe(client_id, query)

    def unsubscribe(self, client_id: str, query: str) -> None:
        self._server.unsubscribe(client_id, query)

    def unsubscribe_all(self, client_id: str) -> None:
        self._server.unsubscribe_all(client_id)

    def subscribe_blocks(self, client_id: str, capacity: int,
                         on_lost=None) -> HeldSubscription:
        """A subscription at a block's grain that holds the publisher back
        (utils/pubsub.HeldSubscription): one BlockEvents a block, at most
        `capacity` of them unfinished, none ever dropped for slowness."""
        sub = HeldSubscription(capacity, on_lost)
        self._block_subs[client_id] = sub
        return sub

    def unsubscribe_blocks(self, client_id: str) -> None:
        sub = self._block_subs.pop(client_id, None)
        if sub is not None:
            sub.close()

    def join(self) -> None:
        """Waits until every block subscriber has finished what was
        published so far; raises a subscriber's error."""
        for sub in list(self._block_subs.values()):
            sub.join()

    # ------------------------------------------------------------------
    def publish_block(self, block, finalize_resp) -> float:
        """What ApplyBlock fires for one block (reference execution.go
        fireEvents): NewBlock, a Tx event a transaction and the validator
        updates to the buffered subscribers, if there are any, then the
        block as one BlockEvents to each block subscriber. Returns the
        seconds a block subscriber held the caller back."""
        height = block.header.height
        txs = block.data.txs
        hashes = None
        if self._server.has_subscribers():
            hashes = [tmhash(tx) for tx in txs]
            self.publish_new_block(block, finalize_resp)
            for i, tx in enumerate(txs):
                self.publish_tx(height, i, tx, finalize_resp.tx_results[i],
                                tx_hash=hashes[i])
            if finalize_resp.validator_updates:
                self.publish_validator_set_updates(
                    finalize_resp.validator_updates)
        waited = 0.0
        if self._block_subs:
            item = BlockEvents(height, block, finalize_resp, hashes)
            for sub in list(self._block_subs.values()):
                waited += sub.publish(item)
        return waited

    def publish_new_block(self, block, finalize_resp) -> None:
        h = str(block.header.height)
        events = {TYPE_KEY: [EVENT_NEW_BLOCK], "block.height": [h]}
        _merge_abci_events(events, getattr(finalize_resp, "events", []))
        self._server.publish(
            {"type": EVENT_NEW_BLOCK, "block": block, "result": finalize_resp},
            events,
        )

    def publish_tx(self, height: int, index: int, tx: bytes, result,
                   tx_hash: bytes | None = None) -> None:
        events = {
            TYPE_KEY: [EVENT_TX],
            "tx.height": [str(height)],
            "tx.hash": [(tx_hash or tmhash(tx)).hex().upper()],
        }
        _merge_abci_events(events, getattr(result, "events", []))
        self._server.publish(
            {"type": EVENT_TX, "height": height, "index": index, "tx": tx,
             "result": result},
            events,
        )

    def publish_vote(self, vote) -> None:
        self._server.publish(
            {"type": EVENT_VOTE, "vote": vote}, {TYPE_KEY: [EVENT_VOTE]}
        )

    def publish_validator_set_updates(self, updates) -> None:
        self._server.publish(
            {"type": EVENT_VALIDATOR_SET_UPDATES, "updates": updates},
            {TYPE_KEY: [EVENT_VALIDATOR_SET_UPDATES]},
        )


def abci_attributes(abci_events):
    """(type.key, value, marked for indexing) of every attribute of some
    ABCI events, in order. An event is (type, attributes) or an object
    with those fields; an attribute is (key, value[, index]) or an object
    with those fields. One that says nothing of `index` counts as marked,
    as this bus has always treated them (reference types/events.go:
    the composite key is type.key)."""
    for ev in abci_events or ():
        if isinstance(ev, tuple):
            etype, attrs = ev[0], ev[1]
        else:
            etype, attrs = ev.type, ev.attributes
        for a in attrs or ():
            if isinstance(a, tuple):
                k, v = a[0], a[1]
                marked = a[2] if len(a) > 2 else True
            else:
                k, v, marked = a.key, a.value, getattr(a, "index", True)
            if isinstance(k, bytes):
                k = k.decode("utf-8", "replace")
            if isinstance(v, bytes):
                v = v.decode("utf-8", "replace")
            yield f"{etype}.{k}", str(v), bool(marked)


def _merge_abci_events(events: dict, abci_events) -> None:
    for composite, value, _ in abci_attributes(abci_events):
        events.setdefault(composite, []).append(value)
