"""Event pub/sub with the reference's query language.

Behavior parity: reference internal/pubsub (Server, :~600) +
internal/pubsub/query (the `tm.event='NewBlock' AND tx.height > 5`
language). Supported operators: =, !=, <, <=, >, >=, CONTAINS, EXISTS,
combined with AND (the reference's language has no OR). Values compare
numerically when both sides parse as numbers, else as strings.
"""

from __future__ import annotations

import re
import threading
import time
from dataclasses import dataclass, field


# ---------------------------------------------------------------- query ---
_TOKEN = re.compile(
    r"\s*(?:(?P<key>[\w.]+)\s*(?P<op><=|>=|!=|=|<|>|\bCONTAINS\b|\bEXISTS\b)"
    r"\s*(?P<val>'[^']*'|[\w.\-]+)?)\s*"
)


@dataclass
class _Condition:
    key: str
    op: str
    value: str | None

    def matches(self, events: dict[str, list[str]]) -> bool:
        vals = events.get(self.key)
        if self.op == "EXISTS":
            return vals is not None
        if vals is None:
            return False
        for v in vals:
            if self._match_one(v):
                return True
        return False

    def _match_one(self, v: str) -> bool:
        want = self.value
        if self.op == "CONTAINS":
            return want in v
        try:
            a, b = float(v), float(want)
            if self.op == "=":
                return a == b
            if self.op == "!=":
                return a != b
            if self.op == "<":
                return a < b
            if self.op == "<=":
                return a <= b
            if self.op == ">":
                return a > b
            if self.op == ">=":
                return a >= b
        except (TypeError, ValueError):
            pass
        if self.op == "=":
            return v == want
        if self.op == "!=":
            return v != want
        return False


class Query:
    """Parsed AND-combination of conditions (reference pubsub/query)."""

    def __init__(self, s: str):
        self.source = s
        self.conditions: list[_Condition] = []
        for clause in re.split(r"\bAND\b", s):
            clause = clause.strip()
            if not clause:
                continue
            m = _TOKEN.fullmatch(clause)
            if not m:
                raise ValueError(f"bad query clause: {clause!r}")
            val = m.group("val")
            if val is not None and val.startswith("'"):
                val = val[1:-1]
            op = m.group("op")
            if op == "EXISTS" and val is not None:
                raise ValueError("EXISTS takes no value")
            if op != "EXISTS" and val is None:
                raise ValueError(f"operator {op} needs a value")
            self.conditions.append(_Condition(m.group("key"), op, val))
        if not self.conditions:
            raise ValueError("empty query")

    def matches(self, events: dict[str, list[str]]) -> bool:
        return all(c.matches(events) for c in self.conditions)


# ---------------------------------------------------------------- server --
@dataclass
class Message:
    data: object
    events: dict[str, list[str]] = field(default_factory=dict)


class SubscriptionCancelled(Exception):
    """The subscription was dropped (slow-consumer overflow or explicit
    unsubscribe); the consumer should resubscribe if it still cares."""


class Subscription:
    def __init__(self, query: Query, capacity: int = 256):
        self.query = query
        self.capacity = capacity
        self._buf: list[Message] = []
        self._cv = threading.Condition()
        self.cancelled = False

    def publish(self, msg: Message) -> None:
        """Buffer a matching message; a subscriber that stops draining is
        cancelled at capacity (reference pubsub drops slow subscribers
        rather than buffering unboundedly — internal/pubsub/pubsub.go)."""
        with self._cv:
            if self.cancelled:
                return
            if len(self._buf) >= self.capacity:
                self.cancelled = True
                self._buf.clear()
                self._cv.notify_all()
                return
            self._buf.append(msg)
            self._cv.notify_all()

    def next(self, timeout: float | None = None) -> Message | None:
        """Pop the next message, or None on timeout. Raises
        SubscriptionCancelled once the subscription was dropped (capacity
        overflow or unsubscribe) so consumers can resubscribe instead of
        polling a dead buffer forever."""
        with self._cv:
            if self.cancelled:
                raise SubscriptionCancelled(self.query.source)
            if not self._buf:
                self._cv.wait(timeout)
            if self.cancelled:
                raise SubscriptionCancelled(self.query.source)
            if self._buf:
                return self._buf.pop(0)
            return None

    def cancel(self) -> None:
        with self._cv:
            self.cancelled = True
            self._buf.clear()
            self._cv.notify_all()

    def drain(self) -> list[Message]:
        with self._cv:
            out, self._buf = self._buf, []
            return out


class HeldSubscription:
    """A hand-off that holds its publisher back instead of losing items:
    it holds at most `capacity` items its consumer has not FINISHED
    (queued or in its hands), and publish() waits for a free place. It is
    never cancelled for being slow (reference pubsub SubscribeUnbuffered,
    which the indexer service takes: a slow indexer slows the publisher
    and is never dropped). An item is whatever the publisher's grain is;
    the event bus hands over a block's events as one.

    The consumer's loop is next() ... done(), or fail(exc) when it gives
    up: a failed subscription raises that error from every later
    publish() and join(). close() ends it in order: what was published is
    still handed out, a later publish() is not taken. `on_lost(item)` is
    told of every item that will never be handed out (queued behind a
    failure, or published after the close), so a loss is never silent."""

    def __init__(self, capacity: int, on_lost=None):
        if capacity < 1:
            raise ValueError("a held subscription holds at least one item")
        self.capacity = capacity
        self._on_lost = on_lost or (lambda item: None)
        self._q: list = []
        self._cv = threading.Condition()
        self.published = 0
        self.finished = 0
        self.max_held = 0  # the most it ever held
        self.closed = False
        self.error: BaseException | None = None

    @property
    def held(self) -> int:
        return self.published - self.finished

    def publish(self, item) -> float:
        """Seconds the publisher was held back."""
        with self._cv:
            t0 = None
            while (self.held >= self.capacity
                   and not (self.closed or self.error)):
                t0 = t0 or time.perf_counter()
                self._cv.wait()
            waited = time.perf_counter() - t0 if t0 else 0.0
            if self.error is not None:
                self._on_lost(item)
                raise self.error
            if self.closed:
                self._on_lost(item)
                return waited
            self._q.append(item)
            self.published += 1
            self.max_held = max(self.max_held, self.held)
            self._cv.notify_all()
        return waited

    def next(self):
        """The next item, or None once the subscription is closed and
        empty."""
        with self._cv:
            while not self._q:
                if self.closed or self.error:
                    return None
                self._cv.wait()
            return self._q.pop(0)

    def done(self) -> None:
        with self._cv:
            self.finished += 1
            self._cv.notify_all()

    def fail(self, exc: BaseException) -> None:
        with self._cv:
            self.error = exc
            for item in self._q:
                self._on_lost(item)
            self._q.clear()
            self._cv.notify_all()

    def close(self) -> None:
        with self._cv:
            self.closed = True
            self._cv.notify_all()

    def join(self) -> None:
        """Waits until everything published so far is finished; raises the
        consumer's error if it failed."""
        with self._cv:
            want = self.published
            self._cv.wait_for(
                lambda: self.finished >= want or self.error is not None)
            if self.error is not None:
                raise self.error


class PubSubServer:
    def __init__(self):
        self._subs: dict[tuple[str, str], Subscription] = {}
        self._lock = threading.Lock()

    def subscribe(self, client_id: str, query_str: str) -> Subscription:
        q = Query(query_str)
        sub = Subscription(q)
        with self._lock:
            self._subs[(client_id, query_str)] = sub
        return sub

    def unsubscribe(self, client_id: str, query_str: str) -> None:
        with self._lock:
            sub = self._subs.pop((client_id, query_str), None)
        if sub:
            sub.cancel()

    def unsubscribe_all(self, client_id: str) -> None:
        with self._lock:
            gone = [k for k in self._subs if k[0] == client_id]
            for k in gone:
                self._subs.pop(k).cancel()

    def has_subscribers(self) -> bool:
        """Whether a publish() could reach anyone: a publisher may skip
        building its messages when not."""
        return bool(self._subs)

    def publish(self, data, events: dict[str, list[str]] | None = None) -> None:
        msg = Message(data, events or {})
        with self._lock:
            subs = list(self._subs.items())
        dead = []
        for key, sub in subs:
            if sub.cancelled:
                dead.append(key)
                continue
            if sub.query.matches(msg.events):
                sub.publish(msg)
        if dead:
            with self._lock:
                for key in dead:
                    if self._subs.get(key) is not None and self._subs[key].cancelled:
                        self._subs.pop(key, None)
