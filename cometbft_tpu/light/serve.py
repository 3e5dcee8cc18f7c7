"""Light-client serving surface (ROADMAP item #2).

Inverts `light/` from a client library into a server: one node-side
service that streams committed headers + proofs to thousands of
concurrent light clients while paying each height's commit verification
exactly once through the existing crypto dispatch.

Pieces:

- ``VerifiedCommitCache`` — height-keyed, single-flight, LRU-bounded.
  The first caller for a height runs ``verify_commit_light`` (through
  native/ladder/mesh dispatch); every concurrent and later caller waits on
  the in-flight entry or hits the cached verdict. Hit/miss counters
  prove the fan-out amortization.
- ``LightServe`` — maintains the MMR header accumulator incrementally
  at commit time (hooked into ``BlockExecutor.event_handlers``), renders
  each height's stream payload ONCE and fans it out to every
  subscriber, generates peak-walking ancestry proofs, and plans+serves
  skipping-verification bisection pivots server-side.
- ``StreamSubscriber`` — backpressure-aware bounded queue, drop-oldest
  on overflow with drop accounting (same pattern as the p2p switch
  broadcast queue).

The bisection planner is deliberately signature-free: candidate hops
are screened with a host-side voting-power overlap check (does the
trusted next-validator set hold > 1/3 of the power signing the
candidate commit?), and signatures are verified once per CHOSEN pivot
through the cache — so planning cost does not scale with probe count.
"""

from __future__ import annotations

import threading
from collections import OrderedDict, deque

from ..crypto.sched import verify_context
from ..types.validation import verify_commit_light
from ..utils.metrics import light_metrics
from .mmr import MMR, MMRProof
from .types import LightBlock, SignedHeader


class _InFlight:
    __slots__ = ("event", "result", "exc")

    def __init__(self):
        self.event = threading.Event()
        self.result = None
        self.exc = None


class VerifiedCommitCache:
    """Single-flight LRU cache of per-height commit verification.

    ``get_or_verify(height, fn)`` returns fn()'s result, guaranteeing
    fn runs at most once per height while the entry is resident —
    concurrent callers for the same height block on the first caller's
    in-flight entry instead of re-verifying. Failed verifications are
    NOT cached (a transient backend fault must not poison the height).
    """

    def __init__(self, capacity: int = 4096):
        self.capacity = max(1, int(capacity))
        self._done: OrderedDict[int, object] = OrderedDict()
        self._inflight: dict[int, _InFlight] = {}
        self._lock = threading.Lock()
        # verify invocations per height — the workload's ==1 assertion
        self.verify_calls: dict[int, int] = {}

    def get_or_verify(self, height: int, fn):
        m = light_metrics()
        while True:
            with self._lock:
                if height in self._done:
                    self._done.move_to_end(height)
                    m.verify_cache_hits_total.inc()
                    return self._done[height]
                entry = self._inflight.get(height)
                if entry is None:
                    entry = self._inflight[height] = _InFlight()
                    owner = True
                    m.verify_cache_misses_total.inc()
                else:
                    owner = False
                    m.verify_cache_hits_total.inc()
            if not owner:
                entry.event.wait()
                if entry.exc is not None:
                    raise entry.exc
                return entry.result
            try:
                with self._lock:
                    self.verify_calls[height] = (
                        self.verify_calls.get(height, 0) + 1
                    )
                result = fn()
            except Exception as e:  # noqa: BLE001 — propagate to waiters
                entry.exc = e
                with self._lock:
                    self._inflight.pop(height, None)
                entry.event.set()
                raise
            with self._lock:
                self._done[height] = result
                while len(self._done) > self.capacity:
                    self._done.popitem(last=False)
                self._inflight.pop(height, None)
            entry.result = result
            entry.event.set()
            return result

    def peek(self, height: int):
        """Cached verdict for a height, or None — never triggers a
        verify (the replication feed reports cert status with it)."""
        with self._lock:
            return self._done.get(height)

    def __len__(self) -> int:
        with self._lock:
            return len(self._done)


class StreamSubscriber:
    """Bounded per-subscriber payload queue: drop-oldest on overflow
    (p2p/switch.py broadcast-queue pattern), dropped count accounted."""

    __slots__ = ("_q", "_cv", "limit", "dropped", "closed")

    def __init__(self, limit: int = 4096):
        self.limit = max(1, int(limit))
        self._q: deque = deque()
        self._cv = threading.Condition()
        self.dropped = 0
        self.closed = False

    def push(self, payload) -> None:
        with self._cv:
            if self.closed:
                return
            if len(self._q) >= self.limit:
                self._q.popleft()
                self.dropped += 1
                light_metrics().stream_dropped_total.inc()
            self._q.append(payload)
            self._cv.notify()

    def pop(self, timeout: float | None = None):
        """Next payload, or None on timeout/close."""
        with self._cv:
            if not self._q and not self.closed:
                self._cv.wait(timeout)
            if self._q:
                return self._q.popleft()
            return None

    def drain(self) -> list:
        with self._cv:
            out = list(self._q)
            self._q.clear()
            return out

    def __len__(self) -> int:
        with self._cv:
            return len(self._q)

    def close(self) -> None:
        with self._cv:
            self.closed = True
            self._cv.notify_all()


class LightServe:
    """Node-side light-client streaming service."""

    def __init__(
        self,
        chain_id: str,
        block_store,
        state_store,
        backend: str = "tpu",
        cache_size: int = 4096,
        subscriber_queue: int = 4096,
        mmr_store=None,
        trust_level: tuple[int, int] = (1, 3),
        sched=None,
        tenant: str = "",
        payload_retain: int = 4096,
    ):
        self.chain_id = chain_id
        self.block_store = block_store
        self.state_store = state_store
        self.backend = backend
        self.sched = sched  # shared VerifyScheduler (crypto/sched.py)
        self.tenant = tenant
        self.trust_level = trust_level
        self.subscriber_queue = subscriber_queue
        self.cache = VerifiedCommitCache(cache_size)
        self.mmr = MMR.load(mmr_store) if (
            mmr_store is not None and mmr_store.node_count() > 0
        ) else MMR(store=mmr_store)
        # leaf i of the MMR is the header at base_height + i; a fresh
        # accumulator anchors at the first height it sees committed.
        self.base_height: int | None = None
        if mmr_store is not None:
            self.base_height = mmr_store.load_base_height()
        self._mmr_store = mmr_store
        self._subs: dict[int, StreamSubscriber] = {}
        self._next_sub_id = 0
        self._lock = threading.Lock()
        self.heights_served = 0
        # rendered-payload ring: lets a reconnecting subscriber resume
        # from a cursor (`subscribe(since=H)`) without re-rendering —
        # the replayed dicts are the exact objects live pushes carried
        self.payload_retain = max(1, int(payload_retain))
        self._payloads: OrderedDict[int, dict] = OrderedDict()
        # optional da.DAServe (node wiring): stream payloads then carry
        # the height's DA commitment fields for sampling clients
        self.da_serve = None

    # -- commit hook -----------------------------------------------------
    def on_commit(self, block, resp=None) -> None:
        """BlockExecutor event handler: fold the committed header into
        the accumulator and fan the height's payload out once."""
        header = block.header
        with self._lock:
            if self.base_height is None:
                self.base_height = header.height
                if self._mmr_store is not None:
                    self._mmr_store.save_base_height(header.height)
            expected = self.base_height + self.mmr.leaf_count
            if header.height != expected:
                # blocksync replay or restart overlap: never double-append
                if header.height < expected:
                    return
                # a gap means the accumulator missed heights (e.g. serve
                # enabled mid-chain after statesync) — re-anchor by
                # backfilling from the block store.
                self._backfill_locked(expected, header.height)
            self.mmr.append(header.hash())
            payload = self._render_payload(header)
            self._payloads[header.height] = payload
            while len(self._payloads) > self.payload_retain:
                self._payloads.popitem(last=False)
            subs = list(self._subs.values())
            self.heights_served += 1
        for sub in subs:
            sub.push(payload)

    def _backfill_locked(self, from_height: int, to_height: int) -> None:
        for h in range(from_height, to_height):
            blk = self.block_store.load_block(h)
            if blk is None:
                raise RuntimeError(
                    f"light serve cannot backfill height {h}: not in store"
                )
            self.mmr.append(blk.header.hash())

    def _render_payload(self, header) -> dict:
        """One shared dict per height — rendered once, pushed to every
        subscriber queue by reference."""
        proof = self._prove_locked(header.height)
        payload = {
            "height": header.height,
            "hash": header.hash().hex().upper(),
            "time": str(header.time),
            "validators_hash": header.validators_hash.hex().upper(),
            "next_validators_hash": header.next_validators_hash.hex().upper(),
            "app_hash": header.app_hash.hex().upper(),
            "mmr_size": self.mmr.leaf_count,
            "mmr_root": self.mmr.root().hex().upper(),
            "mmr_proof": proof.encode().hex(),
        }
        seen = self.block_store.load_seen_commit(header.height)
        cert = getattr(seen, "cert", None) if seen is not None else None
        if cert is not None:
            # cert-native chain (ISSUE 17): ship the aggregate so stream
            # consumers verify the height with one pairing, no re-fetch
            payload["cert"] = cert.encode().hex()
        if self.da_serve is not None:
            # DA commit hook runs before this one (node wiring order), so
            # the height's commitment is already encoded and retained
            payload.update(self.da_serve.stream_fields(header.height))
        return payload

    # -- MMR proofs ------------------------------------------------------
    def _leaf_index(self, height: int) -> int:
        if self.base_height is None:
            raise IndexError("light serve accumulator is empty")
        idx = height - self.base_height
        if not (0 <= idx < self.mmr.leaf_count):
            raise IndexError(
                f"height {height} outside accumulator "
                f"[{self.base_height}, {self.base_height + self.mmr.leaf_count})"
            )
        return idx

    def _prove_locked(self, height: int) -> MMRProof:
        proof = self.mmr.prove(self._leaf_index(height))
        light_metrics().proof_bytes.observe(proof.num_bytes())
        return proof

    def ancestry_proof(self, height: int) -> MMRProof:
        """Peak-walking ancestry proof for a committed height against
        the accumulator's current snapshot."""
        with self._lock:
            return self._prove_locked(height)

    def mmr_snapshot(self) -> tuple[int, bytes]:
        """(leaf_count, root) of the current accumulator."""
        with self._lock:
            return self.mmr.leaf_count, self.mmr.root()

    # -- verified commits ------------------------------------------------
    def verified_commit(self, height: int):
        """The height's (SignedHeader, ValidatorSet), commit-verified at
        most once regardless of fan-out."""
        return self.cache.get_or_verify(
            height, lambda: self._verify_height(height)
        )

    def _verify_height(self, height: int):
        block = self.block_store.load_block(height)
        commit = self.block_store.load_block_commit(height)
        if commit is None:
            commit = self.block_store.load_seen_commit(height)
        vals = self.state_store.load_validators(height)
        if block is None or commit is None or vals is None:
            raise KeyError(f"height {height} not available to light serve")
        with verify_context(self.sched, self.tenant, "light"):
            verify_commit_light(
                self.chain_id, vals, commit.block_id, height, commit,
                backend=self.backend,
            )
        light_metrics().headers_verified_total.inc()
        return LightBlock(SignedHeader(block.header, commit), vals)

    # -- server-side skipping bisection ----------------------------------
    def _commit_at(self, height: int):
        commit = self.block_store.load_block_commit(height)
        if commit is None:
            commit = self.block_store.load_seen_commit(height)
        return commit

    def _overlap_ok(self, trusted_height: int, candidate: int) -> bool:
        """Host-side screen for one skipping hop: does the trusted
        next-validator set (the set at trusted_height+1) hold more than
        trust_level of the power signing the candidate commit? No
        signature is checked here — the chosen pivot pays that once via
        the cache."""
        trusted_next = self.state_store.load_validators(trusted_height + 1)
        commit = self._commit_at(candidate)
        if trusted_next is None or commit is None:
            return False
        num, den = self.trust_level
        total = trusted_next.total_voting_power()
        tallied, seen = 0, set()
        cert = getattr(commit, "cert", None)
        if cert is not None:
            # cert-native commit: addresses come from the signing set at
            # the candidate height (the bitmap indexes it), not from the
            # signature column (which a certificate no longer carries)
            signing = self.state_store.load_validators(candidate)
            if signing is None or commit.size() != len(signing):
                return False
            for idx in range(len(signing)):
                if not cert.has_signer(idx):
                    continue
                addr = signing.get_by_index(idx).address
                if addr in seen:
                    continue
                seen.add(addr)
                _, val = trusted_next.get_by_address(addr)
                if val is not None:
                    tallied += val.voting_power
            return tallied > total * num // den
        for cs in commit.signatures:
            if not cs.is_commit():
                continue
            addr = cs.validator_address
            if addr in seen:
                continue
            seen.add(addr)
            _, val = trusted_next.get_by_address(addr)
            if val is not None:
                tallied += val.voting_power
        return tallied > total * num // den

    def plan_bisection(self, trusted_height: int, target_height: int
                       ) -> list[int]:
        """Minimal pivot-height chain trusted→target under validator-set
        churn: greedy farthest-first — from each trusted point, binary
        search the farthest height whose commit the trusted next set
        still covers. Greedy farthest-first yields a minimal chain
        because hop reachability is monotone in the starting height."""
        if target_height <= trusted_height:
            raise ValueError(
                f"target {target_height} must exceed trusted {trusted_height}"
            )
        pivots: list[int] = []
        cur = trusted_height
        while cur < target_height:
            if cur + 1 == target_height or self._overlap_ok(
                    cur, target_height):
                pivots.append(target_height)
                break
            # farthest m in (cur+1, target) with overlap; adjacent cur+1
            # is always reachable (verified against the exact next set).
            lo, hi, best = cur + 2, target_height - 1, cur + 1
            while lo <= hi:
                mid = (lo + hi) // 2
                if self._overlap_ok(cur, mid):
                    best, lo = mid, mid + 1
                else:
                    hi = mid - 1
            pivots.append(best)
            cur = best
        light_metrics().bisections_total.inc(len(pivots))
        return pivots

    def bisect(self, trusted_height: int, target_height: int
               ) -> list[LightBlock]:
        """Verified pivot light-blocks for the minimal skipping chain;
        each pivot's commit verification goes through the shared cache."""
        plan = self.plan_bisection(trusted_height, target_height)
        return [self.verified_commit(h) for h in plan]

    # -- replica bootstrap -----------------------------------------------
    def bootstrap(self, base_height: int, leaf_hashes: list[bytes]) -> None:
        """Seed an EMPTY accumulator from a snapshot's leaf sequence.

        The MMR is append-only post-order, so replaying the same leaves
        reproduces the core's accumulator bit-exactly; subsequent
        `on_commit` calls continue from `base_height + len(leaves)`.
        Used by the serving-replica snapshot restore (replication/)."""
        with self._lock:
            if self.mmr.leaf_count or self.base_height is not None:
                raise RuntimeError(
                    "light serve bootstrap requires an empty accumulator")
            self.base_height = base_height
            if self._mmr_store is not None:
                self._mmr_store.save_base_height(base_height)
            for leaf in leaf_hashes:
                self.mmr.append(leaf)

    # -- subscriptions ---------------------------------------------------
    def subscribe(self, since: int | None = None
                  ) -> tuple[int, StreamSubscriber]:
        """Register a stream subscriber; ``since=H`` preloads every
        retained payload with height > H (cursor resume for failover —
        a client that lost its connection at H sees no gap as long as
        the ring still covers H+1)."""
        with self._lock:
            sub_id = self._next_sub_id
            self._next_sub_id += 1
            sub = self._subs[sub_id] = StreamSubscriber(self.subscriber_queue)
            if since is not None:
                for h, payload in self._payloads.items():
                    if h > since:
                        sub.push(payload)
            light_metrics().serve_subscribers.set(len(self._subs))
        return sub_id, sub

    def unsubscribe(self, sub_id: int) -> None:
        with self._lock:
            sub = self._subs.pop(sub_id, None)
            light_metrics().serve_subscribers.set(len(self._subs))
        if sub is not None:
            sub.close()

    @property
    def subscriber_count(self) -> int:
        with self._lock:
            return len(self._subs)

    # -- introspection / lifecycle ---------------------------------------
    def stats(self) -> dict:
        hits = light_metrics().verify_cache_hits_total.values().get((), 0.0)
        misses = light_metrics().verify_cache_misses_total.values().get(
            (), 0.0)
        with self._lock:
            dropped = sum(s.dropped for s in self._subs.values())
            return {
                "subscribers": len(self._subs),
                "heights_served": self.heights_served,
                "mmr_size": self.mmr.leaf_count,
                "mmr_root": self.mmr.root().hex().upper(),
                "base_height": self.base_height,
                "cache_entries": len(self.cache),
                "cache_hits": int(hits),
                "cache_misses": int(misses),
                "stream_dropped": dropped,
                "max_verify_calls_per_height": max(
                    self.cache.verify_calls.values(), default=0),
            }

    def stop(self) -> None:
        with self._lock:
            subs = list(self._subs.values())
            self._subs.clear()
            light_metrics().serve_subscribers.set(0)
        for s in subs:
            s.close()


__all__ = [
    "LightServe",
    "StreamSubscriber",
    "VerifiedCommitCache",
]
