"""Shared verification scheduler: one dispatcher, N tenants (ISSUE 15).

Every verify consumer in the node — consensus commit validation,
blocksync replay windows, light-serve VerifiedCommitCache misses, and
mempool admission signature windows — used to run its own
Ed25519BatchVerifier dispatch. The engines are wire-bound per call
(round-5 bench: fixed per-dispatch cost dwarfs the per-sig cost at small n),
so under mixed load the device sees many small calls where it could see
few large ones. This module puts ONE scheduler between all of them and
the crypto dispatch:

  consumers --submit(filled verifier, tenant, source)--> per-tenant
  per-class queues --drainer--> coalesced mega-batch (absorb() merges
  the filled verifiers lane-exactly, recording each request's
  [start, end) range) --> ONE dispatch through the existing
  native/ladder/mesh path --> per-request verdict slices, bit-exact vs
  what each consumer's own dispatch would have returned.

Scheduling policy:

* Priority classes order service strictly: consensus > blocksync >
  light > background (admission rides in background). A queued commit
  verification never waits behind a flood of admission windows.
* Within a class, tenants are served by deficit round-robin weighted
  by signature count: each round an active tenant's deficit grows by
  ``quantum_sigs * weight`` and it may dequeue requests while its head
  fits the deficit. A hot tenant's share of any contended mega-batch is
  therefore bounded by weight/(total weight) plus one request of slack
  — the classic DRR bound — no matter how fast it submits.
* Coalescing window, the rule for an IDLE scheduler: the drainer
  collects until ``max_coalesce_sigs`` or until the OLDEST queued
  request has waited ``max_coalesce_delay_ms``, whichever comes first.
  Single-waiter fast path: when exactly one request is queued, nothing
  else arrives by the time the drainer looks AND no batch is unanswered,
  it dispatches immediately — a tenant alone on its scheduler pays zero
  coalescing tax. Behind an unanswered batch (whichever engine verified
  it) a lone request lingers to its deadline: that batch's callers are
  about to come back.
* Two batches unanswered, the window UNDER LOAD: the drainer does not
  wait for a verdict. It merges, packs and launches (``submit()``),
  hands the handle to the completion thread and returns to its queues,
  so batch n+1 is packed while batch n is on the device. At most
  ``_MAX_UNANSWERED`` batches are launched and unanswered; the cap holds
  back the LAUNCH, not the collection: the queues keep filling, and the
  moment a slot frees the drainer takes everything queued (priority and
  DRR order, up to ``max_coalesce_sigs``). Strict priority and DRR order
  what is QUEUED, so a consensus request can find two batches of at most
  ``max_coalesce_sigs`` lanes ahead of it, at 2.0 us a lane (66 ms at
  the default 16,384; 10 ms where 16 chains of 150 share a scheduler).

Two threads, started lazily on first submit and joined together: the
drainer (``verify-sched``: collect, merge, launch) and the completion
thread it owns (``verify-sched-done``: ``result()`` in launch order,
slices, answers, then the batch's slot). A ``backend="cpu"`` verifier
and one that cannot be merged (``coalescable`` false) have no
``submit()`` worth the name and are verified and answered on the
drainer's thread; ``drain_once()`` (manual mode) does everything on the
caller's. ``stop()`` drains what it can, then fails queued requests AND
those of every launched, unanswered batch with tenant context after
``stop_timeout_s``; ``close()`` additionally refuses later submits
immediately.

Multi-tenant wiring: ``acquire_shared()/release_shared()`` refcount one
process-wide scheduler per backend so N independent chains (distinct
chain_ids) share one scheduler + one mesh; each Node passes its
chain_id as the tenant. ``verify_context()`` is the thread-local seam
types/validation.py consults so verify_commit callers route their
ed25519 batch groups here without threading a scheduler through every
call signature.
"""

from __future__ import annotations

import queue
import threading
import time
from collections import deque
from concurrent.futures import Future

from ..utils import trace as _trace
from ..utils.metrics import crypto_metrics
from . import ed25519 as _ed

# strict service order; unknown sources verify at background priority
PRIORITY_CLASS = {
    "consensus": 0,
    "blocksync": 1,
    "light": 2,
    "admission": 3,
    "background": 3,
}
_N_CLASSES = 4
# batches launched and not yet answered, at most (blocksync.ReplayEngine's
# depth): one on the device, the next merged and packed behind it
_MAX_UNANSWERED = 2


class _Request:
    # t_done exists on a request answered by a traced run only: the
    # instant its verdict was set (read with getattr)
    __slots__ = ("bv", "tenant", "source", "prio", "n", "t_enqueue",
                 "t_taken", "t_done", "batch", "future")

    def __init__(self, bv, tenant: str, source: str, prio: int):
        self.bv = bv
        self.tenant = tenant
        self.source = source
        self.prio = prio
        self.n = bv.count()
        self.t_enqueue = time.perf_counter()
        # traced runs only: when _take_batch popped it, and the id of
        # the crypto.sched_coalesce span it rode in
        self.t_taken = self.t_enqueue
        self.batch = None
        self.future: Future = Future()


class _Batch:
    """One _take_batch from the queues to its last answer: it holds one
    of the _MAX_UNANSWERED slots, and stop() fails its requests if it
    cannot wait for them."""

    __slots__ = ("reqs", "ahead", "pending", "part", "ranges", "span_id",
                 "t_launch")

    def __init__(self, reqs: list[_Request], ahead: int):
        self.reqs = reqs
        # batches on the device and unanswered when this one was taken
        self.ahead = ahead
        # what submit() returned for `part` (the merged requests, lane
        # ranges in `ranges`, or the lone one, ranges None); None while
        # not launched and where everything was answered by the thread
        # that dispatched it
        self.pending = None
        self.part = reqs
        self.ranges = None
        # traced runs: the dispatch's crypto.sched_coalesce, and when
        # its submit() returned
        self.span_id = None
        self.t_launch = None

    def on_device(self) -> bool:
        # the host engine's batch comes back from submit() verified
        return (self.pending is not None
                and not isinstance(self.pending, _ed.DonePending))


class SchedPending:
    """Pending-compatible handle (.result()/.prefetch()) over a
    scheduler future, interchangeable with PendingBatch where consumers
    hold one — blocksync's window pipeline calls prefetch() on it."""

    __slots__ = ("_req",)

    def __init__(self, req: _Request):
        self._req = req

    @property
    def _future(self) -> Future:
        return self._req.future

    def prefetch(self) -> None:
        # the launch happens on the drainer's thread and the device
        # fetch on the completion thread, which asks for each verdict
        # as soon as its batch is launched; there is nothing for the
        # consumer to start early
        return None

    def result(self, timeout: float | None = None) -> tuple[bool, list[bool]]:
        if not _trace.enabled:
            return self._future.result(timeout)
        # the caller's wait, in its own tree (a child of its
        # types.verify_commit); `batch` names the crypto.sched_coalesce
        # the request rode in, on the drainer's thread; `wake_ms` is
        # what lies between the verdict set on the completion thread
        # and this thread running again (none before the call began)
        req = self._req
        with _trace.span("crypto.verdict_wait", path="sched",
                         n=req.n) as sp:
            t_call = time.perf_counter()
            try:
                return self._future.result(timeout)
            finally:
                sp.add(batch=req.batch)
                t_done = getattr(req, "t_done", None)
                if t_done is not None:
                    sp.add(wake_ms=round((time.perf_counter() - max(
                        t_done, t_call)) * 1e3, 3))


def _fail(fut: Future, exc: Exception) -> None:
    if not fut.done():
        try:
            fut.set_exception(exc)
        except Exception:  # noqa: BLE001 — lost the resolution race
            pass


def _fail_batch(reqs: list[_Request], exc: Exception) -> None:
    for req in reqs:
        _fail(req.future, RuntimeError(
            f"verify dispatch failed for tenant "
            f"{req.tenant!r} ({req.source}): {exc}"))


def _resolve(fut: Future, value) -> None:
    if not fut.done():
        try:
            fut.set_result(value)
        except Exception:  # noqa: BLE001 — lost the resolution race
            pass


def _slices(verdict, ranges: list | None) -> list:
    """Each request's own (ok, bits) out of its batch's verdict."""
    if ranges is None:
        return [verdict]
    bits_all = verdict[1]
    return [(all(bits_all[start:end]), bits_all[start:end])
            for start, end in ranges]


class VerifyScheduler:
    """Coalescing verify dispatcher with per-tenant weighted fairness."""

    def __init__(
        self,
        backend: str = "tpu",
        max_coalesce_sigs: int = 16384,
        max_coalesce_delay_ms: float = 2.0,
        stop_timeout_s: float = 2.0,
        quantum_sigs: int = 512,
        manual: bool = False,
    ):
        self.backend = backend
        self.max_coalesce_sigs = max(1, int(max_coalesce_sigs))
        self.max_coalesce_delay_s = max(0.0, float(max_coalesce_delay_ms)) / 1e3
        self.stop_timeout_s = float(stop_timeout_s)
        self.quantum_sigs = max(1, int(quantum_sigs))
        # manual mode (tests + deterministic measurement): no drainer
        # thread; callers pump batches with drain_once()
        self.manual = manual
        # queues[tenant][prio] -> deque[_Request]; _order preserves
        # first-seen tenant order for round-robin stability
        self._queues: dict[str, list[deque]] = {}
        self._order: list[str] = []
        self._weights: dict[str, float] = {}
        self._deficit: dict[str, float] = {}
        self._cv = threading.Condition()
        self._thread: threading.Thread | None = None
        self._stopped = False
        self._closed = False
        # every batch taken and not yet answered, oldest first
        self._inflight: list[_Batch] = []
        self._n_queued = 0
        # counters a workload can snapshot: dispatches is the number the
        # coalescing win is measured on (dispatch calls per 1k sigs)
        self.stats = {
            "requests": 0, "sigs": 0, "dispatches": 0,
            "coalesced_requests": 0, "passthrough": 0,
        }
        self._tenant_sigs: dict[str, int] = {}

    # -- producer side ---------------------------------------------------
    def submit(self, bv, tenant: str = "default",
               source: str = "background") -> SchedPending:
        """Enqueue a filled Ed25519BatchVerifier; the returned handle's
        result() is bit-exact with what ``bv.verify()`` would return."""
        prio = PRIORITY_CLASS.get(source, _N_CLASSES - 1)
        req = _Request(bv, tenant, source, prio)
        if req.n == 0:
            # match Ed25519BatchVerifier.verify() on an empty batch
            _resolve(req.future, (False, []))
            return SchedPending(req)
        with self._cv:
            if self._closed:
                _fail(req.future,
                      RuntimeError("verify scheduler closed"))
                return SchedPending(req)
            if not self.manual and (self._stopped or self._thread is None):
                # lazy start, admission-pipeline style: first submit
                # after construction (or stop()) spins the drainer up,
                # and the drainer its completion thread
                self._stopped = False
                if self._thread is None:
                    self._thread = threading.Thread(
                        target=self._drain_loop, daemon=True,
                        name="verify-sched",
                    )
                    self._thread.start()
            q = self._queues.get(tenant)
            if q is None:
                q = self._queues[tenant] = [deque() for _ in range(_N_CLASSES)]
                self._order.append(tenant)
            q[req.prio].append(req)
            self._n_queued += 1
            self.stats["requests"] += 1
            self.stats["sigs"] += req.n
            self._tenant_sigs[tenant] = \
                self._tenant_sigs.get(tenant, 0) + req.n
            crypto_metrics().sched_queue_depth.set(
                sum(len(d) for d in q), tenant)
            self._cv.notify()
        return SchedPending(req)

    def set_tenant_weight(self, tenant: str, weight: float) -> None:
        with self._cv:
            self._weights[tenant] = max(0.01, float(weight))

    def tenant_stats(self) -> dict[str, int]:
        """Per-tenant signatures accepted (fairness accounting)."""
        with self._cv:
            return dict(self._tenant_sigs)

    # -- lifecycle -------------------------------------------------------
    def stop(self) -> None:
        """Stop both threads (the drainer joins its completion thread);
        queued requests and those of launched batches not answered
        within stop_timeout_s fail with tenant context."""
        with self._cv:
            self._stopped = True
            self._cv.notify_all()
        t = self._thread
        if t is not None:
            t.join(timeout=self.stop_timeout_s)
        self._thread = None
        with self._cv:
            orphans: list[_Request] = []
            for q in self._queues.values():
                for d in q:
                    orphans.extend(d)
                    d.clear()
            self._n_queued = 0
            for b in self._inflight:
                orphans.extend(b.reqs)
            # a thread that outlived the join finds its slot gone and
            # its requests answered; a restart begins with both free
            self._inflight = []
            self._cv.notify_all()
            for tenant in self._queues:
                crypto_metrics().sched_queue_depth.set(0.0, tenant)
        for req in orphans:
            _fail(req.future, RuntimeError(
                f"verify scheduler stopped: {req.n}-sig {req.source} "
                f"request from tenant {req.tenant!r} abandoned"))

    def close(self) -> None:
        """Terminal stop: later submits error immediately."""
        with self._cv:
            self._closed = True
        self.stop()

    # -- drainer ---------------------------------------------------------
    def _drain_loop(self) -> None:
        # the hand-over: launched batches, in launch order
        done_q: queue.SimpleQueue = queue.SimpleQueue()
        done = threading.Thread(target=self._done_loop, args=(done_q,),
                                daemon=True, name="verify-sched-done")
        done.start()
        try:
            while (b := self._collect()) is not None:
                self._dispatch(b)
                if b.pending is None:
                    self._finish(b)
                else:
                    done_q.put(b)
        finally:
            done_q.put(None)
            done.join()

    def _done_loop(self, done_q: queue.SimpleQueue) -> None:
        while (b := done_q.get()) is not None:
            self._finish(b)

    def _finish(self, b: _Batch) -> None:
        """The rest of a dispatched batch: the verdict and the answers
        of what it launched, then its slot (the one place that frees
        one)."""
        if b.pending is not None:
            self._complete(b)
        with self._cv:
            if b in self._inflight:  # stop() may have let it go
                self._inflight.remove(b)
            self._cv.notify_all()

    def _collect(self) -> _Batch | None:
        """Wait for work and for a free slot, linger for the coalescing
        window, pop one DRR-ordered batch into the slot. None = stopped
        with nothing queued. The span opens before and closes after the
        lock, so the tracer never flushes under it."""
        with _trace.span("crypto.sched_collect") as sp, self._cv:
            t_work = 0
            while True:
                if self._n_queued:
                    if sp.id is not None:
                        # traced: when this pass saw work queued; the
                        # first such reading ends the idle wait, the
                        # last (a slot is free) the wait for a slot
                        t_slot = time.perf_counter_ns()
                        t_work = t_work or t_slot
                    if len(self._inflight) < _MAX_UNANSWERED:
                        break
                    # the cap holds back the launch only: the queues
                    # fill on meanwhile
                elif self._stopped:
                    return None
                self._cv.wait()
            oldest = min(
                d[0].t_enqueue
                for q in self._queues.values() for d in q if d)
            deadline = oldest + self.max_coalesce_delay_s
            # single-waiter fast path: one request queued and no batch
            # unanswered falls straight through, so a tenant alone on
            # its scheduler dispatches with zero added latency. Behind
            # an unanswered batch a lone request is the first of the
            # next cohort and lingers like any other; what waited out a
            # full cap is past its deadline and leaves at once, all of it
            while (not self._stopped
                   and (self._n_queued > 1 or self._inflight)
                   and self._queued_sigs() < self.max_coalesce_sigs):
                left = deadline - time.perf_counter()
                if left <= 0:
                    break
                self._cv.wait(timeout=left)
            b = self._take()
            if t_work:
                sp.add(idle_ms=round((t_work - sp.t0_ns) / 1e6, 3),
                       slot_ms=round((t_slot - t_work) / 1e6, 3),
                       linger_ms=round(
                           (time.perf_counter_ns() - t_slot) / 1e6, 3))
            return b

    def _take(self) -> _Batch:
        """One batch off the queues and into a slot. Caller holds the
        lock; every other unanswered batch is launched by now."""
        b = _Batch(self._take_batch(),
                   sum(x.on_device() for x in self._inflight))
        self._inflight.append(b)
        return b

    def _queued_sigs(self) -> int:
        return sum(r.n for q in self._queues.values() for d in q for r in d)

    def _take_batch(self) -> list[_Request]:
        """Pop up to max_coalesce_sigs of queued requests in (priority,
        weighted-DRR) order. Caller holds the lock."""
        batch: list[_Request] = []
        sigs = 0
        for prio in range(_N_CLASSES):
            while sigs < self.max_coalesce_sigs:
                active = [t for t in self._order
                          if self._queues[t][prio]]
                if not active:
                    break
                progressed = False
                for tenant in active:
                    d = self._queues[tenant][prio]
                    if not d:
                        continue
                    self._deficit[tenant] = (
                        self._deficit.get(tenant, 0.0)
                        + self.quantum_sigs * self._weights.get(tenant, 1.0))
                    while d and sigs < self.max_coalesce_sigs:
                        req = d[0]
                        if req.n > self._deficit[tenant]:
                            break
                        if batch and sigs + req.n > self.max_coalesce_sigs:
                            break  # request waits for the next batch
                        d.popleft()
                        self._n_queued -= 1
                        self._deficit[tenant] -= req.n
                        batch.append(req)
                        sigs += req.n
                        progressed = True
                    if not d:
                        # idle flows carry no credit into the next
                        # contention period (standard DRR reset)
                        self._deficit[tenant] = 0.0
                if not progressed and sigs > 0:
                    break
                if not progressed and sigs == 0:
                    # every head exceeds its deficit: keep accumulating
                    # rounds — bounded, since deficits grow by at least
                    # quantum_sigs * min_weight per round
                    continue
            if sigs >= self.max_coalesce_sigs:
                break
        for tenant in self._order:
            crypto_metrics().sched_queue_depth.set(
                sum(len(d) for d in self._queues[tenant]), tenant)
        if _trace.enabled:
            now = time.perf_counter()
            for req in batch:
                req.t_taken = now
        return batch

    def _dispatch(self, b: _Batch) -> None:
        """ONE crypto dispatch for the whole batch, launched and left in
        `b.pending` for _finish(); per-request verdicts are recovered
        from the mega-bitmap by recorded lane ranges."""
        try:
            # non-coalescable verifiers (certificate one-pairing checks,
            # ISSUE 17) dispatch individually inside this drain cycle;
            # only ed25519-absorbing verifiers share the mega-batch, and
            # a lone one of those dispatches as-is too
            solo, merged = [], []
            for req in b.reqs:
                (merged if getattr(req.bv, "coalescable", True)
                 else solo).append(req)
            if len(merged) == 1:
                solo.append(merged.pop())
            for req in solo:
                self._pass_through(req, b)
            if merged:
                self._coalesce(merged, b)
        except Exception as exc:  # noqa: BLE001 — deliver, don't die
            _fail_batch(b.reqs, exc)

    def _pass_through(self, req: _Request, b: _Batch) -> None:
        """A lone request's verifier dispatches as-is: no absorb copy,
        no coalescing tax."""
        self.stats["dispatches"] += 1
        self.stats["passthrough"] += 1
        crypto_metrics().sched_batch_sigs.observe(req.n)
        with _trace.span("crypto.sched_coalesce") as sp:
            self._launch(b, req.bv, [req], None, sp, None)

    def _coalesce(self, merged: list[_Request], b: _Batch) -> None:
        m = crypto_metrics()
        with _trace.span("crypto.sched_coalesce") as sp:
            t0 = time.perf_counter() if _trace.enabled else None
            mega = _ed.Ed25519BatchVerifier(backend=self.backend)
            ranges = [mega.absorb(req.bv) for req in merged]
            absorb_s = None if t0 is None else time.perf_counter() - t0
            for req in merged:
                m.sched_coalesced_total.inc(1.0, req.source)
            self.stats["dispatches"] += 1
            self.stats["coalesced_requests"] += len(merged)
            m.sched_batch_sigs.observe(mega.count())
            self._launch(b, mega, merged, ranges, sp, absorb_s)

    def _launch(self, b: _Batch, bv, part: list[_Request],
                ranges: list | None, sp, absorb_s: float | None) -> None:
        """Inside the dispatch's span: launch `bv` for `part` without
        waiting for its verdict. The span closes behind the launch, so
        its fields are those known by now."""
        crypto_metrics().sched_overlap_total.inc(1.0, str(b.ahead))
        if _trace.enabled:
            per_tenant: dict[str, int] = {}
            for req in part:
                req.batch = sp.id
                per_tenant[req.tenant] = per_tenant.get(req.tenant, 0) + req.n
            sigs = sum(per_tenant.values())
            sp.add(n_requests=len(part), sigs=sigs,
                   lanes_bucket=_ed._bucket(sigs),
                   tenants=",".join(sorted(per_tenant)),
                   sources=",".join(sorted({r.source for r in part})),
                   per_tenant_sigs=per_tenant, inflight=b.ahead)
            if absorb_s is not None:
                sp.add(absorb_ms=round(absorb_s * 1e3, 3))
        if not getattr(bv, "coalescable", True) or bv.backend == "cpu":
            # the certificate's pairing check and the oracle have no
            # submit() worth the name: verified and answered here
            self._answer(part, _slices(bv.verify(), ranges), sp.id)
            return
        b.part, b.ranges, b.span_id = part, ranges, sp.id
        b.pending = bv.submit()
        if _trace.enabled:
            b.t_launch = time.perf_counter()

    def _complete(self, b: _Batch) -> None:
        """The verdict of a launched batch (the fetch releases the
        interpreter), its slices, its answers. What raises here fails
        the requests of this batch only."""
        try:
            with _trace.span("crypto.sched_complete", batch=b.span_id,
                             n_requests=len(b.part)) as sp:
                t0 = time.perf_counter() if _trace.enabled else None
                verdict = b.pending.result()
                if t0 is not None:
                    sp.add(wait_ms=round(
                        (time.perf_counter() - t0) * 1e3, 3))
                self._answer(b.part, _slices(verdict, b.ranges), b.span_id)
                if b.t_launch is not None:
                    sp.add(since_launch_ms=round(
                        (time.perf_counter() - b.t_launch) * 1e3, 3))
        except Exception as exc:  # noqa: BLE001 — deliver, don't die
            _fail_batch(b.part, exc)

    def _answer(self, part: list[_Request], verdicts: list,
                span_id: int | None) -> None:
        """Resolve every request of one dispatch, then (traced runs)
        write one crypto.sched_wait a request: children of the
        dispatch's crypto.sched_coalesce (`span_id`) whichever thread
        writes them, stamped with the instant their verdict was set."""
        if not _trace.enabled:
            for req, verdict in zip(part, verdicts):
                _resolve(req.future, verdict)
            return
        for req, verdict in zip(part, verdicts):
            # before the verdict, so that the caller it wakes finds it
            req.t_done = time.perf_counter()
            _resolve(req.future, verdict)
        alone = len(part) == 1
        # emit() lets a field of its own name stand for the stack's
        tree = {} if span_id is None else {"parent": span_id,
                                           "root": span_id}
        for req in part:
            _trace.emit(
                "crypto.sched_wait", "span",
                dur_ms=round((req.t_done - req.t_enqueue) * 1e3, 3),
                queued_ms=round((req.t_taken - req.t_enqueue) * 1e3, 3),
                tenant=req.tenant, source=req.source, n=req.n,
                batch=span_id, alone=alone, **tree)

    # -- manual pump (tests, deterministic measurement) ------------------
    def drain_once(self) -> int:
        """Form, dispatch and answer one batch from whatever is queued
        right now, on the caller's thread; returns the number of
        requests dispatched. Only meaningful in manual mode (no drainer
        thread to race with)."""
        with self._cv:
            b = self._take()
        self._dispatch(b)
        self._finish(b)
        return len(b.reqs)


# ----------------------------------------------------------------------
# thread-local routing context: verify_commit callers wrap their call in
# verify_context(...) and types/validation.py routes ed25519 batch
# groups through the scheduler without new plumbing in every signature.
# ----------------------------------------------------------------------
class _Ctx:
    __slots__ = ("sched", "tenant", "source")

    def __init__(self, sched: VerifyScheduler, tenant: str, source: str):
        self.sched = sched
        self.tenant = tenant
        self.source = source

    def submit(self, bv) -> SchedPending:
        return self.sched.submit(bv, tenant=self.tenant, source=self.source)


_tls = threading.local()


class verify_context:
    """``with verify_context(sched, tenant, source):`` — route ed25519
    batch verification inside the block to the shared scheduler. Nestable;
    a None scheduler makes the block a no-op (config-off wiring stays
    branch-free at call sites)."""

    def __init__(self, sched: VerifyScheduler | None, tenant: str,
                 source: str):
        self._ctx = _Ctx(sched, tenant, source) if sched is not None else None
        self._prev = None

    def __enter__(self):
        self._prev = getattr(_tls, "ctx", None)
        if self._ctx is not None:
            _tls.ctx = self._ctx
        return self

    def __exit__(self, *exc):
        if self._ctx is not None:
            _tls.ctx = self._prev
        return False


def current_context() -> _Ctx | None:
    return getattr(_tls, "ctx", None)


# ----------------------------------------------------------------------
# process-wide shared scheduler: N nodes (N chains) in one process share
# one scheduler per backend — the "many chains, one mesh" wiring.
# ----------------------------------------------------------------------
_shared: dict[str, tuple[VerifyScheduler, int]] = {}
_shared_lock = threading.Lock()


def acquire_shared(backend: str = "tpu", **cfg) -> VerifyScheduler:
    """Refcounted per-backend singleton. The first acquirer's config
    wins (one scheduler can only have one coalescing policy); later
    acquirers share it as additional tenants."""
    with _shared_lock:
        ent = _shared.get(backend)
        if ent is None or ent[0]._closed:
            s = VerifyScheduler(backend=backend, **cfg)
            _shared[backend] = (s, 1)
            return s
        s, refs = ent
        _shared[backend] = (s, refs + 1)
        return s


def release_shared(sched: VerifyScheduler) -> None:
    """Drop one reference; the last release closes the scheduler."""
    with _shared_lock:
        for backend, (s, refs) in list(_shared.items()):
            if s is sched:
                if refs <= 1:
                    del _shared[backend]
                    break
                _shared[backend] = (s, refs - 1)
                return
    if sched is not None:
        sched.close()
