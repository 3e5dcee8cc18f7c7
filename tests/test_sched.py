"""Shared verification scheduler (crypto/sched.py, ISSUE 15).

Coalescing correctness is differential: the mega-batch's per-request
verdict slices must be bit-exact with what each request's own
``verify()`` would have returned — on accept AND on reject, across
request boundaries. Fairness is the DRR bound: an adversarial hot
tenant's share of any contended batch is limited by its weight. The
lifecycle mirrors the admission pipeline: stop() fails queued and
in-flight requests with tenant context, close() refuses later submits.
"""

import json
import threading
import time

import pytest

from cometbft_tpu.crypto import sched as S
from cometbft_tpu.crypto.ed25519 import (
    DonePending, Ed25519BatchVerifier, Ed25519PrivKey)
from cometbft_tpu.types import validation
from cometbft_tpu.utils import factories as fx

_PRIVS = [Ed25519PrivKey.generate() for _ in range(8)]


def _bv(n, bad=(), tag=b"", backend="cpu"):
    """A filled verifier (the cpu oracle unless told) with n sigs;
    indices in `bad` carry a corrupted signature."""
    bv = Ed25519BatchVerifier(backend=backend)
    for i in range(n):
        p = _PRIVS[i % len(_PRIVS)]
        msg = b"sched-msg-%d-" % i + tag
        sig = p.sign(msg)
        if i in bad:
            sig = sig[:-1] + bytes([sig[-1] ^ 0xFF])
        bv.add(p.pub_key(), msg, sig)
    return bv


# -- coalescing correctness ---------------------------------------------

def test_coalesced_matches_sequential_accept_and_reject():
    """Differential: every request's sliced verdict from one coalesced
    dispatch equals its own standalone verify(), including rejects that
    sit at and across request boundaries."""
    shapes = [
        (3, ()), (5, (0,)), (1, ()), (4, (3,)), (2, (0, 1)), (7, ()),
    ]
    expected = []
    for i, (n, bad) in enumerate(shapes):
        ok, bits = _bv(n, bad, tag=b"seq%d" % i).verify()
        expected.append((ok, bits))

    s = S.VerifyScheduler(backend="cpu", manual=True)
    handles = [
        s.submit(_bv(n, bad, tag=b"seq%d" % i), tenant="t%d" % (i % 2),
                 source="consensus")
        for i, (n, bad) in enumerate(shapes)
    ]
    assert s.drain_once() == len(shapes)
    assert s.stats["dispatches"] == 1
    for h, (ok, bits) in zip(handles, expected):
        got_ok, got_bits = h.result(timeout=5)
        assert (got_ok, got_bits) == (ok, bits)


def test_coalesced_reject_bit_positions_exact():
    """A bad lane in request k must never bleed into request k±1."""
    s = S.VerifyScheduler(backend="cpu", manual=True)
    h_good = s.submit(_bv(4, tag=b"g"), tenant="a", source="light")
    h_bad = s.submit(_bv(4, bad=(0, 3), tag=b"b"), tenant="b",
                     source="light")
    h_good2 = s.submit(_bv(4, tag=b"g2"), tenant="a", source="blocksync")
    s.drain_once()
    ok, bits = h_good.result(5)
    assert ok and bits == [True] * 4
    ok, bits = h_bad.result(5)
    assert not ok and bits == [False, True, True, False]
    ok, bits = h_good2.result(5)
    assert ok and bits == [True] * 4


def test_empty_submit_matches_empty_verify():
    s = S.VerifyScheduler(backend="cpu", manual=True)
    ok, bits = s.submit(Ed25519BatchVerifier(backend="cpu")).result(1)
    assert (ok, bits) == Ed25519BatchVerifier(backend="cpu").verify()


def test_priority_classes_order_service():
    """With the sig budget capping one batch, consensus work dispatches
    ahead of earlier-queued admission work."""
    s = S.VerifyScheduler(backend="cpu", manual=True,
                          max_coalesce_sigs=4)
    h_adm = s.submit(_bv(3, tag=b"adm"), tenant="a", source="admission")
    h_cons = s.submit(_bv(3, tag=b"cons"), tenant="a", source="consensus")
    s.drain_once()
    assert h_cons._future.done()
    assert not h_adm._future.done()
    s.drain_once()
    assert h_adm.result(5)[0]


# -- fairness -----------------------------------------------------------

def test_drr_hot_tenant_bounded_by_weight():
    """Adversarial tenant floods 60 requests; victim submits 6. In every
    contended batch the hot tenant's sig share stays near its DRR
    entitlement (equal weights -> ~1/2) instead of the ~10/11 a FIFO
    would give it, and the victim is fully served within the first
    batches."""
    s = S.VerifyScheduler(backend="cpu", manual=True,
                          max_coalesce_sigs=64, quantum_sigs=8)
    s.set_tenant_weight("hot", 1.0)
    s.set_tenant_weight("victim", 1.0)
    hot = [s.submit(_bv(4, tag=b"h%d" % i), tenant="hot", source="light")
           for i in range(60)]
    vic = [s.submit(_bv(4, tag=b"v%d" % i), tenant="victim",
                    source="light") for i in range(6)]
    batches = 0
    while s.drain_once():
        batches += 1
        if batches == 1:
            # victim fully served in the first contended batch: its 24
            # sigs fit its ~32-sig half share of the 64-sig batch
            assert all(h._future.done() for h in vic)
            done_hot = sum(h._future.done() for h in hot)
            # hot tenant bounded: it only backfills what the victim
            # left unused — (64 - 24)/4 = 10 requests, not the 16 a
            # FIFO would have given it before the victim's first
            assert done_hot <= 10
        assert batches < 64  # termination guard
    assert all(h.result(5)[0] for h in hot + vic)
    stats = s.tenant_stats()
    assert stats["hot"] == 240 and stats["victim"] == 24


def test_drr_weight_skews_share():
    """A 3x-weight tenant drains ~3x the sigs of a 1x tenant from the
    first contended batch."""
    s = S.VerifyScheduler(backend="cpu", manual=True,
                          max_coalesce_sigs=32, quantum_sigs=8)
    s.set_tenant_weight("big", 3.0)
    s.set_tenant_weight("small", 1.0)
    big = [s.submit(_bv(4, tag=b"B%d" % i), tenant="big", source="light")
           for i in range(20)]
    small = [s.submit(_bv(4, tag=b"s%d" % i), tenant="small",
                      source="light") for i in range(20)]
    s.drain_once()
    done_big = sum(h._future.done() for h in big)
    done_small = sum(h._future.done() for h in small)
    assert done_big > done_small
    while s.drain_once():
        pass
    assert all(h.result(5)[0] for h in big + small)


# -- latency floor ------------------------------------------------------

def test_single_waiter_passthrough_no_delay_wait():
    """A lone request on an otherwise-empty queue dispatches without
    waiting out the coalescing window, via the pass-through path (no
    absorb copy)."""
    s = S.VerifyScheduler(backend="cpu", max_coalesce_delay_ms=500.0)
    t0 = time.perf_counter()
    ok, bits = s.submit(_bv(3), tenant="solo", source="consensus").result(5)
    elapsed = time.perf_counter() - t0
    assert ok and len(bits) == 3
    assert elapsed < 0.25, f"single waiter waited {elapsed:.3f}s"
    assert s.stats["passthrough"] == 1
    s.close()


def test_deadline_bounds_coalescing_wait():
    """Two requests below the sig cap: the drainer lingers only until
    the oldest request's deadline, then dispatches both together."""
    s = S.VerifyScheduler(backend="cpu", max_coalesce_delay_ms=50.0,
                          max_coalesce_sigs=1 << 20)
    h1 = s.submit(_bv(2, tag=b"d1"), tenant="a", source="light")
    h2 = s.submit(_bv(2, tag=b"d2"), tenant="b", source="light")
    t0 = time.perf_counter()
    assert h1.result(5)[0] and h2.result(5)[0]
    assert time.perf_counter() - t0 < 2.0
    assert s.stats["dispatches"] >= 1
    s.close()


# -- concurrency --------------------------------------------------------

@pytest.mark.parametrize("backend", ["cpu", "tpu", "device"])
def test_concurrent_submit_stress_no_lost_or_duplicate_futures(backend,
                                                               monkeypatch):
    """16 producer threads x 12 submits each race the drainer, which
    answers what the oracle verified ("cpu"), and the completion thread,
    which answers what submit() launched: on the host engine ("tpu": off
    a chip every batch is under the dispatch's line) or on the device
    ("device": the stubbed submit() below, its handles open, so two
    batches are in flight at times); every future resolves exactly once
    with its own request's verdict."""
    if backend == "device":
        gate, backend = _Gate(monkeypatch, opened=True), "tpu"
    s = S.VerifyScheduler(backend=backend, max_coalesce_delay_ms=1.0,
                          max_coalesce_sigs=256)
    results = {}
    lock = threading.Lock()
    errors = []

    def producer(tid):
        try:
            for i in range(12):
                bad = (0,) if (tid + i) % 3 == 0 else ()
                tag = b"c%d-%d" % (tid, i)
                h = s.submit(_bv(2, bad=bad, tag=tag, backend=backend),
                             tenant="t%d" % (tid % 4), source="light")
                ok, bits = h.result(timeout=30)
                expect_ok = not bad
                with lock:
                    results[(tid, i)] = (ok, bits, expect_ok)
        except Exception as e:  # noqa: BLE001 — collect, assert below
            with lock:
                errors.append((tid, repr(e)))

    threads = [threading.Thread(target=producer, args=(t,))
               for t in range(16)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert not errors, errors
    assert len(results) == 16 * 12
    for (tid, i), (ok, bits, expect_ok) in results.items():
        assert ok == expect_ok, (tid, i, ok, bits)
        assert len(bits) == 2
    st = s.stats
    assert st["requests"] == 16 * 12
    assert st["dispatches"] <= st["requests"]
    s.close()


# -- lifecycle ----------------------------------------------------------

def test_submit_after_close_errors_immediately():
    s = S.VerifyScheduler(backend="cpu")
    s.close()
    h = s.submit(_bv(2), tenant="late", source="light")
    with pytest.raises(RuntimeError, match="closed"):
        h.result(timeout=1)


def test_stop_fails_queued_with_tenant_context():
    """Requests still queued when stop() gives up carry the tenant and
    source in the failure, mirroring the admission pipeline's abandoned
    futures."""
    s = S.VerifyScheduler(backend="cpu", manual=True, stop_timeout_s=0.1)
    h = s.submit(_bv(3, tag=b"orphan"), tenant="chain-z", source="blocksync")
    s.stop()  # manual mode: nothing drains it
    with pytest.raises(RuntimeError) as ei:
        h.result(timeout=1)
    msg = str(ei.value)
    assert "chain-z" in msg and "blocksync" in msg and "3-sig" in msg


def test_stop_then_resubmit_restarts_drainer():
    s = S.VerifyScheduler(backend="cpu")
    assert s.submit(_bv(2, tag=b"r1"), tenant="a").result(5)[0]
    s.stop()
    assert s.submit(_bv(2, tag=b"r2"), tenant="a").result(5)[0]
    s.close()


# -- shared registry + multi-chain --------------------------------------

def test_acquire_shared_refcounts_per_backend():
    a = S.acquire_shared("cpu", max_coalesce_delay_ms=1.0)
    b = S.acquire_shared("cpu")
    assert a is b
    S.release_shared(b)
    assert not a._closed  # one ref left
    S.release_shared(a)
    assert a._closed
    c = S.acquire_shared("cpu", max_coalesce_delay_ms=1.0)
    assert c is not a  # closed singleton recreated
    S.release_shared(c)


def test_two_chains_one_scheduler_via_verify_context():
    """Two tenants (distinct chain_ids) route real verify_commit calls
    through one shared scheduler; per-tenant accounting sees both."""
    sched = S.VerifyScheduler(backend="cpu", max_coalesce_delay_ms=1.0)
    try:
        for chain, tenant in (("chain-a", "chain-a"), ("chain-b", "chain-b")):
            signers = fx.make_signers(6, seed=7)
            vals = fx.make_validator_set(signers)
            by_addr = {x.address(): x for x in signers}
            bid = fx.make_block_id(chain.encode())
            commit = fx.make_commit(chain, 3, 0, bid, vals, by_addr)
            with S.verify_context(sched, tenant, "consensus"):
                validation.verify_commit(chain, vals, bid, 3, commit,
                                         backend="cpu")
        stats = sched.tenant_stats()
        assert stats.get("chain-a", 0) > 0
        assert stats.get("chain-b", 0) > 0
        assert sched.stats["requests"] >= 2
    finally:
        sched.close()


def test_verify_context_reject_still_blames_exact_index():
    """Routed through the scheduler, a bad signature still raises
    ErrInvalidSignature naming the exact commit index (the sliced
    bitmap is index-aligned)."""
    sched = S.VerifyScheduler(backend="cpu", max_coalesce_delay_ms=1.0)
    try:
        signers = fx.make_signers(6, seed=13)
        vals = fx.make_validator_set(signers)
        by_addr = {x.address(): x for x in signers}
        bid = fx.make_block_id(b"blame")
        commit = fx.make_commit("blame-chain", 4, 0, bid, vals, by_addr)
        sig = bytearray(commit.signatures[3].signature)
        sig[0] ^= 0xFF
        commit.signatures[3].signature = bytes(sig)
        with S.verify_context(sched, "blame-chain", "consensus"):
            with pytest.raises(validation.ErrInvalidSignature) as ei:
                validation.verify_commit("blame-chain", vals, bid, 4,
                                         commit, backend="cpu")
        assert "index 3" in str(ei.value)
    finally:
        sched.close()


def test_verify_context_none_sched_is_noop():
    with S.verify_context(None, "t", "light"):
        assert S.current_context() is None


# -- a second batch in flight (ISSUE 35) ---------------------------------
# No chip: Ed25519BatchVerifier.submit is stubbed with a handle that
# resolves when the test opens it, so the test decides when each batch's
# verdict lands. Nothing here waits without a limit.

LIMIT_S = 30.0
# wide enough that two submits in a row meet in one window on a busy box
_WINDOW_MS = 50.0


class _Handle:
    """What the stubbed submit() returns for a batch on the device: its
    true verdict (the cpu oracle's), given out by result() once the test
    opens it."""

    def __init__(self, n, verdict, opened):
        self.n = n
        self.verdict = verdict
        self.t_launch = time.perf_counter()
        self.error = None
        self._open = threading.Event()
        if opened:
            self._open.set()

    def open(self, error=None):
        self.error = error
        self._open.set()

    def prefetch(self):
        pass

    def result(self):
        if not self._open.wait(LIMIT_S):
            raise TimeoutError("the test never opened this batch")
        if self.error is not None:
            raise self.error
        return self.verdict


class _HostHandle(_Handle, DonePending):
    """The host engine's: verified when submit() returns (a DonePending
    to the scheduler), its answer held back like the device's so that a
    test can look while it is unanswered."""

    def __init__(self, n, verdict, opened):
        DonePending.__init__(self, *verdict)
        _Handle.__init__(self, n, verdict, opened)


class _Gate:
    """Stubs Ed25519BatchVerifier.submit; `launched` holds the handles
    of what went to the device and `host` those of what the host engine
    verified, each in launch order."""

    def __init__(self, monkeypatch, opened=False):
        self.launched: list[_Handle] = []
        self.host: list[_HostHandle] = []
        self.fail_next_submit = None
        # the dispatch's line: a batch of fewer lanes comes back from
        # submit() verified, as the host engine's does (answered at
        # once unless the test holds it)
        self.host_under = 0
        self.hold_host = False
        self._cv = threading.Condition()
        gate = self

        def submit(bv):
            if gate.fail_next_submit is not None:
                exc, gate.fail_next_submit = gate.fail_next_submit, None
                raise exc
            oracle = Ed25519BatchVerifier(backend="cpu")
            oracle.absorb(bv)
            if bv.count() < gate.host_under:
                h = _HostHandle(bv.count(), oracle.verify(),
                                not gate.hold_host)
                to = gate.host
            else:
                h = _Handle(bv.count(), oracle.verify(), opened)
                to = gate.launched
            with gate._cv:
                to.append(h)
                gate._cv.notify_all()
            return h

        monkeypatch.setattr(Ed25519BatchVerifier, "submit", submit)

    def wait_launched(self, n, timeout=LIMIT_S, host=False):
        with self._cv:
            to = self.host if host else self.launched
            return self._cv.wait_for(lambda: len(to) >= n, timeout)

    def open_all(self):
        with self._cv:
            for h in self.launched + self.host:
                h.open()


def _tpu(n, bad=(), tag=b""):
    return _bv(n, bad, tag, backend="tpu")


def _records(path):
    with open(path, encoding="utf-8") as f:
        return [json.loads(line) for line in f]


def _done_threads():
    return {t for t in threading.enumerate()
            if t.name == "verify-sched-done"}


@pytest.fixture
def gate(monkeypatch):
    g = _Gate(monkeypatch)
    yield g
    g.open_all()  # no thread of a failed test stays in result()


def _two_in_flight(s, gate):
    """Batch 0 (a lone request on an idle scheduler) and batch 1 (two
    requests that met behind it) launched, neither answered."""
    h0 = s.submit(_tpu(3, bad=(2,), tag=b"f0"), tenant="a",
                  source="consensus")
    assert gate.wait_launched(1)
    h1 = s.submit(_tpu(2, tag=b"f1"), tenant="b", source="consensus")
    h2 = s.submit(_tpu(4, bad=(0, 3), tag=b"f2"), tenant="c",
                  source="blocksync")
    assert gate.wait_launched(2), "batch 1 waited for batch 0's verdict"
    assert [h.n for h in gate.launched] == [3, 6]
    assert not any(h._future.done() for h in (h0, h1, h2))
    return h0, h1, h2


_WANT_012 = [(False, [True, True, False]), (True, [True, True]),
             (False, [False, True, True, False])]


@pytest.mark.parametrize("first", [0, 1],
                         ids=["oldest-lands-first", "newest-lands-first"])
def test_second_batch_launches_over_the_first_and_the_cap_holds(gate, first):
    """Batch n+1 is submitted before batch n resolves; never more than
    two are unanswered, and what queues meanwhile leaves as ONE batch the
    moment a slot frees; every answer is its request's own slice,
    whichever verdict lands first."""
    s = S.VerifyScheduler(backend="tpu", max_coalesce_delay_ms=_WINDOW_MS)
    try:
        handles = list(_two_in_flight(s, gate))
        late = [(5, (4,)), (1, ()), (2, (0,))]
        for i, (n, bad) in enumerate(late):
            handles.append(s.submit(_tpu(n, bad, tag=b"late%d" % i),
                                    tenant="t%d" % i, source="light"))
            time.sleep(0.02)
        # several windows pass: the cap holds them, not the window
        assert not gate.wait_launched(3, timeout=0.25)
        assert len(s._inflight) == S._MAX_UNANSWERED == 2
        gate.launched[first].open()
        # verdicts are asked for in launch order: batch 1 alone frees
        # nothing, batch 0 frees one slot
        assert gate.wait_launched(3, timeout=0.3) is (first == 0)
        gate.launched[1 - first].open()
        assert gate.wait_launched(3)
        assert [h.n for h in gate.launched] == [3, 6, 8]
        gate.launched[2].open()
        got = [h.result(timeout=LIMIT_S) for h in handles]
        assert got == _WANT_012 + [
            (False, [True] * 4 + [False]), (True, [True]),
            (False, [False, True])]
        assert s.stats["dispatches"] == 3 and s.stats["passthrough"] == 1
    finally:
        gate.open_all()
        s.close()


@pytest.mark.parametrize("ahead", [None, "device", "host"],
                         ids=["idle", "behind-a-device-batch",
                              "behind-a-host-engine-batch"])
def test_a_lone_request_lingers_only_behind_an_unanswered_batch(gate, ahead):
    """The single-waiter fast path is for an IDLE scheduler: nothing
    queued beside the request and no batch unanswered, whichever engine
    verified it. Behind one the lone request waits out its window like
    any other."""
    delay_s = 0.4 if ahead else 20.0
    gate.hold_host = True
    s = S.VerifyScheduler(backend="tpu", max_coalesce_delay_ms=delay_s * 1e3)
    try:
        if ahead:
            gate.host_under = 3 if ahead == "host" else 0
            s.submit(_tpu(2, tag=b"ahead"), tenant="a", source="consensus")
            assert gate.wait_launched(1, host=ahead == "host")
            gate.host_under = 0
        t0 = time.perf_counter()
        h = s.submit(_tpu(3, tag=b"lone"), tenant="b", source="consensus")
        assert gate.wait_launched(1 + (ahead == "device"), timeout=10.0)
        took = gate.launched[-1].t_launch - t0
        if ahead:
            assert took >= delay_s * 0.95, f"left after {took:.3f}s"
        else:
            assert took < 10.0  # a 20 s window it did not wait out
        gate.open_all()
        assert h.result(timeout=LIMIT_S) == (True, [True] * 3)
        assert s.stats["passthrough"] == 1 + bool(ahead)
    finally:
        gate.open_all()
        s.close()


@pytest.mark.parametrize("where", ["submit", "result"])
def test_a_batch_that_raises_fails_its_own_requests_only(gate, where):
    """An exception from submit() (on the drainer) or from result() (on
    the completion thread) fails the requests of that batch with the
    tenant named; the batch in flight beside it is answered, both
    threads live on and the next batch is answered too."""
    before = _done_threads()
    s = S.VerifyScheduler(backend="tpu", max_coalesce_delay_ms=_WINDOW_MS)
    try:
        h0 = s.submit(_tpu(3, bad=(2,), tag=b"f0"), tenant="a",
                      source="consensus")
        assert gate.wait_launched(1)
        if where == "submit":
            gate.fail_next_submit = OSError("engine down")
        doomed = [s.submit(_tpu(2, tag=b"x%d" % i), tenant="t%d" % i,
                           source="consensus") for i in range(2)]
        if where == "result":
            assert gate.wait_launched(2) and gate.launched[1].n == 4
            gate.launched[1].open(error=OSError("engine down"))
        gate.launched[0].open()
        for i, h in enumerate(doomed):
            with pytest.raises(RuntimeError,
                               match=f"'t{i}'.*consensus.*engine down"):
                h.result(timeout=LIMIT_S)
        assert h0.result(timeout=LIMIT_S) == _WANT_012[0]
        after = s.submit(_tpu(3, bad=(1,), tag=b"after"), tenant="t0",
                         source="consensus")
        assert gate.wait_launched(2 + (where == "result"))
        gate.open_all()
        assert after.result(timeout=LIMIT_S) == (False, [True, False, True])
        (done,) = _done_threads() - before
        assert s._thread.is_alive() and done.is_alive()
        deadline = time.monotonic() + LIMIT_S
        while s._inflight and time.monotonic() < deadline:
            time.sleep(0.005)  # the slot frees behind the last answer
        assert s._inflight == []
    finally:
        gate.open_all()
        s.close()


@pytest.mark.parametrize("end", ["stop", "close"])
def test_stop_fails_two_batches_in_flight_and_joins_both_threads(gate, end):
    before = _done_threads()
    s = S.VerifyScheduler(backend="tpu", max_coalesce_delay_ms=_WINDOW_MS,
                          stop_timeout_s=0.2)
    handles = _two_in_flight(s, gate)
    queued = s.submit(_tpu(1, tag=b"q"), tenant="d", source="light")
    drainer = s._thread
    (done,) = _done_threads() - before
    assert drainer.name == "verify-sched"
    t0 = time.monotonic()
    getattr(s, end)()
    assert time.monotonic() - t0 < 5.0  # one stop_timeout_s for both joins
    for h, (n, tenant, source) in zip(
            handles + (queued,),
            [(3, "a", "consensus"), (2, "b", "consensus"),
             (4, "c", "blocksync"), (1, "d", "light")]):
        with pytest.raises(RuntimeError) as ei:
            h.result(timeout=LIMIT_S)
        msg = str(ei.value)
        assert "abandoned" in msg and f"{n}-sig {source}" in msg
        assert repr(tenant) in msg
    assert s._thread is None and s._inflight == []
    # the verdicts land after all: nothing is answered twice, and both
    # threads end behind them
    gate.open_all()
    for t in (drainer, done):
        t.join(timeout=LIMIT_S)
        assert not t.is_alive()
    late = s.submit(_tpu(2, tag=b"late"), tenant="a", source="consensus")
    if end == "close":
        with pytest.raises(RuntimeError, match="closed"):
            late.result(timeout=LIMIT_S)
        assert s._thread is None
    else:  # a submit after stop() starts both threads anew
        assert gate.wait_launched(3)
        gate.open_all()
        assert late.result(timeout=LIMIT_S) == (True, [True, True])
        assert s._thread is not drainer
        assert len(_done_threads() - before) == 1
        s.close()
    assert _done_threads() - before == set()  # joined with the drainer


def _overlap_counts():
    from cometbft_tpu.utils.metrics import crypto_metrics

    return dict(crypto_metrics().sched_overlap_total.values())


def test_a_host_engine_batch_holds_a_slot_and_is_not_counted_as_overlap(
        gate, monkeypatch, tmp_path):
    """A batch that submit() returns verified (under the dispatch's
    line) goes to the completion thread like any other, is answered in
    launch order and holds its slot till then; but `inflight` counts the
    batches on the DEVICE, so only what is launched over the device's
    batch reads 1."""
    gate.host_under = 4
    answered = []
    orig = S.VerifyScheduler._answer

    def answer(self, part, verdicts, span_id):
        answered.append((threading.current_thread().name,
                         sum(r.n for r in part)))
        orig(self, part, verdicts, span_id)

    from cometbft_tpu.utils import trace

    monkeypatch.setattr(S.VerifyScheduler, "_answer", answer)
    before = _overlap_counts()
    sink = str(tmp_path / "host.jsonl")
    trace.configure(sink)
    s = S.VerifyScheduler(backend="tpu", max_coalesce_delay_ms=_WINDOW_MS)
    try:
        dev = s.submit(_tpu(5, bad=(1,), tag=b"dev"), tenant="a",
                       source="consensus")
        assert gate.wait_launched(1)
        bvs = [_tpu(1, tag=b"h0"), _tpu(2, bad=(0,), tag=b"h1")]
        host = [s.submit(bv, tenant=t, source="light")
                for bv, t in zip(bvs, "bc")]
        assert gate.wait_launched(1, host=True)
        lone = s.submit(_tpu(2, tag=b"lone"), tenant="b", source="light")
        # verified, and behind the device's batch all the same: both
        # slots are taken and the lone request waits for one
        time.sleep(4 * _WINDOW_MS / 1e3)
        assert not any(h._future.done() for h in [dev, lone] + host)
        with s._cv:
            assert [(b.on_device(), sum(r.n for r in b.reqs))
                    for b in s._inflight] == [(True, 5), (False, 3)]
        gate.launched[0].open()
        assert dev.result(timeout=LIMIT_S) == (
            False, [True, False, True, True, True])
        assert [h.result(timeout=LIMIT_S) for h in host] == [
            (True, [True]), (False, [False, True])]
        assert lone.result(timeout=LIMIT_S) == (True, [True, True])
        assert answered == [("verify-sched-done", 5),
                            ("verify-sched-done", 3),
                            ("verify-sched-done", 2)]
        assert len(gate.launched) == 1 and s.stats["dispatches"] == 3
        after = _overlap_counts()
        # the lone request was taken behind the host engine's batch
        # only: a DonePending ahead reads 0
        assert {k: after[k] - before.get(k, 0.0) for k in after} == {
            ("0",): 2.0, ("1",): 1.0}
    finally:
        gate.open_all()
        s.close()
        trace.flush()
        trace.disable()
    # the span's field says the same, dispatch by dispatch
    assert [(r["sigs"], r["inflight"]) for r in _records(sink)
            if r["name"] == "crypto.sched_coalesce"] == [
        (5, 0), (3, 1), (2, 0)]


@pytest.mark.parametrize("backend", ["cpu", "tpu"])
def test_drain_once_is_synchronous_on_the_callers_thread(monkeypatch,
                                                         backend):
    """Manual mode: submit, result, answer, return; no thread exists."""
    before = _done_threads()
    gate = _Gate(monkeypatch, opened=True)
    s = S.VerifyScheduler(backend=backend, manual=True)
    hs = [s.submit(_bv(2, bad=(i,), tag=b"m%d" % i, backend=backend),
                   tenant="t%d" % i, source="consensus") for i in range(2)]
    assert s.drain_once() == 2
    assert all(h._future.done() for h in hs)
    assert [h.result(0)[1] for h in hs] == [[False, True], [True, False]]
    assert len(gate.launched) == (backend == "tpu")
    assert s._thread is None and _done_threads() == before
    assert s._inflight == [] and s.drain_once() == 0


@pytest.mark.parametrize("kind", ["cpu-backend", "not-coalescable"])
def test_a_verifier_without_a_submit_is_answered_by_the_drainer(
        gate, monkeypatch, kind):
    """The oracle and a verifier that cannot be merged are verified and
    answered on the drainer's thread, behind nothing: submit() is never
    called for them and they leave no batch unanswered."""
    answered = []
    orig = S.VerifyScheduler._answer

    def answer(self, part, verdicts, span_id):
        answered.append(threading.current_thread().name)
        orig(self, part, verdicts, span_id)

    monkeypatch.setattr(S.VerifyScheduler, "_answer", answer)
    backend = "cpu" if kind == "cpu-backend" else "tpu"
    s = S.VerifyScheduler(backend=backend, max_coalesce_delay_ms=_WINDOW_MS)
    try:
        bvs = [_bv(2, bad=(i,), tag=b"s%d" % i, backend="cpu")
               for i in range(2)]
        if kind == "not-coalescable":
            for bv in bvs:
                bv.coalescable = False
        hs = [s.submit(bv, tenant="t%d" % i, source="consensus")
              for i, bv in enumerate(bvs)]
        assert [h.result(timeout=LIMIT_S)[1] for h in hs] == [
            [False, True], [True, False]]
        assert set(answered) == {"verify-sched"}
        assert gate.launched == [] and gate.host == []
        deadline = time.monotonic() + LIMIT_S
        while s._inflight and time.monotonic() < deadline:
            time.sleep(0.005)
        assert s._inflight == []
    finally:
        s.close()


def test_the_spans_and_the_counter_say_which_launch_overlapped(gate,
                                                               tmp_path):
    """crypto.sched_coalesce closes behind the launch with every field
    it had, plus `inflight`; the completion side writes one
    crypto.sched_complete a batch and the requests' crypto.sched_wait as
    children of their dispatch; crypto_sched_overlap_total counts by
    `inflight`."""
    from cometbft_tpu.utils import trace

    before = _overlap_counts()
    sink = str(tmp_path / "overlap.jsonl")
    trace.configure(sink)
    s = S.VerifyScheduler(backend="tpu", max_coalesce_delay_ms=_WINDOW_MS)
    try:
        handles = _two_in_flight(s, gate)
        trace.flush()
        # both dispatch spans are written while neither verdict is in
        early = [r for r in _records(sink)
                 if r["name"] == "crypto.sched_coalesce"]
        assert [r["inflight"] for r in early] == [0, 1]
        gate.open_all()
        assert [h.result(timeout=LIMIT_S) for h in handles] == _WANT_012
    finally:
        gate.open_all()
        s.close()
        trace.flush()
        trace.disable()
    recs = _records(sink)
    coalesce = [r for r in recs if r["name"] == "crypto.sched_coalesce"]
    assert [(r["n_requests"], r["sigs"], r["inflight"], "absorb_ms" in r)
            for r in coalesce] == [(1, 3, 0, False), (2, 6, 1, True)]
    assert coalesce[1]["per_tenant_sigs"] == {"b": 2, "c": 4}
    assert coalesce[1]["sources"] == "blocksync,consensus"
    # how long the drainer held a batch back is crypto.sched_collect's
    assert all("collect_ms" not in r and r["lanes_bucket"] >= r["sigs"]
               for r in coalesce)
    done = [r for r in recs if r["name"] == "crypto.sched_complete"]
    assert [(r["batch"], r["n_requests"]) for r in done] == [
        (r["id"], r["n_requests"]) for r in coalesce]
    assert all(r["wait_ms"] >= 0.0 and r["since_launch_ms"] >= r["wait_ms"]
               for r in done)
    # the completion thread's spans are trees of their own
    assert all(r["parent"] is None for r in done)
    waits = [r for r in recs if r["name"] == "crypto.sched_wait"]
    assert sorted((w["tenant"], w["parent"], w["batch"], w["alone"])
                  for w in waits) == [
        ("a", coalesce[0]["id"], coalesce[0]["id"], True),
        ("b", coalesce[1]["id"], coalesce[1]["id"], False),
        ("c", coalesce[1]["id"], coalesce[1]["id"], False)]
    after = _overlap_counts()
    assert {k: after[k] - before.get(k, 0.0) for k in after} == {
        ("0",): 1.0, ("1",): 1.0}


# -- the drainer's account and the caller's wake (ISSUE 38) ---------------

def _traced_collects(sink):
    recs = _records(sink)
    names = {r["tid"]: r["thread"] for r in recs
             if r["name"] == "trace.thread"}
    collects = [r for r in recs if r["name"] == "crypto.sched_collect"]
    assert {names[r["tid"]] for r in collects} == {"verify-sched"}
    return recs, collects


def test_sched_collect_says_what_the_drainer_waited_for(gate, tmp_path):
    """One crypto.sched_collect a batch taken: idle_ms (nothing queued),
    slot_ms (work queued, both slots taken), linger_ms (the window) sum
    to its dur_ms. slot_ms reads over 0 only for the batch that found
    two unanswered; a request behind one batch in flight lingers its
    window."""
    from cometbft_tpu.utils import trace

    hold_s = 0.2
    sink = str(tmp_path / "collect.jsonl")
    trace.configure(sink)
    s = S.VerifyScheduler(backend="tpu", max_coalesce_delay_ms=_WINDOW_MS)
    try:
        handles = list(_two_in_flight(s, gate))
        time.sleep(hold_s)  # the drainer idles: nothing is queued
        handles.append(s.submit(_tpu(2, tag=b"late"), tenant="d",
                                source="light"))
        time.sleep(hold_s)  # ... and now waits for a slot
        assert len(gate.launched) == 2
        gate.open_all()
        assert gate.wait_launched(3)
        gate.open_all()
        assert [h.result(timeout=LIMIT_S) for h in handles] == _WANT_012 + [
            (True, [True, True])]
    finally:
        gate.open_all()
        s.close()
        trace.flush()
        trace.disable()
    recs, collects = _traced_collects(sink)
    taken = [c for c in collects if "idle_ms" in c]
    # each took what the dispatch behind it merged
    assert [r["n_requests"] for r in recs
            if r["name"] == "crypto.sched_coalesce"] == [1, 2, 1]
    for c in taken:
        assert "queued" not in c and "n_requests" not in c
        assert c["idle_ms"] + c["slot_ms"] + c["linger_ms"] == pytest.approx(
            c["dur_ms"], rel=0.05, abs=0.5)
    lone, pair, late = taken
    # an idle scheduler's lone request: no slot to wait for, no window
    assert lone["slot_ms"] == 0.0 and lone["linger_ms"] < 0.9 * _WINDOW_MS
    # behind one batch in flight: a slot is free, the window is kept
    assert pair["slot_ms"] == 0.0
    assert 0.9 * _WINDOW_MS <= pair["linger_ms"] < _WINDOW_MS + 500.0
    # behind two: idle until it arrived, then held for a slot, then gone
    # at once (its window ran out while it was held)
    assert late["idle_ms"] >= 0.9 * hold_s * 1e3
    assert late["slot_ms"] >= 0.9 * hold_s * 1e3
    assert late["linger_ms"] < 0.9 * _WINDOW_MS
    # the one the drainer was stopped in took nothing and says nothing
    assert len(collects) == 4 and "idle_ms" not in collects[-1]
    # in time, a collect lies between two dispatches of its thread
    own = sorted((r for r in recs if r["name"] in (
        "crypto.sched_collect", "crypto.sched_coalesce")),
        key=lambda r: r["t0_ns"])
    assert [r["name"].rsplit("_", 1)[1] for r in own] == [
        "collect", "coalesce"] * 3 + ["collect"]
    assert all(a["t1_ns"] <= b["t0_ns"] for a, b in zip(own, own[1:]))
    assert len({r["tid"] for r in own}) == 1


def test_wake_ms_runs_from_the_verdict_or_from_the_call(gate, tmp_path):
    """crypto.verdict_wait path=sched: wake_ms is what lies between the
    request's verdict being set and result() returning on the caller's
    thread; a verdict that was in before the call is no wake."""
    from cometbft_tpu.utils import trace

    sink = str(tmp_path / "wake.jsonl")
    trace.configure(sink)
    s = S.VerifyScheduler(backend="tpu", max_coalesce_delay_ms=_WINDOW_MS)
    try:
        h = s.submit(_tpu(2, tag=b"w0"), tenant="a", source="consensus")
        assert gate.wait_launched(1)
        got = []
        t = threading.Thread(
            target=lambda: got.append(h.result(timeout=LIMIT_S)))
        t.start()
        time.sleep(0.1)  # the caller waits; then the verdict lands
        gate.open_all()
        t.join(LIMIT_S)
        assert got == [(True, [True, True])]
        # the verdict of the second is in long before anyone asks
        h2 = s.submit(_tpu(1, tag=b"w1"), tenant="b", source="consensus")
        assert gate.wait_launched(2)
        gate.open_all()
        deadline = time.monotonic() + LIMIT_S
        while not h2._future.done() and time.monotonic() < deadline:
            time.sleep(0.005)
        time.sleep(0.1)
        assert h2.result(timeout=LIMIT_S) == (True, [True])
    finally:
        gate.open_all()
        s.close()
        trace.flush()
        trace.disable()
    waits = [r for r in _records(sink) if r["name"] == "crypto.verdict_wait"
             and r.get("path") == "sched"]
    assert len(waits) == 2
    blocked, late = waits
    assert blocked["dur_ms"] >= 90.0
    assert 0.0 <= blocked["wake_ms"] < blocked["dur_ms"] - 80.0
    assert 0.0 <= late["wake_ms"] <= late["dur_ms"] < 50.0
