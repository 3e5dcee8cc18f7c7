"""The shared scheduler as several chains of one validator set drive it
(ISSUE 33): live drainer, verify_commit inside verify_context from many
threads at once, small sizes on the CPU.

What is held: every call's answer is the one its commit would get alone (the
plain reference of benchmark/reference/commit_alone.py judges each commit
lane by lane, knowing nothing of a scheduler), every request is answered once,
a hot tenant gets its weight's share of a contended batch and one request
more, and the spans that make the path traceable say what happened.

No test here waits without a limit: callers are daemon threads joined with a
timeout, and every handle is read with one.
"""

import copy
import json
import os
import sys
import threading
import time

import pytest

from benchmark.harness.check import commit_lanes
from benchmark.reference import commit_alone
from cometbft_tpu.crypto import ed25519 as E
from cometbft_tpu.crypto import sched as S
from cometbft_tpu.types import Commit, validation
from cometbft_tpu.utils import factories as fx
from cometbft_tpu.utils import trace

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
N_VALS = 12
CHAINS = ["live-%d" % c for c in range(8)]
ROUNDS = 3
LIMIT_S = 60.0  # of any join or handle below


@pytest.fixture(scope="module")
def world():
    """One validator set, 8 chains x 3 heights of 12-signer commits; in
    some (seeded) a bad lane or two. Each entry: (chain, height, block id,
    encoded honest commit, bad lanes)."""
    import numpy as np

    signers = fx.make_signers(N_VALS, seed=33)
    vals = fx.make_validator_set(signers)
    by_addr = {s.address(): s for s in signers}
    rng = np.random.default_rng(33)
    entries = []
    for c, chain in enumerate(CHAINS):
        row = []
        for h in range(1, ROUNDS + 1):
            bid = fx.make_block_id(b"live-%d-%d" % (c, h))
            commit = fx.make_commit(chain, h, 0, bid, vals, by_addr)
            bad = ()
            if rng.random() < 0.4:
                bad = tuple(sorted(rng.choice(
                    N_VALS, size=int(rng.integers(1, 3)),
                    replace=False).tolist()))
            row.append((chain, h, bid, commit.encode(), bad))
        entries.append(row)
    assert any(e[4] for row in entries for e in row)
    assert any(not e[4] for row in entries for e in row)
    return vals, entries


def _commit(entry):
    """A fresh decode, as off the wire; the bad lanes' S flipped."""
    _, _, _, enc, bad = entry
    commit = Commit.decode(enc)
    if bad:
        commit = copy.deepcopy(commit)
        for i in bad:
            sig = bytearray(commit.signatures[i].signature)
            sig[40] ^= 0x01
            commit.signatures[i].signature = bytes(sig)
        commit.invalidate_memos()
    return commit


def _reference_answer(vals, entry):
    """verify_commit's answer for this commit ALONE, by the plain
    reference: None (accepted) or the blamed index."""
    return commit_alone.judge(commit_lanes(entry[0], vals, _commit(entry)))


def _run_callers(sched, vals, entries, stagger_s=0.0):
    """One thread a chain, each verifying its chain's commits in order
    inside verify_context; all released together. Returns
    {(chain, height): None | blamed index | "error: ..."}."""
    answers, lock = {}, threading.Lock()
    go = threading.Event()

    def caller(row):
        with S.verify_context(sched, row[0][0], "consensus"):
            go.wait(LIMIT_S)
            for entry in row:
                chain, h, bid, _, _ = entry
                commit = _commit(entry)
                try:
                    validation.verify_commit(chain, vals, bid, h, commit)
                    got = None
                except validation.ErrInvalidSignature as e:
                    got = int(str(e).rsplit(" ", 1)[1])
                except Exception as e:  # noqa: BLE001 - asserted below
                    got = f"error: {e!r}"
                with lock:
                    assert (chain, h) not in answers
                    answers[(chain, h)] = got
                if stagger_s:
                    time.sleep(stagger_s)

    threads = [threading.Thread(target=caller, args=(row,), daemon=True)
               for row in entries]
    for t in threads:
        t.start()
    go.set()
    deadline = time.monotonic() + LIMIT_S
    for t in threads:
        t.join(timeout=max(0.0, deadline - time.monotonic()))
    assert not any(t.is_alive() for t in threads), "a caller never returned"
    return answers


# -- (a) each commit judged alone, whatever batch it rode in -------------

@pytest.mark.parametrize("delay_ms", [0.0, 2.0])
@pytest.mark.parametrize("cap", [16384, 30],
                         ids=["cap-holds-a-round", "cap-splits-a-round"])
def test_every_answer_is_the_commits_own(world, delay_ms, cap):
    vals, entries = world
    want = {(e[0], e[1]): _reference_answer(vals, e)
            for row in entries for e in row}
    assert {v for v in want.values() if v is not None}, "no bad commit"
    sched = S.VerifyScheduler(backend="tpu", max_coalesce_delay_ms=delay_ms,
                              max_coalesce_sigs=cap)
    try:
        got = _run_callers(sched, vals, entries)
        assert got == want
        st = dict(sched.stats)
        assert st["requests"] == len(want) == len(CHAINS) * ROUNDS
        assert st["passthrough"] + st["coalesced_requests"] == st["requests"]
    finally:
        sched.close()


# -- (b) every request answered once; the scheduler's books agree --------

@pytest.mark.parametrize("switch_interval", [None, 1e-5],
                         ids=["default-switching", "switch-every-10us"])
def test_tenant_stats_match_what_each_thread_sent(world, monkeypatch,
                                                  switch_interval):
    vals, entries = world
    # keyed by the future itself: it stays alive, so no id comes twice
    resolved, lock = {}, threading.Lock()
    resolve, fail = S._resolve, S._fail

    def counting(orig):
        def f(fut, value):
            if not fut.done():
                with lock:
                    resolved[fut] = resolved.get(fut, 0) + 1
            orig(fut, value)
        return f

    monkeypatch.setattr(S, "_resolve", counting(resolve))
    monkeypatch.setattr(S, "_fail", counting(fail))
    sched = S.VerifyScheduler(backend="tpu", max_coalesce_delay_ms=1.0,
                              max_coalesce_sigs=40)
    old = sys.getswitchinterval()
    if switch_interval:
        sys.setswitchinterval(switch_interval)
    try:
        got = _run_callers(sched, vals, entries)
    finally:
        sys.setswitchinterval(old)
        sched.close()
    assert len(got) == len(CHAINS) * ROUNDS
    assert not [v for v in got.values() if isinstance(v, str)]
    assert sched.tenant_stats() == {c: ROUNDS * N_VALS for c in CHAINS}
    assert sched.stats["sigs"] == len(CHAINS) * ROUNDS * N_VALS
    # one answer a request, none twice, none dropped
    assert len(resolved) == len(got) and set(resolved.values()) == {1}


def test_a_dispatch_that_raises_answers_every_request_with_an_error():
    """An engine that dies fails each request of its batch once, with the
    tenant named; a later batch is served."""
    class Dies(E.Ed25519BatchVerifier):
        def absorb(self, other):
            raise OSError("engine down")

    sched = S.VerifyScheduler(backend="cpu", manual=True)
    priv = E.Ed25519PrivKey.generate()

    def filled(msg):
        bv = E.Ed25519BatchVerifier(backend="cpu")
        bv.add(priv.pub_key(), msg, priv.sign(msg))
        return bv

    orig = E.Ed25519BatchVerifier
    E.Ed25519BatchVerifier = Dies
    try:
        hs = [sched.submit(filled(b"m%d" % i), tenant="t%d" % i,
                           source="consensus") for i in range(3)]
        assert sched.drain_once() == 3
    finally:
        E.Ed25519BatchVerifier = orig
    for i, h in enumerate(hs):
        with pytest.raises(RuntimeError, match=f"'t{i}'.*engine down"):
            h.result(timeout=LIMIT_S)
    h = sched.submit(filled(b"after"), tenant="t0", source="consensus")
    sched.submit(filled(b"after2"), tenant="t1", source="consensus")
    assert sched.drain_once() == 2
    assert h.result(timeout=LIMIT_S) == (True, [True])


def test_a_handle_nobody_serves_times_out_instead_of_hanging():
    import concurrent.futures

    sched = S.VerifyScheduler(backend="cpu", manual=True)
    priv = E.Ed25519PrivKey.generate()
    bv = E.Ed25519BatchVerifier(backend="cpu")
    bv.add(priv.pub_key(), b"m", priv.sign(b"m"))
    h = sched.submit(bv, tenant="t", source="consensus")
    t0 = time.monotonic()
    with pytest.raises(concurrent.futures.TimeoutError):
        h.result(timeout=0.05)
    assert time.monotonic() - t0 < 5.0
    sched.close()  # fails what is queued, with the tenant named
    with pytest.raises(RuntimeError, match="abandoned"):
        h.result(timeout=LIMIT_S)


def test_certificates_ride_alone_beside_a_merged_batch():
    """A verifier that cannot be merged is dispatched by itself in the same
    drain cycle; the others still share one batch."""
    class Cert:
        coalescable = False

        def count(self):
            return 7

        def verify(self):
            return True, [True]

    sched = S.VerifyScheduler(backend="cpu", manual=True)
    priv = E.Ed25519PrivKey.generate()
    hs = []
    for i in range(3):
        bv = E.Ed25519BatchVerifier(backend="cpu")
        bad = priv.sign(b"x") if i == 1 else priv.sign(b"m%d" % i)
        bv.add(priv.pub_key(), b"m%d" % i, bad)
        hs.append(sched.submit(bv, tenant="ed%d" % i, source="consensus"))
    hc = sched.submit(Cert(), tenant="cert", source="consensus")
    assert sched.drain_once() == 4
    assert hc.result(timeout=LIMIT_S) == (True, [True])
    assert [h.result(timeout=LIMIT_S)[0] for h in hs] == [True, False, True]
    st = sched.stats
    assert (st["dispatches"], st["passthrough"],
            st["coalesced_requests"]) == (2, 1, 3)


# -- (c) fairness under a hot tenant -------------------------------------

@pytest.mark.parametrize("hot_weight", [1.0, 2.0])
def test_a_hot_tenant_gets_its_share_and_one_request_more(tmp_path,
                                                          hot_weight):
    """One tenant submits 10 x what each of three others does. In every
    batch that left each of the others with work still queued (contended
    from its first lane to its last), its lanes are at most weight / total
    weight of the batch, plus one request."""
    cap, req_sigs, others = 32, 4, ["a", "b", "c"]
    sched = S.VerifyScheduler(backend="cpu", manual=True,
                              max_coalesce_sigs=cap, quantum_sigs=req_sigs)
    sched.set_tenant_weight("hot", hot_weight)
    priv = E.Ed25519PrivKey.generate()
    sig = priv.sign(b"drr")

    def filled():
        bv = E.Ed25519BatchVerifier(backend="cpu")
        for _ in range(req_sigs):
            bv.add(priv.pub_key(), b"drr", sig)
        return bv

    handles = [sched.submit(filled(), tenant="hot", source="consensus")
               for _ in range(60)]
    for t in others:
        handles += [sched.submit(filled(), tenant=t, source="consensus")
                    for _ in range(6)]
    sink = str(tmp_path / "drr.jsonl")
    trace.configure(sink)
    try:
        while sched.drain_once():
            pass
        trace.flush()
    finally:
        trace.disable()
    assert all(h.result(timeout=LIMIT_S)[0] for h in handles)
    batches = [r["per_tenant_sigs"] for r in _records(sink)
               if r["name"] == "crypto.sched_coalesce"]
    share = hot_weight / (hot_weight + len(others)) * cap + req_sigs
    left = {t: 6 * req_sigs for t in others}
    contended = 0
    for per in batches:
        for t in others:
            left[t] -= per.get(t, 0)
        if all(left[t] > 0 for t in others):
            contended += 1
            assert per.get("hot", 0) <= share, (per, share)
            assert all(per.get(t, 0) > 0 for t in others), per
    assert contended >= 2 and not any(left.values())
    assert sched.tenant_stats()["hot"] == 60 * req_sigs


# -- (d) the spans that make the path traceable --------------------------

def _records(path):
    with open(path, encoding="utf-8") as f:
        return [json.loads(line) for line in f]


@pytest.fixture
def traced(world, tmp_path):
    """The records of the 8 callers x 3 rounds through a live scheduler."""
    vals, entries = world
    sink = str(tmp_path / "spans.jsonl")
    trace.configure(sink)
    sched = S.VerifyScheduler(backend="tpu", max_coalesce_delay_ms=2.0)
    try:
        # the pause lets some round's first arrival find the drainer idle
        got = _run_callers(sched, vals, entries, stagger_s=0.02)
        trace.flush()
    finally:
        sched.close()
        trace.disable()
    assert len(got) == len(CHAINS) * ROUNDS
    return _records(sink)


def test_one_sched_wait_a_request_names_its_dispatch(traced):
    waits = [r for r in traced if r["name"] == "crypto.sched_wait"]
    batches = {r["id"]: r for r in traced
               if r["name"] == "crypto.sched_coalesce"}
    assert len(waits) == len(CHAINS) * ROUNDS
    rode: dict = {}
    for w in waits:
        b = batches[w["batch"]]  # a dispatch that exists
        assert w["parent"] == b["id"] and w["kind"] == "span"
        assert w["alone"] is (b["n_requests"] == 1)
        assert w["tenant"] in b["per_tenant_sigs"] and w["n"] == N_VALS
        assert w["source"] == "consensus"
        assert 0.0 <= w["queued_ms"] <= w["dur_ms"]
        rode[w["batch"]] = rode.get(w["batch"], 0) + 1
    assert rode == {i: b["n_requests"] for i, b in batches.items()}
    assert sorted(w["tenant"] for w in waits) == sorted(CHAINS * ROUNDS)


def test_the_dispatch_span_carries_the_merge_and_the_linger(traced):
    batches = [r for r in traced if r["name"] == "crypto.sched_coalesce"]
    assert any(b["n_requests"] > 1 for b in batches)
    for b in batches:
        assert b["sigs"] == b["n_requests"] * N_VALS
        assert b["sigs"] == sum(b["per_tenant_sigs"].values())
        assert b["lanes_bucket"] == E._bucket(b["sigs"])
        assert "collect_ms" not in b and b["dur_ms"] >= b["self_ms"] >= 0.0
        # the merge loop exists only where something was merged
        assert ("absorb_ms" in b) is (b["n_requests"] > 1)
    # the dispatch's own batch_verify is its child: one tree a dispatch
    kids = [r for r in traced if r["name"] == "crypto.batch_verify"]
    ids = {b["id"] for b in batches}
    assert kids and all(k["parent"] in ids for k in kids)


def test_the_callers_wait_is_a_verdict_wait_in_its_own_tree(traced):
    roots = {r["id"] for r in traced if r["name"] == "types.verify_commit"}
    batches = {r["id"] for r in traced
               if r["name"] == "crypto.sched_coalesce"}
    waits = [r for r in traced if r["name"] == "crypto.verdict_wait"
             and r.get("path") == "sched"]
    assert len(waits) == len(CHAINS) * ROUNDS
    for w in waits:
        assert w["parent"] in roots and w["root"] == w["parent"]
        assert w["batch"] in batches and w["n"] == N_VALS
        # verdict set on the answering thread -> this thread running again
        assert 0.0 <= w["wake_ms"] <= w["dur_ms"]


def test_tracing_off_nothing_of_it_runs(world):
    vals, entries = world
    assert not trace.enabled
    seen = []
    orig = S.VerifyScheduler._answer, S.VerifyScheduler._launch

    def launch(self, flight, bv, batch, ranges, sp, absorb_s):
        assert sp.id is None and not absorb_s
        orig[1](self, flight, bv, batch, ranges, sp, absorb_s)

    def answer(self, batch, verdicts, span_id):
        seen.extend(batch)
        assert span_id is None
        orig[0](self, batch, verdicts, span_id)

    S.VerifyScheduler._answer, S.VerifyScheduler._launch = answer, launch
    sched = S.VerifyScheduler(backend="tpu")
    try:
        got = _run_callers(sched, vals, entries)
    finally:
        S.VerifyScheduler._answer, S.VerifyScheduler._launch = orig
        sched.close()
    assert len(got) == len(seen) == len(CHAINS) * ROUNDS
    assert all(r.batch is None and r.t_taken == r.t_enqueue
               and not hasattr(r, "t_done") for r in seen)


# -- (e) the deployment states the program's defaults --------------------

def test_the_ics_configuration_states_schedconfigs_defaults():
    from cometbft_tpu.config import SchedConfig

    with open(os.path.join(ROOT, "benchmark", "configs",
                           "ics-150v.json")) as f:
        cfg = json.load(f)
    stated = cfg["shapes"]["scheduler"]
    d = SchedConfig()
    assert d.enabled is True
    assert stated["max_coalesce_sigs"] == d.max_coalesce_sigs == 16384
    assert stated["max_coalesce_delay_ms"] == d.max_coalesce_delay_ms == 2.0
    assert stated["tenant_weight"] == d.tenant_weight == 1.0
    # what a bare VerifyScheduler runs with is the same policy
    s = S.VerifyScheduler(manual=True)
    assert s.max_coalesce_sigs == d.max_coalesce_sigs
    assert s.max_coalesce_delay_s * 1e3 == d.max_coalesce_delay_ms
    ids = cfg["shapes"]["chain_ids"]
    assert len(ids) == len(set(ids)) == cfg["shapes"]["chains"] == 16
    assert cfg["reduced"] == {}


# -- (f) the drainer's account (ISSUE 38) ---------------------------------

def test_the_drainer_writes_one_collect_a_batch_that_sums_to_its_time(traced):
    """Between two dispatches the live drainer is inside ONE
    crypto.sched_collect, on its own thread, whose three waits sum to
    its duration; with the dispatches they cover its time."""
    names = {r["tid"]: r["thread"] for r in traced
             if r["name"] == "trace.thread"}
    collects = [r for r in traced if r["name"] == "crypto.sched_collect"]
    batches = [r for r in traced if r["name"] == "crypto.sched_coalesce"]
    taken = [c for c in collects if "idle_ms" in c]
    assert {names[c["tid"]] for c in collects} == {"verify-sched"}
    assert len(taken) == len(batches) and len(collects) == len(taken) + 1
    assert sum(b["n_requests"] for b in batches) == len(CHAINS) * ROUNDS
    for c in taken:
        assert c["parent"] is None and c["cpu_ms"] <= c["dur_ms"]
        assert min(c["idle_ms"], c["slot_ms"], c["linger_ms"]) >= 0.0
        assert c["idle_ms"] + c["slot_ms"] + c["linger_ms"] <= (
            c["dur_ms"] + 0.01)
    # ... and over the run they sum to it (one collect may end a
    # thread switch behind its last reading: 16 callers share the
    # interpreter, so the account is held to the sums, as the cell's is)
    assert sum(c["idle_ms"] + c["slot_ms"] + c["linger_ms"]
               for c in taken) == pytest.approx(
        sum(c["dur_ms"] for c in taken), rel=0.05)
    # the drainer's window: nothing of it lies outside the two spans but
    # the steps between them
    own = sorted((r for r in collects + batches),
                 key=lambda r: r["t0_ns"])
    window = own[-1]["t1_ns"] - own[0]["t0_ns"]
    inside = sum(r["t1_ns"] - r["t0_ns"] for r in own)
    assert inside >= 0.95 * window
