"""JSONL span/event tracer (utils/trace.py): schema, sink lifecycle,
env-var auto-configure, and the disabled-path overhead budget."""

import json
import os
import subprocess
import sys
import time

import pytest

from cometbft_tpu.utils import trace


def _cleanup():
    trace.disable()


def _read(sink):
    """The sink's records less the tracer's own (configure() writes
    trace.clock, a thread's first record follows its trace.thread, a
    collection may add runtime.gc_pause anywhere)."""
    with open(sink, encoding="utf-8") as f:
        recs = [json.loads(line) for line in f]
    return [r for r in recs if r["name"] not in (
        "trace.clock", "trace.thread", "runtime.gc_pause")]


def test_tracer_disabled_is_noop_and_cheap():
    _cleanup()
    assert trace.enabled is False
    # no sink: emit/event must be pure no-ops
    trace.emit("x", foo=1)
    trace.event("y")
    assert trace.tail() == []
    # span() hands back one shared no-op object, no allocation per call
    s1 = trace.span("a", h=1)
    s2 = trace.span("b")
    assert s1 is s2
    with trace.span("c") as s:
        s.add(k=2)
    # overhead budget: a guarded hot path pays one global load; even the
    # UNguarded form (span + enter/exit) must stay in the ~1 us/op
    # class. 50k iterations with a generous single-core CI bound.
    n = 50_000
    t0 = time.perf_counter()
    for _ in range(n):
        if trace.enabled:
            trace.emit("hot", a=1)
    guarded = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(n):
        with trace.span("hot"):
            pass
    unguarded = time.perf_counter() - t0
    assert guarded / n < 5e-6, f"guarded no-op too slow: {guarded / n}s/op"
    assert unguarded / n < 20e-6, f"noop span too slow: {unguarded / n}s/op"


def test_tracer_jsonl_schema_and_tail(tmp_path):
    sink = os.path.join(str(tmp_path), "t", "trace.jsonl")
    trace.configure(sink)
    try:
        assert trace.enabled and trace.path() == sink
        trace.event("consensus.step", height=4, round=0, step="PROPOSE")
        with trace.span("state.apply_block", height=4, txs=7) as s:
            s.add(validate_ms=0.1)
        trace.flush()  # writes are buffered with bounded staleness
        records = _read(sink)
        assert len(records) == 2
        for rec in records:
            # every record carries the merge-safe envelope
            assert {"ts", "pid", "name", "kind"} <= rec.keys()
            assert rec["pid"] == os.getpid()
        ev, sp = records
        assert ev["kind"] == "event" and ev["height"] == 4
        assert sp["kind"] == "span" and sp["name"] == "state.apply_block"
        assert sp["dur_ms"] >= 0 and sp["validate_ms"] == 0.1
        # tail() (the dump_trace RPC backend) parses the same records
        assert [r["name"] for r in trace.tail(10)
                if r["name"] != "runtime.gc_pause"] == [
            "trace.thread", "trace.clock", "consensus.step",
            "state.apply_block",
        ]
        assert trace.tail(1)[0]["name"] == "state.apply_block"
    finally:
        _cleanup()
    # after disable, the sink is closed and writes are dropped
    assert trace.enabled is False
    trace.emit("late")
    assert len(_read(sink)) == 2


def test_tail_window_grows_past_initial_seek(tmp_path):
    """tail(n) starts from a 256 KiB seek-back; when `n` lines do not
    fit it must widen the window instead of silently shorting the RPC
    (the old fixed window capped tail() at whatever fit in 256 KiB)."""
    sink = os.path.join(str(tmp_path), "big.jsonl")
    trace.configure(sink)
    try:
        pad = "x" * 220  # ~260 B/record -> 3000 records ≈ 780 KiB
        for i in range(3000):
            trace.event("grow", i=i, pad=pad)
        trace.flush()  # records wait in memory until a flush
        assert os.path.getsize(sink) > 256 * 1024
        got = [r for r in trace.tail(2600) if r["name"] == "grow"][-2500:]
        assert len(got) == 2500
        assert got[0]["i"] == 500 and got[-1]["i"] == 2999
        # n beyond the file returns every record, first line included
        everything = trace.tail(100_000)
        assert [r["name"] for r in everything[:2]] == [
            "trace.thread", "trace.clock"]
        grown = [r for r in everything if r["name"] == "grow"]
        assert len(grown) == 3000 and grown[0]["i"] == 0
    finally:
        _cleanup()


def test_fork_child_stamps_own_pid(tmp_path):
    """A process forked after configure() must stamp its own pid (and
    not scribble through the parent's buffered file object)."""
    import multiprocessing

    try:
        ctx = multiprocessing.get_context("fork")
    except ValueError:
        import pytest

        pytest.skip("platform has no fork start method")
    sink = os.path.join(str(tmp_path), "fork.jsonl")
    trace.configure(sink)
    try:
        trace.event("parent.mark")
        proc = ctx.Process(target=trace.event, args=("child.mark",))
        proc.start()
        proc.join(30)
        assert proc.exitcode == 0
        trace.flush()  # the child flushed at exit; flush our own buffer
        recs = [json.loads(line) for line in open(sink, encoding="utf-8")]
        by_name = {r["name"]: r for r in recs}
        assert by_name["parent.mark"]["pid"] == os.getpid()
        assert by_name["child.mark"]["pid"] != os.getpid()
    finally:
        _cleanup()


def test_set_node_first_caller_wins(tmp_path):
    sink = os.path.join(str(tmp_path), "node.jsonl")
    trace.configure(sink)
    try:
        trace.event("before")
        trace.set_node("aabb" * 10)
        trace.set_node("ffff" * 10)  # in-process second node: ignored
        assert trace.node_id() == "aabb" * 10
        trace.event("after")
        trace.flush()
        recs = _read(sink)
        assert "node" not in recs[0]
        assert recs[1]["node"] == "aabb" * 10
    finally:
        _cleanup()
    assert trace.node_id() == ""  # disable() clears the identity


def test_tracer_env_var_configures_subprocess(tmp_path):
    """COMETBFT_TPU_TRACE reaches processes with no config plumbing
    (subprocess e2e nodes, bench.py)."""
    sink = os.path.join(str(tmp_path), "env_trace.jsonl")
    env = dict(os.environ)
    env["COMETBFT_TPU_TRACE"] = sink
    code = (
        "from cometbft_tpu.utils import trace; "
        "assert trace.enabled; trace.event('boot', ok=1)"
    )
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    p = subprocess.run(
        [sys.executable, "-c", code], env=env, cwd=repo,
        capture_output=True, text=True, timeout=60,
    )
    assert p.returncode == 0, p.stderr[-2000:]
    recs = _read(sink)  # written at exit: nothing flushed them before
    assert recs and recs[0]["name"] == "boot" and recs[0]["ok"] == 1


# ----------------------------------------------------------------------
# ISSUE 24: the span tree (id / parent / root / t0_ns / t1_ns / self_ms),
# records held in memory, the collector's pauses, the profiler's clock
# ----------------------------------------------------------------------
def _spans(sink):
    return {r["name"]: r for r in _read(sink) if r["kind"] == "span"}


def test_nested_spans_form_one_tree_with_self_time(tmp_path):
    sink = os.path.join(str(tmp_path), "tree.jsonl")
    trace.configure(sink)
    try:
        with trace.span("root", height=7) as root:
            with trace.span("a") as a:
                time.sleep(0.02)
                trace.event("mark", k=1)
                with trace.span("a.inner"):
                    time.sleep(0.01)
            with trace.span("b"):
                time.sleep(0.01)
            root.add(n=3)
        trace.flush()
        sp = _spans(sink)
        ids = [sp[n]["id"] for n in ("root", "a", "a.inner", "b")]
        assert len(set(ids)) == 4 and a.id == sp["a"]["id"]
        assert sp["root"]["parent"] is None
        assert sp["a"]["parent"] == sp["b"]["parent"] == sp["root"]["id"]
        assert sp["a.inner"]["parent"] == sp["a"]["id"]
        assert {r["root"] for r in sp.values()} == {sp["root"]["id"]}
        for r in sp.values():
            assert r["t0_ns"] <= r["t1_ns"]
            assert abs((r["t1_ns"] - r["t0_ns"]) / 1e6 - r["dur_ms"]) < 1e-3
            assert {"ts", "pid", "name", "kind", "dur_ms"} <= r.keys()
        # children lie inside their parent, on one clock
        assert sp["root"]["t0_ns"] <= sp["a"]["t0_ns"]
        assert sp["a"]["t1_ns"] <= sp["b"]["t0_ns"] <= sp["root"]["t1_ns"]
        # self time = duration less what the DIRECT children covered
        assert sp["root"]["self_ms"] == pytest.approx(
            sp["root"]["dur_ms"] - sp["a"]["dur_ms"] - sp["b"]["dur_ms"],
            abs=0.01)
        assert sp["a"]["self_ms"] == pytest.approx(
            sp["a"]["dur_ms"] - sp["a.inner"]["dur_ms"], abs=0.01)
        assert sp["a"]["self_ms"] >= 19 and sp["b"]["self_ms"] >= 9
        assert sp["root"]["self_ms"] < 5
        assert sp["root"]["height"] == 7 and sp["root"]["n"] == 3
        # a bare record inside a span knows the span that caused it
        mark = next(r for r in _read(sink) if r["name"] == "mark")
        assert mark["parent"] == sp["a"]["id"]
        assert mark["root"] == sp["root"]["id"]
        # the clock pair that puts t0_ns on the wall clock
        clock = next(r for r in trace.tail(1000)
                     if r["name"] == "trace.clock")
        wall = clock["time_ns"] + sp["root"]["t1_ns"] - clock["perf_ns"]
        assert abs(wall / 1e9 - sp["root"]["ts"]) < 0.05
    finally:
        _cleanup()


def test_spans_of_two_threads_keep_their_own_trees(tmp_path):
    import threading

    sink = os.path.join(str(tmp_path), "threads.jsonl")
    trace.configure(sink)
    try:
        go = threading.Barrier(2, timeout=30)

        def work(tag):
            with trace.span("root", tag=tag):
                go.wait()  # both roots open at once
                for _ in range(50):
                    with trace.span("leaf", tag=tag):
                        pass

        threads = [threading.Thread(target=work, args=(t,)) for t in "xy"]
        for t in threads:
            t.start()
        for t in threads:
            t.join(30)
            assert not t.is_alive()
        trace.flush()
        recs = [r for r in _read(sink) if r["kind"] == "span"]
        roots = {r["tag"]: r for r in recs if r["name"] == "root"}
        leaves = [r for r in recs if r["name"] == "leaf"]
        assert len(roots) == 2 and len(leaves) == 100
        assert len({r["id"] for r in recs}) == 102
        for leaf in leaves:  # never adopted by the other thread's root
            assert leaf["parent"] == leaf["root"] == roots[leaf["tag"]]["id"]
        for r in roots.values():
            assert r["parent"] is None and r["root"] == r["id"]
    finally:
        _cleanup()


def test_records_wait_in_memory_until_a_flush(tmp_path, monkeypatch):
    sink = os.path.join(str(tmp_path), "held.jsonl")
    trace.configure(sink)
    try:
        trace.flush()
        size0 = os.path.getsize(sink)
        with trace.span("root"):
            for i in range(200):
                with trace.span("leaf", i=i):
                    pass
            # nothing was serialised inside the span that encloses them
            assert os.path.getsize(sink) == size0
            # ... but tail() (the dump_trace RPC) sees them
            assert [r["i"] for r in trace.tail(5)] == [195, 196, 197, 198, 199]
            assert os.path.getsize(sink) > size0
            # bounded: past MAX_BUFFERED waiting records, one that
            # closes inside a span writes them out
            monkeypatch.setattr(trace, "MAX_BUFFERED", 10)
            monkeypatch.setattr(trace, "FLUSH_INTERVAL_S", 1e-6)
            monkeypatch.setattr(trace, "NESTED_FLUSH_INTERVALS", 1e12)
            size1 = os.path.getsize(sink)
            for i in range(9):
                with trace.span("leaf", i=i):
                    pass
            assert os.path.getsize(sink) == size1
            with trace.span("leaf", i=9):
                pass
            assert os.path.getsize(sink) > size1
        # a record that closes outside any span flushes on the interval
        monkeypatch.setattr(trace, "MAX_BUFFERED", 1 << 20)
        size2 = os.path.getsize(sink)
        trace.event("bare")
        assert os.path.getsize(sink) > size2
        assert len([r for r in _read(sink) if r["name"] == "leaf"]) == 210
    finally:
        _cleanup()


def test_gc_pause_is_a_child_of_the_span_it_interrupted(tmp_path):
    import gc

    sink = os.path.join(str(tmp_path), "gc.jsonl")
    before = len(gc.callbacks)
    trace.configure(sink)
    try:
        assert len(gc.callbacks) == before + 1
        with trace.span("root"):
            with trace.span("busy"):
                gc.collect()  # a full collection
        trace.flush()
        with open(sink, encoding="utf-8") as f:
            recs = [json.loads(line) for line in f]
        sp = {r["name"]: r for r in recs}
        pause = next(r for r in recs if r["name"] == "runtime.gc_pause"
                     and r["generation"] == 2)
        assert pause["parent"] == sp["busy"]["id"]
        assert pause["root"] == sp["root"]["id"]
        assert "collected" in pause and pause["t0_ns"] <= pause["t1_ns"]
        # the interrupted span's self time leaves the pause out
        assert sp["busy"]["self_ms"] == pytest.approx(
            sp["busy"]["dur_ms"] - pause["dur_ms"], abs=0.01)
        # a young collection that pauses under 1 ms leaves no record
        assert all(r["generation"] == 2 or r["dur_ms"] >= 1.0
                   for r in recs if r["name"] == "runtime.gc_pause")
    finally:
        _cleanup()
    assert len(gc.callbacks) == before  # disable() removes the hook


def test_a_span_under_the_profiler_is_in_the_xplane_with_its_id(tmp_path):
    """While tracing is on and jax is imported, a span also enters
    jax.profiler.TraceAnnotation(name, span_id=id): under a profiler
    session it lies on the profiler's clock, joined to the sink by id."""
    import glob

    import jax
    import jax.numpy as jnp

    from cometbft_tpu.utils import xplane

    sink = os.path.join(str(tmp_path), "prof.jsonl")
    prof = os.path.join(str(tmp_path), "prof")
    trace.configure(sink)
    try:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(prof, profiler_options=opts)
        try:
            with trace.span("types.verify_commit", height=3) as outer:
                with trace.span("crypto.batch_verify", n=2) as inner:
                    jnp.arange(8).sum().block_until_ready()
        finally:
            jax.profiler.stop_trace()
        trace.flush()
    finally:
        _cleanup()
    found = glob.glob(os.path.join(prof, "**", "*.xplane.pb"),
                      recursive=True)
    assert found
    xp = xplane.load(found[-1])
    assert xp["start_ns"] is not None
    spans = {sp["span_id"]: sp for p in xp["planes"]
             for sp in p.get("spans", [])}
    assert spans[outer.id]["name"] == "types.verify_commit"
    assert spans[inner.id]["name"] == "crypto.batch_verify"
    o, i = spans[outer.id], spans[inner.id]
    assert o["start_ns"] <= i["start_ns"]
    assert i["start_ns"] + i["dur_ns"] <= o["start_ns"] + o["dur_ns"]
    # the same two spans, by id, in the sink
    by_id = {r["id"]: r for r in _read(sink) if r["kind"] == "span"}
    assert by_id[inner.id]["parent"] == outer.id
    # and on one clock: the sink's t0_ns, moved to the wall clock by
    # trace.clock, lands on the annotation's start
    with open(sink, encoding="utf-8") as f:
        clock = next(r for r in map(json.loads, f)
                     if r["name"] == "trace.clock")
    wall = clock["time_ns"] + by_id[outer.id]["t0_ns"] - clock["perf_ns"]
    assert abs(wall - (xp["start_ns"] + o["start_ns"])) < 5e6  # 5 ms


# ----------------------------------------------------------------------
# ISSUE 38: threads in the span tree (tid, trace.thread, a root's
# cpu_ms) and a flush that holds no other thread
# ----------------------------------------------------------------------
def _all(sink):
    with open(sink, encoding="utf-8") as f:
        return [json.loads(line) for line in f]


def _spin(seconds):
    """Keeps this thread at the interpreter until it has been on a CPU
    that long (by its own CPU clock: a busy box only stretches it)."""
    end = time.thread_time() + seconds
    n = 0
    while time.thread_time() < end:
        n += 1
    return n


def test_every_record_names_its_thread_and_a_thread_is_named_once(tmp_path):
    import threading

    sink = os.path.join(str(tmp_path), "tid.jsonl")
    trace.configure(sink)
    try:
        def work():
            with trace.span("root"):
                trace.event("mark")
                sp = trace.open_span("leg")
                sp.close()

        t = threading.Thread(target=work, name="worker-7")
        t.start()
        t.join(30)
        work()
        trace.flush()
        recs = _all(sink)
    finally:
        _cleanup()
    assert all(isinstance(r["tid"], int) for r in recs)
    named = [r for r in recs if r["name"] == "trace.thread"]
    assert sorted(r["thread"] for r in named) == ["MainThread", "worker-7"]
    tids = {r["thread"]: r["tid"] for r in named}
    assert tids["MainThread"] == threading.get_native_id() != tids["worker-7"]
    # a thread's name lies ahead of every other record of that thread
    first = {}
    for i, r in enumerate(recs):
        first.setdefault(r["tid"], (i, r["name"]))
    assert {name for _i, name in first.values()} == {"trace.thread"}
    for tid in tids.values():
        assert sorted(r["name"] for r in recs if r["tid"] == tid
                      and r["name"] in ("root", "mark", "leg")) == [
            "leg", "mark", "root"]
    # a new sink gets the names anew
    sink2 = os.path.join(str(tmp_path), "tid2.jsonl")
    trace.configure(sink2)
    try:
        trace.event("again")
        trace.flush()
        assert [r["name"] for r in _all(sink2)] == [
            "trace.thread", "trace.clock", "again"]
    finally:
        _cleanup()


@pytest.mark.parametrize("kind", ["sleeps", "spins"])
def test_cpu_ms_is_what_the_thread_ran(tmp_path, kind):
    """cpu_ms never passes dur_ms; a span that sleeps 20 ms was on a CPU
    for next to none of it, one that spins 20 ms of CPU reads them (and
    on an idle box little more as its duration)."""
    sink = os.path.join(str(tmp_path), f"cpu-{kind}.jsonl")
    trace.configure(sink)
    try:
        with trace.span("work"):
            if kind == "sleeps":
                time.sleep(0.02)
            else:
                _spin(0.02)
        leg = trace.open_span("leg")
        leg.close()
        trace.flush()
        recs = _all(sink)
    finally:
        _cleanup()
    work = next(r for r in recs if r["name"] == "work")
    assert 0.0 <= work["cpu_ms"] <= work["dur_ms"]
    assert work["dur_ms"] >= 19.0
    if kind == "sleeps":
        assert work["cpu_ms"] < 5.0
    else:
        assert 19.0 <= work["cpu_ms"] <= work["dur_ms"]
    # a span that may end on another thread has no CPU time to give
    leg = next(r for r in recs if r["name"] == "leg")
    assert "cpu_ms" not in leg and leg["parent"] is None and "tid" in leg


def test_only_a_root_reads_the_cpu_clock(tmp_path, monkeypatch):
    """The thread's CPU clock costs microseconds a reading under a
    sandboxed kernel, so a span pays for it only at a root: two
    readings a tree, whatever lies under it; a root's cpu_ms holds its
    children's time, a collector's pause included."""
    import gc

    reads = []
    clock = time.thread_time_ns

    def counted():
        reads.append(1)
        return clock()

    sink = os.path.join(str(tmp_path), "roots.jsonl")
    trace.configure(sink)
    try:
        gc.disable()  # no pause but the one asked for
        monkeypatch.setattr(trace.time, "thread_time_ns", counted)
        with trace.span("parent"):
            with trace.span("child"):
                _spin(0.03)
            time.sleep(0.01)
            with trace.span("interrupted"):
                gc.collect()
        monkeypatch.setattr(trace.time, "thread_time_ns", clock)
        trace.flush()
        recs = _all(sink)
    finally:
        gc.enable()
        _cleanup()
    assert len(reads) == 2
    sp = {r["name"]: r for r in recs if r["kind"] == "span"}
    assert set(sp) == {"parent", "child", "interrupted", "runtime.gc_pause"}
    assert [n for n, r in sp.items() if "cpu_ms" in r] == ["parent"]
    assert sp["runtime.gc_pause"]["parent"] == sp["interrupted"]["id"]
    assert 29.0 <= sp["parent"]["cpu_ms"] <= sp["parent"]["dur_ms"] - 9.0
    # self time is the wall clock's, as it was
    assert sp["parent"]["self_ms"] == pytest.approx(
        sp["parent"]["dur_ms"] - sp["child"]["dur_ms"]
        - sp["interrupted"]["dur_ms"], abs=0.01)


def test_a_pause_outside_every_span_is_a_root_with_cpu_ms(tmp_path):
    import gc

    sink = os.path.join(str(tmp_path), "pause.jsonl")
    trace.configure(sink)
    try:
        gc.collect()
        trace.flush()
        recs = _all(sink)
    finally:
        _cleanup()
    pause = next(r for r in recs if r["name"] == "runtime.gc_pause")
    assert pause["parent"] is None
    assert 0.0 <= pause["cpu_ms"] <= pause["dur_ms"]


def test_two_threads_that_spin_under_one_lock_each_ran_half_their_wall(
        tmp_path):
    """What the thread did not run is not in cpu_ms: two threads that
    take turns (a lock here, the interpreter in the scheduler's cell)
    each read about half their wall time as on-CPU."""
    import threading

    sink = os.path.join(str(tmp_path), "turns.jsonl")
    trace.configure(sink)
    try:
        lock = threading.Condition()
        turn = [0]
        go = threading.Barrier(2, timeout=30)

        def work(me):
            go.wait()
            with trace.span("turns", tag=me):
                for _ in range(20):
                    with lock:
                        assert lock.wait_for(lambda: turn[0] == me, 30)
                        _spin(0.005)
                        turn[0] = 1 - me
                        lock.notify_all()

        threads = [threading.Thread(target=work, args=(t,)) for t in (0, 1)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
            assert not t.is_alive()
        trace.flush()
        recs = [r for r in _all(sink) if r["name"] == "turns"]
    finally:
        _cleanup()
    assert len(recs) == 2 and recs[0]["tid"] != recs[1]["tid"]
    for r in recs:
        # 20 turns of 5 ms of CPU each, the other thread's 19 or 20
        # between them: it ran its own half and no more
        assert r["dur_ms"] >= 190.0
        assert 99.0 <= r["cpu_ms"] <= 0.65 * r["dur_ms"], r


def test_a_record_from_another_thread_does_not_wait_for_a_slow_flush(
        tmp_path, monkeypatch):
    """A flush takes the waiting records under the buffer's lock and
    serialises them outside it: while one thread is inside a slow
    json.dumps, another closes spans without waiting, its records go
    out with the next flush, and flush() itself waits its turn."""
    import threading

    sink = os.path.join(str(tmp_path), "slow.jsonl")
    trace.configure(sink)
    try:
        serialising = threading.Event()
        release = threading.Event()
        orig = trace._serialise

        def slow(batch):
            serialising.set()
            assert release.wait(30)
            return orig(batch)

        trace.event("first")
        monkeypatch.setattr(trace, "_serialise", slow)
        flusher = threading.Thread(target=trace.flush)
        flusher.start()
        assert serialising.wait(30)
        # the flush is under way and stays so
        monkeypatch.setattr(trace, "FLUSH_INTERVAL_S", 0.0)
        t0 = time.perf_counter()
        for i in range(50):
            with trace.span("meanwhile", i=i):
                pass
        took = time.perf_counter() - t0
        assert flusher.is_alive() and not release.is_set()
        assert took < 1.0, f"50 spans took {took:.3f}s beside a flush"
        monkeypatch.setattr(trace, "_serialise", orig)
        waiter = threading.Thread(target=trace.flush)
        waiter.start()
        waiter.join(0.2)
        assert waiter.is_alive()  # flush() keeps its guarantee: it waits
        release.set()
        flusher.join(30)
        waiter.join(30)
        assert not flusher.is_alive() and not waiter.is_alive()
        recs = _read(sink)
        assert [r["name"] for r in recs] == ["first"] + ["meanwhile"] * 50
        assert [r["i"] for r in recs[1:]] == list(range(50))
    finally:
        release.set()
        _cleanup()
