"""The cell ics-150v.coalesced: its traced rehearsal's line, its new readers
fed by hand, its own path check fed by hand, and its hang guard."""

import json
import os
import subprocess
import sys
import threading
import time

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")

from benchmark.harness import readers  # noqa: E402
from benchmark.harness.spec import BENCH, Cell, load_benchmark  # noqa: E402

ROOT = os.path.dirname(BENCH)
CELL = "ics-150v.coalesced"
NEW = ["sched_queue_wait_ms.sched", "sched_request_ms.sched",
       "sched_requests_per_dispatch.sched", "sched_alone_lane_share.sched",
       "sched_dispatch_ms.sched", "sched_absorb_ms.sched",
       "a_cache_hit_share.sched"]
# what only a device launch writes: silent in a rehearsal, where the
# dispatch keeps every batch on the host engine
CHIP_ONLY = {"a_cache_hit_share.sched", "pack_ms.commit",
             "pack_pooled_lane_share.commit", "device_launch_ms.commit",
             "device_busy_ms.commit", "submit_to_verdict_ms.commit"}


def _spec(name):
    with open(os.path.join(BENCH, "layer_metrics", name + ".json")) as f:
        return json.load(f)


def test_the_new_per_layer_entries_have_their_files_and_their_reader():
    bench = load_benchmark()
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        m, spec = by_name[name], _spec(name)
        assert m["workloads"] == [CELL] == spec["cells"]
        assert (m["layer"], m["moves"], m["source"]) == (
            "scheduler", "commit_verify_ms.p50", "program_span")
        assert spec["reader"] == "span_stat"
    assert [m["name"] for m in bench["per_layer"]][-len(NEW):] == NEW


def test_the_new_readers_read_the_schedulers_spans():
    """Two dispatches: a lone request on the host engine, then three merged
    on the ladder, which found its pubkey column on the device once in two."""
    spans = [
        {"name": "crypto.sched_coalesce", "id": 1, "dur_ms": 5.0,
         "n_requests": 1, "sigs": 150, "collect_ms": 0.0},
        {"name": "crypto.sched_coalesce", "id": 2, "dur_ms": 11.0,
         "n_requests": 3, "sigs": 450, "absorb_ms": 0.2, "collect_ms": 1.5},
        {"name": "crypto.sched_wait", "dur_ms": 5.5, "queued_ms": 0.1,
         "batch": 1, "alone": True},
        {"name": "crypto.sched_wait", "dur_ms": 15.0, "queued_ms": 4.0,
         "batch": 2, "alone": False},
        {"name": "crypto.sched_wait", "dur_ms": 14.0, "queued_ms": 3.0,
         "batch": 2, "alone": False},
        {"name": "crypto.sched_wait", "dur_ms": 13.0, "queued_ms": 2.0,
         "batch": 2, "alone": False},
        {"name": "crypto.device_launch", "dur_ms": 1.0, "a_cache": "miss"},
        {"name": "crypto.device_launch", "dur_ms": 0.9, "a_cache": "hit"},
    ]
    got = {n: readers.span_stat(_spec(n)["params"], {"spans": spans})
           for n in NEW}
    assert got == {
        "sched_queue_wait_ms.sched": 2.5,
        "sched_request_ms.sched": 13.5,
        "sched_requests_per_dispatch.sched": 2.0,
        "sched_alone_lane_share.sched": 25.0,
        "sched_dispatch_ms.sched": 8.0,
        "sched_absorb_ms.sched": 0.2,
        "a_cache_hit_share.sched": 50.0,
    }
    # a program without the spans (the parent of the PR that brought them):
    # every reader finds nothing, raises nothing, and the line leaves it out
    old = [{"name": "crypto.sched_coalesce", "dur_ms": 5.0, "n_requests": 1,
            "sigs": 150},
           {"name": "crypto.device_launch", "dur_ms": 1.0, "bytes": 9}]
    none = {n: readers.span_stat(_spec(n)["params"], {"spans": old})
            for n in NEW}
    assert [n for n in NEW if none[n] is None] == [
        "sched_queue_wait_ms.sched", "sched_request_ms.sched",
        "sched_absorb_ms.sched", "a_cache_hit_share.sched"]


# ---------------------------------------------------------------------
# the driver, in this process, at a toy size


class _Ctx:
    """What run.py's Ctx gives a driver, without a run around it."""

    def __init__(self, params, workdir, trace_path=None):
        from benchmark.harness import env

        self.cell = type("cell", (), {"params": params})()
        self.seed = 5
        self.workdir = str(workdir)
        self.counters = env.Counters()
        self.trace_path = trace_path
        self.trace_off = 0
        self.profiler = None
        self.snap_open = None

    def objects_tracked(self, stage, census=0):
        pass

    def window_opens(self, now):
        self.snap_open = self.counters.snap()


def _params(**over):
    p = dict(Cell(load_benchmark(), CELL, rehearse=True).params)
    p.update(validators=6, chains=4, commits=2, caller_grace_s=1.0)
    p.update(over)
    return p


@pytest.fixture
def driver(tmp_path):
    from benchmark.drivers import commit_verify_coalesced as D

    d = D.Driver(_Ctx(_params(), tmp_path))
    d._build()
    d._acquire()
    yield d
    d.release()


def test_the_hang_guard_ends_the_run_on_a_handle_that_never_resolves(
        driver, monkeypatch):
    from cometbft_tpu.crypto import sched as S

    never = threading.Event()
    monkeypatch.setattr(S.SchedPending, "result",
                        lambda self, timeout=None: never.wait())
    t0 = time.monotonic()
    with pytest.raises(SystemExit) as e:
        driver.window(0.2)
    took = time.monotonic() - t0
    assert "had not returned" in str(e.value) and "4 caller(s)" in str(e.value)
    # the window's 0.2 s, the grace of 1 s, and never a wait beyond them
    assert 1.0 <= took < 5.0
    never.set()  # lets the stubbed callers go


def test_a_healthy_window_counts_every_call_and_every_answer(driver):
    driver.window(0.3)
    assert driver.failed == 0 and driver.attempted() > 4
    checks = driver._answered_checks()
    assert [c.ok for c in checks] == [True, True]
    m = driver.metrics()
    assert 0 < m["commit_verify_ms.p50"] <= m["commit_verify_ms.p95"]
    # a caller that lost an answer: the books no longer agree
    driver.calls[0].append((0.001, None))
    assert [c.ok for c in driver._answered_checks()] == [False, False]


def test_the_drivers_own_path_check_fed_by_hand(tmp_path):
    """A batch of over 1,024 lanes on the host engine turns the run
    incorrect, by the counters always and by the spans when traced; a lone
    request on the host engine does not."""
    from benchmark.drivers import commit_verify_coalesced as D

    sink = tmp_path / "spans.jsonl"
    good = [{"name": "crypto.batch_verify", "n": 150, "path": "native",
             "t0_ns": 5e9},
            {"name": "crypto.batch_verify", "n": 2400, "path": "ladder",
             "t0_ns": 6e9},
            # outside the window: set-up may do what it likes
            {"name": "crypto.batch_verify", "n": 2400, "path": "native",
             "t0_ns": 1e9}]
    sink.write_text("".join(json.dumps(r) + "\n" for r in good))

    def checks(dev, host, big, line=1024, trace_path=None):
        ctx = _Ctx(_params(coalesced_device_from_lanes=line), tmp_path,
                   trace_path)
        d = D.Driver(ctx)
        d.t0, d.t1 = 4.0, 10.0
        ctx.snap_open = d.snap_close = None
        ctx.counters = type("c", (), {"delta": staticmethod(lambda a, b: {
            "path_selected_total": {("ladder", "ed25519"): float(dev),
                                    ("native", "ed25519"): float(host),
                                    ("batch", "ed25519"): 99.0},
            "gave_way_total": {}})})()
        d.big_open, d.big_close = 7, 7 + big
        return {c.name: c.ok for c in d._path_checks()}

    assert all(checks(dev=40, host=60, big=40).values())
    hidden = checks(dev=0, host=100, big=40)  # --fault host_path
    assert not hidden["batches_over_1024_lanes_less_batches_on_a_device_path"]
    assert not hidden["batches_on_a_device_path"]
    assert checks(dev=40, host=60, big=40, line=None) == {}
    traced = checks(dev=40, host=60, big=40, trace_path=str(sink))
    assert traced["spans_of_big_batches_on_a_host_path"] is True
    sink.write_text(sink.read_text() + json.dumps(
        {"name": "crypto.batch_verify", "n": 1050, "path": "native",
         "t0_ns": 7e9}) + "\n")
    traced = checks(dev=40, host=60, big=40, trace_path=str(sink))
    assert traced["spans_of_big_batches_on_a_host_path"] is False


def test_batches_over_1024_reads_the_histogram_as_a_scrape_would():
    from benchmark.drivers.commit_verify_coalesced import batches_over_1024
    from cometbft_tpu.utils.metrics import crypto_metrics

    before = batches_over_1024()
    for n in (150, 1024, 1050, 2400, 10000):
        crypto_metrics().batch_size.observe(n)
    assert batches_over_1024() - before == 3


# ---------------------------------------------------------------------
# the cell through run.py (a child pinned to the CPU), traced


def test_the_traced_rehearsal_line_holds_every_name_the_cell_lists():
    bench = load_benchmark()
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed",
         "2147499033", "--seconds", "2", "--trace", "1", "--rehearse"],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=900)
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["rehearsal"] is True
    listed = {m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", [CELL])}
    assert set(NEW) <= listed
    assert set(line["metrics"]) == listed - CHIP_ONLY
    assert CHIP_ONLY <= listed
    # the end-to-end names it lists are in the untraced line
    # (test_rehearse.py holds that line name for name)
    e2e = {m["name"] for m in bench["end_to_end"]
           if CELL in m.get("workloads", [CELL])}
    assert e2e == {"commit_verify_ms.p50", "commit_verify_ms.p95", "setup_s"}
    # the mixed round and the sliced bitmaps ran, and held
    names = set(line["checks"])
    assert "mixed_round.bad_and_honest_in_one_dispatch" in names
    assert any(n.startswith("sliced.bad.") for n in names)
    assert all(c["ok"] for c in line["checks"].values())
