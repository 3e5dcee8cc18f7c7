"""The comparisons that decide `correct`, fed by hand: a batch on a host path,
a compile inside the window, a reader that finds nothing."""

import json
import os

import pytest

from benchmark.harness import check as C
from benchmark.harness import readers
from benchmark.harness.env import CompileWatch
from benchmark.harness.spec import BENCH, load_benchmark


def _delta(paths, gave=None, lanes=0, batches=0):
    return {"path_selected_total": paths, "gave_way_total": gave or {},
            "lanes": lanes, "batches": batches}


def test_a_big_batch_on_the_host_makes_the_run_incorrect():
    d = _delta({("mesh", "ed25519"): 9.0, ("native", "ed25519"): 1.0,
                ("batch", "ed25519"): 10.0})
    checks = C.path_checks(d, None, 1024, 10)
    assert not all(c.ok for c in checks)
    good = _delta({("mesh", "ed25519"): 9.0, ("ladder", "ed25519"): 1.0,
                   ("batch", "ed25519"): 10.0})
    assert all(c.ok for c in C.path_checks(good, None, 1024, 10))


def test_spans_name_the_hidden_batch_when_tracing_is_on():
    good = _delta({("mesh", "ed25519"): 1.0})
    spans = [{"name": "crypto.batch_verify", "n": 10000, "path": "native"},
             {"name": "crypto.batch_verify", "n": 150, "path": "native"}]
    checks = C.path_checks(good, spans, 1024, 1)
    assert [c.ok for c in checks if c.name.startswith("spans_")] == [False]
    assert all(c.ok for c in C.path_checks(good, spans[1:], 1024, 1))


def test_a_cell_below_the_line_asks_nothing_of_the_paths():
    d = _delta({("native", "ed25519"): 50.0})
    assert C.path_checks(d, None, None, 50) == []


def test_lanes_sent_to_the_host_at_result_are_a_fault():
    d = _delta({("ladder", "ed25519"): 1.0}, gave={("oversize",): 3.0})
    assert not all(c.ok for c in C.path_checks(d, None, 1024, 1))


@pytest.mark.parametrize("program", [
    "jit(verify_batch_cached_a)", "jit(decompress_pubkeys)",
    "jit(local)",  # the mesh's, as jax.monitoring names it on four devices
])
def test_a_verify_program_compiling_inside_the_window_is_a_fault(program):
    w = CompileWatch.__new__(CompileWatch)
    w.events = [
        {"fn": program, "s": 6.0, "t": 5.0, "cache_hit": True},
        {"fn": program, "s": 6.0, "t": 50.0, "cache_hit": True},
        {"fn": "jit(_stack)", "s": 0.01, "t": 51.0, "cache_hit": False},
    ]
    assert all(c.ok for c in C.compile_checks(w, 10.0, 40.0))
    assert not all(c.ok for c in C.compile_checks(w, 10.0, 60.0))
    # a glue program alone is printed, not a fault
    assert all(c.ok for c in C.compile_checks(w, 50.5, 60.0))


def test_readers_return_nothing_when_there_is_nothing_to_read():
    src = {"spans": [], "counters": _delta({}), "recorder": None,
           "t0": 0.0, "t1": 1.0, "trace": None, "profile": None,
           "units": {"profile": 0}}
    assert readers.span_stat({"span": "x", "field": "n", "stat": "sum"}, src) is None
    assert readers.counter_ratio(
        {"num": [{"counter": "gave_way_total", "labels": ["rlc_declined"]}],
         "den": [{"counter": "path_selected_total", "labels": ["rlc"]}]},
        src) is None
    assert readers.wrapped_call_stat({"targets": ["a:b"], "stat": "mean"}, src) is None
    assert readers.device_busy({}, src) is None
    assert readers.gc_stat({"generation": 2, "stat": "mean"}, src) is None
    assert readers.driver_series({"series": "window_s.mid_pass",
                                  "stat": "median"}, src) is None


def test_span_share_and_counter_ratio():
    spans = [{"name": "crypto.batch_verify", "n": 10000, "path": "rlc"},
             {"name": "crypto.batch_verify", "n": 10000, "path": "ladder"},
             {"name": "crypto.batch_verify", "n": 5000, "path": "native"},
             {"name": "other", "n": 1}]
    src = {"spans": spans,
           "counters": _delta({("rlc", "ed25519"): 9.0},
                              gave={("rlc_declined",): 1.0})}
    share = readers.span_stat(
        {"span": "crypto.batch_verify", "field": "n", "stat": "sum",
         "where": {"path": ["ladder", "rlc", "delta", "mesh"]}, "over": {},
         "scale": 100.0}, src)
    assert share == 80.0
    ratio = readers.counter_ratio(
        {"num": [{"counter": "gave_way_total", "labels": ["rlc_declined"]}],
         "den": [{"counter": "path_selected_total", "labels": ["rlc", "ed25519"]},
                 {"counter": "gave_way_total", "labels": ["rlc_declined"]}],
         "scale": 100.0}, src)
    assert ratio == 10.0


class _Gc:
    pauses = [(2, 1.0, 0.5), (0, 1.2, 0.001), (2, 3.0, 0.7), (2, 9.0, 0.6)]

    def between(self, generation, t0, t1):
        return [s for g, t, s in self.pauses if g == generation and t0 <= t <= t1]


def test_gc_stat_counts_and_times_the_collections_of_one_generation():
    src = {"gc": _Gc(), "t0": 0.0, "t1": 4.0,
           "units": {"profile": 0, "attempted": 20}}
    share = readers.gc_stat({"generation": 2, "stat": "count",
                             "per": "attempted", "scale": 100.0}, src)
    assert share == 10.0  # 2 full collections in 20 calls
    mean = readers.gc_stat({"generation": 2, "stat": "mean",
                            "scale": 1000.0}, src)
    assert abs(mean - 600.0) < 1e-9
    time_share = readers.gc_stat({"generation": 2, "stat": "sum",
                                  "per": "window_s", "scale": 100.0}, src)
    assert abs(time_share - 30.0) < 1e-9
    # a window without a full collection: the count is 0, a pause is nothing
    quiet = dict(src, t0=5.0, t1=8.0)
    assert readers.gc_stat({"generation": 2, "stat": "count",
                            "per": "attempted"}, quiet) == 0.0
    assert readers.gc_stat({"generation": 2, "stat": "mean"}, quiet) is None


def test_the_collector_watch_records_a_forced_full_collection():
    import gc
    import time

    from benchmark.harness.env import GcWatch, tracked_objects

    w = GcWatch()
    t0 = time.perf_counter()
    gc.collect()
    t1 = time.perf_counter()
    w.close()
    gc.collect()  # after close: not recorded
    full = w.between(2, t0, t1)
    assert len(full) == 1 and 0 < full[0] <= t1 - t0
    n, kinds = tracked_objects(census=3)
    assert n > 0 and len(kinds) == 3 and kinds[0][1] >= kinds[1][1]


def test_driver_series_statistic():
    src = {"series": {"window_s.mid_pass": [0.5, 0.4, 1.1]}}
    assert readers.driver_series({"series": "window_s.mid_pass",
                                  "stat": "median"}, src) == 0.5
    assert readers.driver_series({"series": "window_s.first",
                                  "stat": "median"}, src) is None


def test_the_tail_load_marker_reads_the_last_written_window_alone():
    """window_load_tail_ms.catchup: the median of blocksync.window_load over
    heights 193-256, whatever the other two loads of a pass take."""
    with open(os.path.join(BENCH, "layer_metrics",
                           "window_load_tail_ms.catchup.json")) as f:
        spec = json.load(f)
    spans = [{"name": "blocksync.window_load", "window": h, "dur_ms": ms}
             for h, ms in ((65, 70.0), (129, 71.0), (193, 160.0), (65, 72.0),
                           (129, 70.0), (193, 164.0), (193, 168.0))]
    spans.append({"name": "blocksync.window_queue", "window": 193, "dur_ms": 1.0})
    src = {"spans": spans}
    assert readers.KINDS[spec["reader"]](spec["params"], src) == 164.0
    assert readers.KINDS[spec["reader"]](spec["params"], {"spans": spans[:2]}) is None


def test_every_layer_metric_has_its_file_and_every_file_its_entry():
    bench = load_benchmark()
    names = [m["name"] for m in bench["per_layer"]]
    files = sorted(f[:-5] for f in os.listdir(os.path.join(BENCH, "layer_metrics")))
    assert sorted(names) == files
    cells = {w["name"] for w in bench["workloads"]}
    for m in bench["per_layer"]:
        with open(os.path.join(BENCH, "layer_metrics", m["name"] + ".json")) as f:
            spec = json.load(f)
        assert spec["reader"] in readers.KINDS, m["name"]
        # a cell added as data (PR 26) is in BENCHMARK.json alone
        assert set(spec["cells"]) <= set(m["workloads"]) <= cells, m["name"]
        assert (spec["unit"], spec["layer"], spec["moves"]) == (
            m["unit"], m["layer"], m["moves"]), m["name"]
    # the engines PRs 25 and 28 removed have no reader left
    assert not [n for n in names if n.startswith(("rlc_", "batch_materialize_",
                                                  "gc_full_pause_"))]
