"""The reduction from a trace to busy/idle, on a small recorded trace.

data/small_trace.json is in the form load_xplane() gives. Device 0 runs
[1000,3000) [2500,5500) [8000,9000) [9000,10000) ns: merged [1000,5500) and
[8000,10000), busy 6500 ns; the host's first event opens the window at 0 and
its last closes it at 12000 ns.
"""

import json
import os

import pytest

from benchmark.harness.profile import merge, reduce_trace

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture()
def trace():
    with open(os.path.join(HERE, "data", "small_trace.json")) as f:
        return json.load(f)


def test_merge_unions_overlapping_intervals():
    assert merge([(5, 7), (1, 3), (2, 4), (7, 8)]) == [[1, 4], [5, 8]]


def test_busy_is_the_union_not_the_sum(trace):
    red = reduce_trace(trace)
    assert red["devices"] == 1
    assert red["busy_s"] == pytest.approx(6500e-9)
    assert red["window_s"] == pytest.approx(12000e-9)


def test_top_operations_carry_their_program(trace):
    ops = dict(reduce_trace(trace)["device_ops"])
    assert ops["jit_rlc_verify_stream/custom-call.2"] == pytest.approx(3000e-9)
    assert ops["jit_rlc_verify_stream/fusion.1"] == pytest.approx(2000e-9)
    assert ops["jit_verify_batch_cached_a/fusion.1"] == pytest.approx(1000e-9)


def test_idle_gaps_go_to_the_innermost_host_annotation(trace):
    gaps = dict(reduce_trace(trace)["idle_gaps"])
    # gaps: [0,1000) outer; [5500,8000): outer to 5600, pack to 7600, outer
    # to 8000; [10000,12000) outer
    assert gaps["pack"] == pytest.approx(2000e-9)
    assert gaps["outer"] == pytest.approx((1000 + 100 + 400 + 2000) * 1e-9)
    assert sum(gaps.values()) == pytest.approx((12000 - 6500) * 1e-9)


def test_module_filter_keeps_one_program(trace):
    red = reduce_trace(trace, module="rlc_verify_stream")
    assert red["busy_s"] == pytest.approx(4500e-9)


def test_a_trace_with_no_device_operation_reads_busy_zero(trace):
    trace["planes"] = [p for p in trace["planes"]
                       if not p["name"].startswith("/device:")]
    red = reduce_trace(trace)
    assert red["busy_s"] == 0.0 and red["devices"] == 0
    assert red["window_s"] == pytest.approx(12000e-9)
