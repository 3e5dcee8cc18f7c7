"""The cell catchup-1000v-churn.replay: its rehearsal through run.py traced
and untraced, its two controls, its five new readers fed by hand, and its
entries in BENCHMARK.json. (test_rehearse.py runs the same four rehearsals for
every cell of BENCHMARK.json; here the line is held to this cell's names.)"""

import json
import os
import subprocess
import sys

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")

from benchmark.harness import readers  # noqa: E402
from benchmark.harness.spec import BENCH, Cell, load_benchmark  # noqa: E402
from benchmark.reference import valset_replay as ref  # noqa: E402

ROOT = os.path.dirname(BENCH)
CELL = "catchup-1000v-churn.replay"
NEW = ["window_blocks.churn", "window_lanes.churn",
       "set_change_drain_ms.churn", "valset_update_ms.churn",
       "a_cache_hit_share.churn"]
SHARED = ["window_load_span_ms", "window_queue_span_ms",
          "window_resolve_span_ms", "window_fill_ms", "window_apply_ms",
          "apply_ms_per_block", "pack_ms", "device_launch_ms",
          "submit_to_verdict_ms", "device_busy_s", "gc_full_time_share"]
# what only the profiler's device planes feed: silent in a rehearsal
CHIP_ONLY = {"device_busy_s.catchup"}


def _spec(name):
    with open(os.path.join(BENCH, "layer_metrics", name + ".json")) as f:
        return json.load(f)


def run(*extra, seed=3):
    bench = load_benchmark()
    cmd = [sys.executable, *bench["command"][1:], "--workload", CELL,
           "--seed", str(seed), "--seconds", "2", "--rehearse", *extra]
    p = subprocess.run(cmd, capture_output=True, text=True, cwd=ROOT,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"), timeout=900)
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    return p, json.loads(p.stdout.strip().splitlines()[-1])


def test_the_new_entries_have_their_files_and_the_cell_its_lists():
    bench = load_benchmark()
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        m, spec = by_name[name], _spec(name)
        assert m["workloads"] == [CELL] == spec["cells"]
        assert (m["moves"], m["source"], spec["reader"]) == (
            "catchup_blocks_per_s", "program_span", "span_stat")
        assert m["layer"] == spec["layer"] and m["unit"] == spec["unit"]
    for stem in SHARED:
        assert by_name[stem + ".catchup"]["workloads"] == [
            "catchup-1000v.replay", CELL]
    for stem in ("window_load_tail_ms", "window_mid_pass_s", "window_load_s",
                 "window_queue_s", "window_wait_s"):
        assert CELL not in by_name[stem + ".catchup"]["workloads"]
    cell = Cell(bench, CELL)
    assert [m["name"] for m in cell.end_to_end()] == [
        "catchup_blocks_per_s", "setup_s"]
    assert {m["name"] for m, _ in cell.layer_metrics()} == set(NEW) | {
        s + ".catchup" for s in SHARED}
    assert cell.chips == 1 and cell.driver_name == "catchup_replay_churn"
    # the sibling's shapes, and the churn
    sib = Cell(bench, "catchup-1000v.replay").config["shapes"]
    assert {k: cell.config["shapes"][k] for k in sib} == sib
    assert {k: v for k, v in cell.config["shapes"].items() if k not in sib} == {
        "update_every": 10, "repowered_members": 5, "power_min": 30,
        "power_max": 100, "spare_keys": 16}
    assert list(cell.config["reduced"]) == ["blocks"]
    assert len(cell.config["guarantees"]) == 9


def test_the_new_readers_read_the_replays_spans():
    """Two windows around one boundary: a speculative load that met the new
    set at once (no window comes of it), the drain, the re-queued window
    whose column is new."""
    spans = [
        {"name": "blocksync.window_load", "dur_ms": 3.0, "window": 65,
         "blocks": 3, "end": "set_change"},
        {"name": "blocksync.window_load", "dur_ms": 0.4, "window": 68,
         "blocks": 0, "end": "set_change"},
        {"name": "blocksync.window_load", "dur_ms": 1.2, "window": 68,
         "blocks": 1, "end": "set_change"},
        {"name": "blocksync.window_apply", "dur_ms": 9.0, "window": 65,
         "blocks": 3, "txs": 7},
        {"name": "blocksync.window_apply", "dur_ms": 3.0, "window": 68,
         "blocks": 1, "txs": 2},
        {"name": "blocksync.window_fill", "dur_ms": 2.0, "window": 65,
         "commits": 4, "lanes": 4000, "columnar": 4},
        {"name": "blocksync.window_fill", "dur_ms": 1.0, "window": 68,
         "commits": 2, "lanes": 2000, "columnar": 2},
        {"name": "blocksync.set_change", "dur_ms": 9.0, "height": 68,
         "reason": "set_change"},
        {"name": "blocksync.set_change", "dur_ms": 5.0, "height": 69,
         "reason": "set_change"},
        {"name": "blocksync.set_change", "dur_ms": 7.0, "height": 70,
         "reason": "speculation_failed"},
        {"name": "state.valset_update", "dur_ms": 1.5, "height": 66,
         "changes": 1},
        {"name": "state.valset_update", "dur_ms": 2.5, "height": 67,
         "changes": 3},
        {"name": "crypto.device_launch", "dur_ms": 1.0, "a_cache": "miss"},
        {"name": "crypto.device_launch", "dur_ms": 0.9, "a_cache": "miss"},
        {"name": "crypto.device_launch", "dur_ms": 0.9, "a_cache": "hit"},
        {"name": "crypto.device_launch", "dur_ms": 0.9, "a_cache": "miss"},
    ]
    got = {n: readers.span_stat(_spec(n)["params"], {"spans": spans})
           for n in NEW}
    assert got == {
        "window_blocks.churn": 2.0,
        "window_lanes.churn": 3000.0,
        "set_change_drain_ms.churn": 7.0,
        "valset_update_ms.churn": 2.0,
        "a_cache_hit_share.churn": 25.0,
    }
    # a program without the spans and fields (the parent of the PR that
    # brought them): the three readers of what is new find nothing and raise
    # nothing; the window's size and lanes were in the spans before
    old = [{"name": "blocksync.window_apply", "dur_ms": 170.0, "window": 65,
            "blocks": 64, "txs": 128},
           {"name": "blocksync.window_fill", "dur_ms": 2.0, "lanes": 65000},
           {"name": "crypto.device_launch", "dur_ms": 1.0, "bytes": 9}]
    none = {n: readers.span_stat(_spec(n)["params"], {"spans": old})
            for n in NEW}
    assert [n for n in NEW if none[n] is None] == [
        "set_change_drain_ms.churn", "valset_update_ms.churn",
        "a_cache_hit_share.churn"]
    assert (none["window_blocks.churn"], none["window_lanes.churn"]) == (
        64.0, 65000.0)


def test_the_reference_hashes_a_set_as_the_spec_does():
    """RFC 6962 over SimpleValidator encodings, by hand at two members."""
    import hashlib

    a, b = (b"\x01" * 32, 5), (b"\x02" * 32, 300)
    enc = [b"\x0a\x22\x0a\x20" + a[0] + b"\x10\x05",
           b"\x0a\x22\x0a\x20" + b[0] + b"\x10\xac\x02"]
    leaves = [hashlib.sha256(b"\x00" + e).digest() for e in enc]
    assert ref.set_hash([a, b]) == hashlib.sha256(
        b"\x01" + leaves[0] + leaves[1]).digest()
    assert ref.val_updates([b"k1=v", b"val:" + b"ab" * 32 + b"=7", b"val:zz=1",
                            b"val:" + b"cd" * 32 + b"=0"]) == [
        (b"\xab" * 32, 7), (b"\xcd" * 32, 0)]


def test_rehearsal_untraced_line():
    _, line = run("--trace", "0")
    assert line["correct"] is True and line["rehearsal"] is True
    assert set(line["metrics"]) == {"catchup_blocks_per_s", "setup_s"}
    for name in ("marks_valset_hash_differs_from_reference",
                 "flipped_signature_after_set_change.blame_height_index",
                 "old_set_signs_after_rotation.refused_at_height",
                 "nil_votes_under_new_powers.refused_at_height",
                 "nil_votes_under_new_powers.reference"):
        assert line["checks"][name]["ok"], name


def test_rehearsal_traced_line_has_the_new_and_the_shared_metrics():
    p, line = run("--trace", "1")
    assert line["correct"] is True
    assert "outside every span of the measured window" in p.stdout
    assert set(line["metrics"]) == (
        set(NEW) | {s + ".catchup" for s in SHARED}) - CHIP_ONLY
    assert 1.0 <= line["metrics"]["window_blocks.churn"]["value"] <= 4.0
    assert any("sigs_verified_is_not_its_window_fill_lanes" in c
               for c in line["checks"])


@pytest.mark.parametrize("fault", ("accept_all", "host_path"))
def test_both_controls_turn_correct_false(fault):
    _, line = run("--trace", "0", "--fault", fault, seed=4)
    assert line["correct"] is False and line["fault"] == fault
    failing = [n for n, c in line["checks"].items() if not c["ok"]]
    if fault == "accept_all":
        assert failing == [
            "flipped_signature_after_set_change.blame_height_index",
            "flipped_signature_after_set_change.blocks_applied"]
    else:
        assert failing and all("on_a_host_path" in n or "on_a_device_path" in n
                               for n in failing), failing
