"""The cell catchup-1000v-1ktx.replay: its rehearsal through run.py traced and
untraced, its two controls, its six new readers fed by hand, the reference
against Merkle roots written out by hand, and its entries in BENCHMARK.json.
(test_rehearse.py runs the same four rehearsals for every cell of
BENCHMARK.json; here the line is held to this cell's names.)"""

import hashlib
import json
import os
import subprocess
import sys

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")

from benchmark.harness import readers  # noqa: E402
from benchmark.harness.spec import BENCH, Cell, load_benchmark  # noqa: E402
from benchmark.reference import kvstore_replay as ref  # noqa: E402

ROOT = os.path.dirname(BENCH)
CELL = "catchup-1000v-1ktx.replay"
NEW = ["finalize_ms_per_block.loaded", "validate_ms_per_block.loaded",
       "data_hash_ms_per_block.loaded", "state_save_ms_per_block.loaded",
       "block_decode_ms_per_block.loaded", "window_load_mb.loaded"]
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
    assert [m["name"] for m in bench["per_layer"][-len(NEW):]] == NEW
    for name in NEW:
        m, spec = by_name[name], _spec(name)
        assert m["workloads"] == [CELL] == spec["cells"]
        assert (m["moves"], m["source"], spec["reader"]) == (
            "catchup_blocks_per_s", "program_span", "span_stat")
        assert m["layer"] == spec["layer"] and m["unit"] == spec["unit"]
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    for stem in SHARED:
        assert by_name[stem + ".catchup"]["workloads"] == [
            "catchup-1000v.replay", "catchup-1000v-churn.replay", CELL]
    for stem in ("window_load_tail_ms", "window_mid_pass_s", "window_load_s",
                 "window_queue_s", "window_wait_s"):
        assert CELL not in by_name[stem + ".catchup"]["workloads"]
    cell = Cell(bench, CELL)
    assert [m["name"] for m in cell.end_to_end()] == [
        "catchup_blocks_per_s", "setup_s"]
    assert {m["name"] for m, _ in cell.layer_metrics()} == set(NEW) | {
        s + ".catchup" for s in SHARED}
    assert cell.chips == 1 and cell.driver_name == "catchup_replay_loaded"
    assert bench["workloads"][-1]["name"] == CELL
    assert all(len(e["why"]) <= 200 for e in (bench["workloads"][-1],
                                              bench["configs"][-1]))


def test_the_configuration_is_the_siblings_with_the_load_and_the_store():
    bench = load_benchmark()
    cell = Cell(bench, CELL)
    sib = Cell(bench, "catchup-1000v.replay").config["shapes"]
    shapes = cell.config["shapes"]
    assert {k: shapes[k] for k in sib if k != "txs_per_block"} == {
        k: v for k, v in sib.items() if k != "txs_per_block"}
    assert {k: v for k, v in shapes.items()
            if k not in sib or k == "txs_per_block"} == {
        "txs_per_block": 400, "tx_bytes": 1024, "tx_format": "loadtime",
        "load_connections": 1, "load_rate": 400, "state_store": "sqlite",
        "indexer": "null"}
    entry = bench["configs"][-1]
    assert entry["name"] == "catchup-1000v-1ktx"
    assert entry["source"] == cell.config["source"]
    assert len(entry["source"]) <= 200
    for word in ("CometBFT-QA-38.md", "test/loadtime", "BASELINE.json configs[3]"):
        assert word in entry["source"]
    assert list(cell.config["reduced"]) == ["blocks"] == entry["reduced"]
    sib_g = Cell(bench, "catchup-1000v.replay").config["guarantees"]
    assert cell.config["guarantees"][:5] == sib_g
    assert len(cell.config["guarantees"]) == 8
    # every number set here has its reason
    assert {"txs_per_block", "validators", "one_key", "indexer",
            "source_values"} <= set(cell.config["assumed"])
    # the driver's parameters: what it reads is in the file
    for key in ("validators", "blocks", "window", "txs_per_block", "tx_bytes",
                "tx_format", "load_connections", "load_rate", "state_store",
                "indexer", "warmup_windows", "profile_windows",
                "device_from_lanes"):
        assert key in cell.params, key
    rehearsed = Cell(bench, CELL, rehearse=True).params
    assert (rehearsed["validators"], rehearsed["window"], rehearsed["blocks"],
            rehearsed["txs_per_block"], rehearsed["tx_bytes"]) == (
        8, 4, 16, 12, 1024)


def test_the_new_readers_read_the_replays_spans():
    spans = [
        {"name": "blocksync.window_load", "dur_ms": 140.0, "window": 65,
         "blocks": 64, "end": "full", "bytes": 33_000_000, "read_ms": 20.0,
         "decode_ms": 96.0},
        {"name": "blocksync.window_load", "dur_ms": 150.0, "window": 129,
         "blocks": 64, "end": "full", "bytes": 33_200_000, "read_ms": 22.0,
         "decode_ms": 160.0},
        {"name": "state.apply_block", "dur_ms": 20.0, "validate_ms": 2.0,
         "data_hash_ms": 1.0, "finalize_ms": 9.0, "update_state_ms": 2.5,
         "commit_ms": 0.1, "save_events_ms": 6.0, "state_save_ms": 5.9,
         "txs": 400, "tx_bytes": 409600},
        {"name": "state.apply_block", "dur_ms": 22.0, "validate_ms": 2.4,
         "data_hash_ms": 1.2, "finalize_ms": 11.0, "update_state_ms": 2.5,
         "commit_ms": 0.1, "save_events_ms": 6.2, "state_save_ms": 6.1,
         "txs": 400, "tx_bytes": 409600},
        {"name": "state.apply_block", "dur_ms": 90.0, "validate_ms": 2.2,
         "data_hash_ms": 1.1, "finalize_ms": 10.0, "update_state_ms": 2.5,
         "commit_ms": 0.1, "save_events_ms": 70.0, "state_save_ms": 69.0,
         "txs": 400, "tx_bytes": 409600},
    ]
    got = {n: readers.span_stat(_spec(n)["params"], {"spans": spans})
           for n in NEW}
    assert got == {
        "finalize_ms_per_block.loaded": 10.0,
        "validate_ms_per_block.loaded": 2.2,
        "data_hash_ms_per_block.loaded": 1.1,
        "state_save_ms_per_block.loaded": 6.1,
        "block_decode_ms_per_block.loaded": 2.0,
        "window_load_mb.loaded": pytest.approx(33.1),
    }
    # a program without the new fields (the parent of the PR that brought
    # them): the readers of what is new find nothing and raise nothing;
    # finalize_ms and validate_ms were in the span before
    old = [{"name": "blocksync.window_load", "dur_ms": 80.0, "window": 65,
            "blocks": 64, "end": "full"},
           {"name": "state.apply_block", "dur_ms": 0.94, "validate_ms": 0.59,
            "finalize_ms": 0.23, "update_state_ms": 0.07, "commit_ms": 0.01,
            "save_events_ms": 0.01, "txs": 2}]
    none = {n: readers.span_stat(_spec(n)["params"], {"spans": old})
            for n in NEW}
    assert [n for n in NEW if none[n] is None] == NEW[2:]
    assert (none[NEW[0]], none[NEW[1]]) == (0.23, 0.59)


def test_the_reference_against_hand_made_blocks():
    """A two-leaf and a three-leaf Merkle root written out by hand, over
    the transactions' hashes and over the results' encodings."""
    sha = lambda b: hashlib.sha256(b).digest()  # noqa: E731
    t1, t2, t3 = b"a=01", b"a=02", b"b=xyz"
    l1, l2, l3 = (sha(b"\x00" + sha(t)) for t in (t1, t2, t3))
    two = sha(b"\x01" + l1 + l2)
    three = sha(b"\x01" + two + l3)
    assert ref.data_hash([t1, t2]) == two
    assert ref.data_hash([t1, t2, t3]) == three
    assert ref.data_hash([]) == sha(b"")
    # results: code 0 left out, the value as data (field 2, length, bytes)
    r1, r2, r3 = (sha(b"\x00" + b"\x12" + bytes([len(v)]) + v)
                  for v in (b"01", b"02", b"xyz"))
    root2 = sha(b"\x01" + r1 + r2)
    root3 = sha(b"\x01" + root2 + r3)
    r = ref.Replay(keep=(1, 2))
    r.block(1, [t1, t2], two, b"")
    assert (r.store, r.results_root[1], r.differs) == (
        {b"a": b"02"}, root2, [])
    r.block(2, [t1, t2, t3], three, root2)
    assert r.store == {b"a": b"02", b"b": b"xyz"} == r.snapshots[2]
    assert r.snapshots[1] == {b"a": b"02"}
    assert (r.results_root[2], r.differs) == (root3, [])
    assert (r.txs, r.tx_bytes) == (5, 4 + 4 + 4 + 4 + 5)
    # a header that carries other roots is named, by height and by hash
    r.block(3, [t3], two, two)
    assert r.differs == [(3, "data_hash"), (3, "last_results_hash")]
    with pytest.raises(ValueError):
        r.block(5, [], b"", b"")
    # a refused transaction: code 1 (field 1), no data, nothing stored
    bad = ref.Replay()
    bad.block(1, [b"novalue"], ref.data_hash([b"novalue"]), b"")
    assert bad.store == {}
    assert bad.results_root[1] == sha(b"\x00" + b"\x08\x01")


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(BENCH, "reference", "kvstore_replay.py")) as f:
        src = f.read()
    code = src.split('"""', 2)[2]  # behind the module's docstring
    assert "cometbft_tpu" not in code
    imports = [ln for ln in code.splitlines()
               if ln.startswith(("import ", "from "))]
    assert imports == ["from __future__ import annotations", "import hashlib"]


def test_rehearsal_untraced_line():
    _, line = run("--trace", "0")
    assert line["correct"] is True and line["rehearsal"] is True
    assert set(line["metrics"]) == {"catchup_blocks_per_s", "setup_s"}
    for name in ("boundaries_app_store_differs_from_the_references_dict",
                 "heights_of_16_whose_data_hash_or_last_results_hash_is_not_"
                 "the_references",
                 "read_back.state_height_and_app_hash",
                 "read_back.heights_of_8_whose_results_root_is_not_the_"
                 "references",
                 "flipped_signature.blame_height_index",
                 "flipped_transaction_byte.refused_with",
                 "flipped_transaction_byte.blocks_applied"):
        assert line["checks"][name]["ok"], name
    assert not any("window_apply_txs" in c for c in line["checks"])


def test_rehearsal_traced_line_has_the_new_and_the_shared_metrics():
    p, line = run("--trace", "1")
    assert line["correct"] is True
    assert "outside every span of the measured window" in p.stdout
    assert set(line["metrics"]) == (
        set(NEW) | {s + ".catchup" for s in SHARED}) - CHIP_ONLY
    m = {k: v["value"] for k, v in line["metrics"].items()}
    assert m["data_hash_ms_per_block.loaded"] <= m[
        "validate_ms_per_block.loaded"]
    assert 0 < m["state_save_ms_per_block.loaded"] < m[
        "apply_ms_per_block.catchup"]
    # 4 blocks of 12 x 1,024 bytes and 8 signatures a window
    assert 0.05 < m["window_load_mb.loaded"] < 0.06
    assert any("window_apply_txs_is_not_144" in c for c in line["checks"])
    assert "medians of the run's spans, ms a block" in p.stdout


@pytest.mark.parametrize("fault", ("accept_all", "host_path"))
def test_both_controls_turn_correct_false(fault):
    _, line = run("--trace", "0", "--fault", fault, seed=4)
    assert line["correct"] is False and line["fault"] == fault
    failing = [n for n, c in line["checks"].items() if not c["ok"]]
    if fault == "accept_all":
        assert failing == ["flipped_signature.blame_height_index",
                           "flipped_signature.blocks_applied"]
    else:
        assert failing and all("on_a_host_path" in n or "on_a_device_path" in n
                               for n in failing), failing


def test_a_program_without_the_generator_ends_at_once(tmp_path):
    """The driver's own guard, as the tree before this cell meets it."""
    code = (
        "import sys; sys.path.insert(0, %r)\n"
        "from cometbft_tpu.utils import factories as fx\n"
        "del fx.LoadtimeTxs\n"
        "from benchmark.drivers import catchup_replay_loaded as d\n"
        "d._fixtures()\n" % ROOT)
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=dict(os.environ, JAX_PLATFORMS="cpu"),
                       timeout=300)
    assert p.returncode == 1
    assert "no LoadtimeTxs" in p.stderr
