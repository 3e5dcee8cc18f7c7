"""The cell catchup-1000v-1ktx-kvindex.replay: its files, its rehearsal through
run.py traced and untraced, its six new readers fed by hand, the reference
against an index written out by hand, and the check of the index read back,
which must fail when one record is gone. Entries are pinned BY NAME and a line
is held to a SUPERSET of names, so that a later PR may append."""

import hashlib
import json
import os
import subprocess
import sys

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")

from benchmark.drivers import catchup_replay_indexed as driver  # noqa: E402
from benchmark.harness import readers  # noqa: E402
from benchmark.harness.spec import BENCH, Cell, load_benchmark  # noqa: E402
from benchmark.reference import tx_index as ref  # noqa: E402

ROOT = os.path.dirname(BENCH)
CELL = "catchup-1000v-1ktx-kvindex.replay"
SIBLING = "catchup-1000v-1ktx.replay"
NEW = {"index_ms_per_block.indexed": ("index.block", "indexer"),
       "index_encode_ms_per_block.indexed": ("index.block", "indexer"),
       "index_write_ms_per_block.indexed": ("index.block", "indexer"),
       "publish_ms_per_block.indexed": ("state.apply_block",
                                        "consensus, apply and store"),
       "index_wait_ms_per_block.indexed": ("state.apply_block",
                                           "consensus, apply and store"),
       "index_behind_blocks.indexed": ("index.block", "indexer")}
# what only the profiler's device planes feed: silent in a rehearsal
CHIP_ONLY = {"device_busy_s.catchup"}


def _spec(name):
    with open(os.path.join(BENCH, "layer_metrics", name + ".json")) as f:
        return json.load(f)


def run(*extra, seed=5):
    bench = load_benchmark()
    cmd = [sys.executable, *bench["command"][1:], "--workload", CELL,
           "--seed", str(seed), "--seconds", "2", "--rehearse", *extra]
    p = subprocess.run(cmd, capture_output=True, text=True, cwd=ROOT,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"), timeout=900)
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    return p, json.loads(p.stdout.strip().splitlines()[-1])


def test_the_cells_files_load_and_its_entries_are_there_by_name():
    bench = load_benchmark()
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name, (span, layer) in NEW.items():
        m, spec = by_name[name], _spec(name)
        assert m["workloads"] == [CELL] == spec["cells"]
        assert (m["moves"], m["source"], spec["reader"]) == (
            "catchup_blocks_per_s", "program_span", "span_stat")
        assert (m["layer"], spec["layer"], spec["params"]["span"]) == (
            layer, layer, span)
        assert m["unit"] == spec["unit"]
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    # the cell reports whatever its sibling reports, and its own six
    sibling = {m["name"] for m, _ in Cell(bench, SIBLING).layer_metrics()}
    cell = Cell(bench, CELL)
    assert {m["name"] for m, _ in cell.layer_metrics()} == sibling | set(NEW)
    assert len(sibling) >= 19 and not sibling & set(NEW)
    assert {m["name"] for m in cell.end_to_end()} == {
        "catchup_blocks_per_s", "setup_s"}
    assert cell.chips == 1 and cell.driver_name == "catchup_replay_indexed"
    entry = {w["name"]: w for w in bench["workloads"]}[CELL]
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        "catchup-1000v-1ktx-kvindex", "replay", 1)
    assert entry["why"] == cell.workload["why"] and len(entry["why"]) <= 200
    assert cell.workload["traffic"] == Cell(bench, SIBLING).workload["traffic"]


def test_the_configuration_is_the_siblings_with_the_indexer_on():
    bench = load_benchmark()
    cell, sib = Cell(bench, CELL), Cell(bench, SIBLING)
    assert cell.config["shapes"] == dict(sib.config["shapes"], indexer="kv")
    entry = {c["name"]: c for c in bench["configs"]}[
        "catchup-1000v-1ktx-kvindex"]
    assert entry["source"] == cell.config["source"]
    assert len(entry["source"]) <= 200 and len(entry["why"]) <= 200
    for word in ("DefaultTxIndexConfig", 'indexer = "kv"',
                 "indexer_service.go", "kv/kv.go AddBatch", "c=1 r=400"):
        assert word in entry["source"], word
    assert list(cell.config["reduced"]) == ["blocks"] == entry["reduced"]
    assert cell.config["guarantees"][:8] == sib.config["guarantees"]
    assert len(cell.config["guarantees"]) == 13
    assert set(sib.config["assumed"]) | {
        "K", "application_events", "queries"} == set(cell.config["assumed"])
    assert cell.config["rehearse"] == sib.config["rehearse"]
    assert Cell(bench, CELL, rehearse=True).params["indexer"] == "kv"


def test_the_new_readers_read_the_indexers_spans():
    spans = [
        {"name": "index.block", "dur_ms": 11.0, "height": 65, "txs": 400,
         "keys": 801, "bytes": 870000, "encode_ms": 2.5, "write_ms": 8.0,
         "behind": 1},
        {"name": "index.block", "dur_ms": 13.0, "height": 66, "txs": 400,
         "keys": 801, "bytes": 870000, "encode_ms": 2.7, "write_ms": 10.0,
         "behind": 1},
        {"name": "index.block", "dur_ms": 170.0, "height": 67, "txs": 400,
         "keys": 801, "bytes": 870000, "encode_ms": 2.6, "write_ms": 160.0,
         "behind": 2},
        {"name": "state.apply_block", "dur_ms": 14.0, "save_events_ms": 8.6,
         "state_save_ms": 8.3, "publish_ms": 0.02, "index_wait_ms": 0.0},
        {"name": "state.apply_block", "dur_ms": 14.5, "save_events_ms": 8.7,
         "state_save_ms": 8.4, "publish_ms": 0.03, "index_wait_ms": 0.0},
        {"name": "state.apply_block", "dur_ms": 150.0, "save_events_ms": 144.,
         "state_save_ms": 8.2, "publish_ms": 0.04, "index_wait_ms": 135.0},
    ]
    got = {n: readers.span_stat(_spec(n)["params"], {"spans": spans})
           for n in NEW}
    assert got == {
        "index_ms_per_block.indexed": 13.0,
        "index_encode_ms_per_block.indexed": 2.6,
        "index_write_ms_per_block.indexed": 10.0,
        "publish_ms_per_block.indexed": 0.03,
        "index_wait_ms_per_block.indexed": 45.0,  # the mean: most wait 0
        "index_behind_blocks.indexed": pytest.approx(4 / 3),
    }
    # a program without the indexer's span and fields (the parent of the PR
    # that brought them): every reader finds nothing and raises nothing
    old = [{"name": "state.apply_block", "dur_ms": 13.7, "validate_ms": 1.4,
            "save_events_ms": 8.4, "state_save_ms": 8.3, "txs": 400}]
    assert [readers.span_stat(_spec(n)["params"], {"spans": old})
            for n in NEW] == [None] * len(NEW)


def test_the_reference_against_an_index_written_out_by_hand():
    sha = lambda b: hashlib.sha256(b).digest()  # noqa: E731
    t1, t2, t3, bad = b"a=01", b"b=02", b"a=03", b"novalue"
    ev = lambda k: [("app", [("key", k, True), ("noindex_key", "x", False)])]  # noqa: E731
    want = ref.Index()
    want.block(1, [t1, t2], [ev("a"), ev("b")])
    want.block(2, [bad, t3, t1], [ev("?"), ev("a"), ev("a")])
    assert want.records == {
        sha(t2): (1, 1, t2, 0, b"02"),
        sha(bad): (2, 0, bad, 1, b""),
        sha(t3): (2, 1, t3, 0, b"03"),
        sha(t1): (2, 2, t1, 0, b"01"),  # seen again: its later place
    }
    assert want.by_height == {1: [sha(t1), sha(t2)],
                              2: [sha(bad), sha(t3), sha(t1)]}
    assert want.keys == {
        "tx.height/1/1/0": sha(t1), "tx.height/1/1/1": sha(t2),
        "tx.height/2/2/0": sha(bad), "tx.height/2/2/1": sha(t3),
        "tx.height/2/2/2": sha(t1),
        "app.key/a/1/0": sha(t1), "app.key/b/1/1": sha(t2),
        "app.key/?/2/0": sha(bad), "app.key/a/2/1": sha(t3),
        "app.key/a/2/2": sha(t1),
    }
    assert want.find("app.key", "a") == [sha(t1), sha(t3)]
    assert want.find("app.noindex_key", "x") == []
    assert want.store == {b"a": b"01", b"b": b"02"}
    with pytest.raises(ValueError):
        want.block(4, [])


def test_the_reference_imports_hashlib_and_its_neighbour_alone():
    with open(os.path.join(BENCH, "reference", "tx_index.py")) as f:
        code = f.read().split('"""', 2)[2]  # behind the module's docstring
    assert "cometbft_tpu" not in code
    imports = [ln for ln in code.splitlines()
               if ln.startswith(("import ", "from "))]
    assert imports == ["from __future__ import annotations", "import hashlib",
                       "from benchmark.reference import kvstore_replay"]


def _indexed(tmp_path, heights=6, per=7):
    """An index the program wrote for `heights` blocks, closed, and the
    reference's index of the same transactions."""
    from cometbft_tpu.abci.types import ExecTxResult
    from cometbft_tpu.storage import indexer

    os.makedirs(tmp_path, exist_ok=True)
    txi, bli, dbs = indexer.open_indexers(str(tmp_path))
    want = ref.Index()
    for h in range(1, heights + 1):
        txs = [b"a=%d.%d" % (h, i) for i in range(per)]
        txi.add_batch(h, txs, [ExecTxResult(data=tx[2:]) for tx in txs])
        bli.index(h)
        want.block(h, txs)
    for db in dbs:
        db.close()
    return want


def _failing(checks):
    return [c.name for c in checks if not c.ok]


def test_the_read_back_check_fails_when_one_record_is_gone(tmp_path):
    from cometbft_tpu.storage import indexer, open_kv

    want = _indexed(tmp_path / "ix")
    ok = driver.index_checks(str(tmp_path / "ix"), want, 6, seed=9)
    assert len(ok) == 6 and _failing(ok) == []
    # one transaction's record deleted from the file before the check reads
    victim = want.by_height[4][3]
    db = open_kv(str(tmp_path / "ix" / indexer.TX_INDEX_FILE))
    db.delete(b"TX:" + victim)
    db.close()
    bad = _failing(driver.index_checks(str(tmp_path / "ix"), want, 6, seed=9))
    assert "index.hashes_of_42_not_found_by_get" in bad
    assert "index.records_held" in bad
    # a height key gone: that height's search is short of one hash
    want = _indexed(tmp_path / "ix2")
    db = open_kv(str(tmp_path / "ix2" / indexer.TX_INDEX_FILE))
    for h in range(1, 7):
        db.delete(b"tx.height/%d/%d/2" % (h, h))
    db.close()
    assert _failing(driver.index_checks(
        str(tmp_path / "ix2"), want, 6, seed=9)) == [
        "index.heights_of_6_whose_tx_height_search_is_not_the_references_"
        "hashes_in_order"]
    # a height the block index lacks, a record that says another place
    want = _indexed(tmp_path / "ix3")
    db = open_kv(str(tmp_path / "ix3" / indexer.BLOCK_INDEX_FILE))
    db.delete(b"BE:" + (5).to_bytes(8, "big"))
    db.close()
    txi, _, dbs = indexer.open_indexers(str(tmp_path / "ix3"))
    txi.add_batch(9, [b"a=2.2"], [])
    for db in dbs:
        db.close()
    assert set(_failing(driver.index_checks(
        str(tmp_path / "ix3"), want, 6, seed=9))) >= {
        "index.block_index_heights_are_1_to_tip",
        "index.records_of_42_that_differ_from_the_references"}
    # no completed pass left an index at all
    assert _failing(driver.index_checks(
        str(tmp_path / "none"), want, 6, seed=9)) == [
        "index.read_back_of_a_completed_pass"]


def test_rehearsal_untraced_line():
    _, line = run("--trace", "0")
    assert line["correct"] is True and line["rehearsal"] is True
    assert set(line["metrics"]) == {"catchup_blocks_per_s", "setup_s"}
    for name in ("index.hashes_of_192_not_found_by_get",
                 "index.records_of_192_that_differ_from_the_references",
                 "index.records_held",
                 "index.heights_of_8_whose_tx_height_search_is_not_the_"
                 "references_hashes_in_order",
                 "index.hashes_of_8_the_reference_does_not_hold_found",
                 "index.block_index_heights_are_1_to_tip",
                 "index.events_dropped",
                 "refused_side_chains.index_records_and_heights_left",
                 # the sibling's, unchanged
                 "read_back.state_height_and_app_hash",
                 "flipped_signature.blame_height_index",
                 "flipped_transaction_byte.refused_with"):
        assert line["checks"][name]["ok"], name
    held = [n for n in line["checks"]
            if n.startswith("index.blocks_held_unwritten_beyond_K_2_")]
    assert len(held) == 1 and line["checks"][held[0]]["ok"]


def test_rehearsal_traced_line_carries_the_new_metrics():
    p, line = run("--trace", "1")
    assert line["correct"] is True
    assert "outside every span of the measured window" in p.stdout
    bench = load_benchmark()
    sibling = {m["name"] for m, _ in Cell(bench, SIBLING).layer_metrics()}
    assert set(line["metrics"]) >= (sibling | set(NEW)) - CHIP_ONLY
    m = {k: v["value"] for k, v in line["metrics"].items()}
    assert 0 < m["index_encode_ms_per_block.indexed"] < m[
        "index_ms_per_block.indexed"]
    assert 0 < m["index_write_ms_per_block.indexed"] < m[
        "index_ms_per_block.indexed"]
    assert 1 <= m["index_behind_blocks.indexed"] <= 2
    assert m["publish_ms_per_block.indexed"] > 0
    assert m["index_wait_ms_per_block.indexed"] >= 0
    assert "a completed pass's index:" in p.stdout


def test_a_program_without_the_section_ends_at_once():
    """The driver's own guard, as the tree before this cell meets it."""
    code = (
        "import sys; sys.path.insert(0, %r)\n"
        "from cometbft_tpu import config\n"
        "del config.TxIndexConfig\n"
        "from benchmark.drivers import catchup_replay_indexed as d\n"
        "d._program()\n" % ROOT)
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=dict(os.environ, JAX_PLATFORMS="cpu"),
                       timeout=300)
    assert p.returncode == 1
    assert "no [tx_index] section" in p.stderr
