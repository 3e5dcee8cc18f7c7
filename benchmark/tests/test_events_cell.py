"""The cell catchup-1000v-1ktx-kvevents.replay: its files, its rehearsal
through run.py traced and untraced, its five new readers fed by hand, the
guard that ends the run on a program whose application cannot emit, and the
checks of the events read back, each of which must fail for its own fault
and no other: one attribute key deleted, one stored index flag flipped, one
event dropped from one stored response. Entries are pinned BY NAME and a
line is held to a SUPERSET of names, so that a later PR may append."""

import json
import os
import subprocess
import sys

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")

from benchmark.drivers import catchup_replay_events as driver  # noqa: E402
from benchmark.harness import readers  # noqa: E402
from benchmark.harness.spec import BENCH, Cell, load_benchmark  # noqa: E402
from benchmark.reference import kvstore_events as ref_events  # noqa: E402

ROOT = os.path.dirname(BENCH)
CELL = "catchup-1000v-1ktx-kvevents.replay"
SIBLING = "catchup-1000v-1ktx-kvindex.replay"
NEW = {"index_attr_keys_per_block.events": ("index.block", "indexer"),
       "index_attr_mb_per_block.events": ("index.block", "indexer"),
       "index_batch_mb_per_block.events": ("index.block", "indexer"),
       "response_mb_per_block.events": ("state.apply_block",
                                        "consensus, apply and store"),
       "events_per_block.events": ("state.apply_block",
                                   "consensus, apply and store")}
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
        assert m["workloads"][0] == CELL and spec["cells"] == [CELL]
        assert (m["moves"], m["source"], spec["reader"]) == (
            "catchup_blocks_per_s", "program_span", "span_stat")
        assert (m["layer"], spec["layer"], spec["params"]["span"]) == (
            layer, layer, span)
        assert m["unit"] == spec["unit"]
        assert spec["params"]["stat"] == "median"
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert "roofline" not in name and "mfu" not in name
    # the cell reports whatever its sibling reports, and its own five
    sibling = {m["name"] for m, _ in Cell(bench, SIBLING).layer_metrics()}
    cell = Cell(bench, CELL)
    assert {m["name"] for m, _ in cell.layer_metrics()} >= sibling | set(NEW)
    assert len(sibling) >= 25 and not sibling & set(NEW)
    assert {m["name"] for m in cell.end_to_end()} == {
        "catchup_blocks_per_s", "setup_s"}
    assert cell.chips == 1 and cell.driver_name == "catchup_replay_events"
    entry = {w["name"]: w for w in bench["workloads"]}[CELL]
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        "catchup-1000v-1ktx-kvevents", "replay", 1)
    assert entry["why"] == cell.workload["why"] and len(entry["why"]) <= 200
    assert cell.workload["traffic"] == Cell(bench, SIBLING).workload["traffic"]


def test_the_configuration_is_the_siblings_with_upstreams_events():
    bench = load_benchmark()
    cell, sib = Cell(bench, CELL), Cell(bench, SIBLING)
    assert cell.config["shapes"] == dict(sib.config["shapes"],
                                         app_events="upstream")
    entry = {c["name"]: c for c in bench["configs"]}[
        "catchup-1000v-1ktx-kvevents"]
    assert entry["source"] == cell.config["source"]
    assert len(entry["source"]) <= 200 and len(entry["why"]) <= 200
    for word in ("kvstore.go FinalizeBlock", "2 `app` events a tx",
                 "creator, key, index_key, noindex_key",
                 "kv/kv.go indexEvents", "c=1 r=400"):
        assert word in entry["source"], word
    assert list(cell.config["reduced"]) == ["blocks"] == entry["reduced"]
    assert cell.config["guarantees"][:13] == sib.config["guarantees"]
    assert len(cell.config["guarantees"]) == 16
    assumed = set(sib.config["assumed"]) - {"application_events"}
    assert assumed | {"app_events", "attribute_keys", "block_events",
                      "event_values"} == set(cell.config["assumed"])
    assert "$es$" in cell.config["assumed"]["attribute_keys"]
    assert cell.config["rehearse"] == sib.config["rehearse"]
    # the siblings say of themselves that their application emits nothing
    assert sib.config["assumed"]["application_events"].startswith("none")


def test_the_new_readers_read_the_new_span_fields():
    spans = [
        {"name": "index.block", "dur_ms": 60.0, "keys": 2801,
         "bytes": 1854810, "attr_keys": 2000, "attr_bytes": 997850},
        {"name": "index.block", "dur_ms": 62.0, "keys": 2801,
         "bytes": 1854812, "attr_keys": 2000, "attr_bytes": 997852},
        {"name": "index.block", "dur_ms": 61.0, "keys": 2801,
         "bytes": 1854814, "attr_keys": 2000, "attr_bytes": 997854},
        {"name": "state.apply_block", "dur_ms": 30.0, "events": 800,
         "response_bytes": 910834},
        {"name": "state.apply_block", "dur_ms": 31.0, "events": 800,
         "response_bytes": 910836},
    ]
    got = {n: readers.span_stat(_spec(n)["params"], {"spans": spans})
           for n in NEW}
    assert got == {
        "index_attr_keys_per_block.events": 2000.0,
        "index_attr_mb_per_block.events": pytest.approx(0.997852),
        "index_batch_mb_per_block.events": pytest.approx(1.854812),
        "response_mb_per_block.events": pytest.approx(0.910835),
        "events_per_block.events": 800.0,
    }
    # a program without the fields (the parent of the PR that brought
    # them): four readers find nothing and raise nothing; the batch's
    # bytes were always there
    old = [{"name": "state.apply_block", "dur_ms": 13.7, "txs": 400},
           {"name": "index.block", "dur_ms": 38.0, "keys": 801,
            "bytes": 870000}]
    assert {n: readers.span_stat(_spec(n)["params"], {"spans": old})
            for n in NEW} == dict.fromkeys(
        NEW, None) | {"index_batch_mb_per_block.events": 0.87}


def test_the_reference_is_plain():
    with open(os.path.join(BENCH, "reference", "kvstore_events.py")) as f:
        code = f.read().split('"""', 2)[2]  # behind the module's docstring
    assert "cometbft_tpu" not in code
    assert [ln for ln in code.splitlines()
            if ln.startswith(("import ", "from "))] == [
        "from __future__ import annotations"]
    first, second = ref_events.events(b"a=0801")
    assert first == ("app", [("creator", "Cosmoshi Netowoko", True),
                             ("key", "a", True),
                             ("index_key", "index is working", True),
                             ("noindex_key", "index is working", False)])
    assert second[1][:2] == [("creator", "Cosmoshi", True),
                             ("key", "0801", True)]
    assert ref_events.indexed(b"a=0801") == [
        ("app.creator", "Cosmoshi Netowoko"), ("app.key", "a"),
        ("app.index_key", "index is working"), ("app.creator", "Cosmoshi"),
        ("app.key", "0801"), ("app.index_key", "index is working")]


# -- the checks of the events read back, on files written by hand --------

HEIGHTS, PER, FIRST = 6, 7, 1


def _files(tmp_path):
    """An index and a state store the program's own indexer and encoder
    wrote for HEIGHTS blocks of the eventful application, closed; the
    reference's index of the same transactions; the last_results_hash each
    header would carry."""
    from cometbft_tpu.abci import types as T
    from cometbft_tpu.abci import wire
    from cometbft_tpu.abci.kvstore import KVStoreApp
    from cometbft_tpu.state.execution import results_hash
    from cometbft_tpu.storage import StateStore, indexer, open_kv

    os.makedirs(tmp_path / "ix")
    txi, bli, dbs = indexer.open_indexers(str(tmp_path / "ix"))
    skv = open_kv(str(tmp_path / "state.db"))
    ss, app, blocks, roots = StateStore(skv), KVStoreApp(events=True), [], {}
    for h in range(1, HEIGHTS + 1):
        txs = [b"a=%02d%02d" % (h, i) for i in range(PER)]
        resp = app.finalize_block(T.FinalizeBlockRequest(txs=txs, height=h))
        app.commit()
        txi.add_batch(h, txs, resp.tx_results)
        bli.index(h, resp.events)
        ss.save_abci_responses(h, wire.enc_finalize_resp(resp))
        roots[h] = results_hash(resp.tx_results)
        blocks.append((h, txs))
    for db in (*dbs, skv):
        db.close()
    want = driver.reference_index(blocks)
    return want, {h + 1: root for h, root in roots.items()}


def _checks(tmp_path, want, carried):
    return driver.event_checks(
        str(tmp_path / "ix"), str(tmp_path / "state.db"), want, carried,
        FIRST, HEIGHTS, seed=9)


def _failing(checks):
    return [c.name for c in checks if not c.ok]


KEYS = ("events.attribute_keys_of_210_missing_or_pointing_at_another_hash",
        "events.attribute_keys_held_under_creator_key_index_key")
STORED = "events.heights_of_6_whose_stored_events_are_not_the_references"


def _rewrite_response(tmp_path, height, change):
    """One stored FinalizeBlockResponse decoded, changed and put back."""
    from cometbft_tpu.abci import wire
    from cometbft_tpu.storage import StateStore, open_kv

    skv = open_kv(str(tmp_path / "state.db"))
    ss = StateStore(skv)
    resp = wire.dec_finalize_resp(ss.load_abci_responses(height))
    change(resp)
    ss.save_abci_responses(height, wire.enc_finalize_resp(resp))
    skv.close()


def test_the_checks_hold_on_what_the_program_wrote(tmp_path):
    want, carried = _files(tmp_path)
    ok = _checks(tmp_path, want, carried)
    assert len(ok) == 9 and _failing(ok) == []
    assert {c.name for c in ok} >= {*KEYS, STORED}
    # no completed pass left the files at all
    assert _failing(driver.event_checks(
        str(tmp_path / "none"), str(tmp_path / "state.db"), want, carried,
        FIRST, HEIGHTS, seed=9)) == ["events.read_back_of_a_completed_pass"]


def test_one_attribute_key_deleted_fails_the_keys_checks_alone(tmp_path):
    from cometbft_tpu.storage import indexer, open_kv

    want, carried = _files(tmp_path)
    db = open_kv(str(tmp_path / "ix" / indexer.TX_INDEX_FILE))
    db.delete(b"app.creator/Cosmoshi/4/3")
    db.close()
    assert _failing(_checks(tmp_path, want, carried)) == list(KEYS)


def test_a_key_written_for_an_unmarked_attribute_fails_its_check_alone(
        tmp_path):
    from cometbft_tpu.storage import indexer, open_kv

    want, carried = _files(tmp_path)
    db = open_kv(str(tmp_path / "ix" / indexer.TX_INDEX_FILE))
    db.set(b"app.noindex_key/index is working/2/0", want.by_height[2][0])
    db.close()
    assert _failing(_checks(tmp_path, want, carried)) == [
        "events.attribute_keys_held_under_noindex_key_or_another"]


def test_one_stored_index_flag_flipped_fails_the_stored_events_alone(
        tmp_path):
    want, carried = _files(tmp_path)

    def flip(resp):
        ev = resp.tx_results[2].events[1]
        ev.attributes[3] = ev.attributes[3]._replace(index=True)

    _rewrite_response(tmp_path, 3, flip)
    assert _failing(_checks(tmp_path, want, carried)) == [STORED]


def test_one_event_dropped_from_a_stored_response_fails_that_check_alone(
        tmp_path):
    want, carried = _files(tmp_path)
    _rewrite_response(tmp_path, 5, lambda resp: resp.tx_results[6].events.pop())
    assert _failing(_checks(tmp_path, want, carried)) == [STORED]


def test_a_record_without_its_attributes_fails_what_reads_them(tmp_path):
    from cometbft_tpu.encoding import proto as pb
    from cometbft_tpu.storage import indexer, open_kv

    want, carried = _files(tmp_path)
    db = open_kv(str(tmp_path / "ix" / indexer.TX_INDEX_FILE))
    key = b"TX:" + want.by_height[2][4]
    kept = [(f, v) for f, _, v in pb.parse_fields(db.get(key)) if f != 6]
    db.set(key, b"".join(pb.f_varint(f, v) if isinstance(v, int)
                         else pb.f_bytes(f, v) for f, v in kept))
    db.close()
    # the record's check, and the search that holds each candidate of a
    # height to `app.key = 'a'` by the attributes its record keeps
    assert sorted(_failing(_checks(tmp_path, want, carried))) == [
        "events.heights_of_6_whose_app_key_and_height_search_is_not_the_"
        "references_hashes_in_order",
        "events.records_of_42_whose_stored_attributes_are_not_the_references"]


# -- the rehearsed lines ------------------------------------------------

def test_rehearsal_untraced_line():
    _, line = run("--trace", "0")
    assert line["correct"] is True and line["rehearsal"] is True
    assert set(line["metrics"]) == {"catchup_blocks_per_s", "setup_s"}
    for name in ("events.generators_application_emits",
                 "events.heights_of_12_at_which_the_replays_state_is_not_"
                 "the_stored_chains",
                 "events.attribute_keys_of_960_missing_or_pointing_at_"
                 "another_hash",
                 "events.attribute_keys_held_under_creator_key_index_key",
                 "events.attribute_keys_held_under_noindex_key_or_another",
                 "events.values_of_8_whose_app_key_search_is_not_that_one_"
                 "transaction",
                 "events.heights_of_8_whose_app_key_and_height_search_is_"
                 "not_the_references_hashes_in_order",
                 "events.values_of_8_no_transaction_carried_found",
                 "events.records_of_192_whose_stored_attributes_are_not_"
                 "the_references",
                 "events.heights_of_8_whose_stored_events_are_not_the_"
                 "references",
                 "events.heights_of_8_whose_results_root_is_not_the_"
                 "references_and_the_next_headers",
                 # the siblings', unchanged
                 "index.hashes_of_192_not_found_by_get",
                 "index.records_held",
                 "refused_side_chains.index_records_and_heights_left",
                 "read_back.state_height_and_app_hash",
                 "flipped_signature.blame_height_index",
                 "flipped_transaction_byte.refused_with"):
        assert line["checks"][name]["ok"], name
    assert not [n for n in line["checks"] if "spans_attr_keys" in n]


def test_rehearsal_traced_line_carries_the_new_metrics_and_sums():
    p, line = run("--trace", "1")
    assert line["correct"] is True
    bench = load_benchmark()
    sibling = {m["name"] for m, _ in Cell(bench, SIBLING).layer_metrics()}
    assert set(line["metrics"]) >= (sibling | set(NEW)) - CHIP_ONLY
    m = {k: v["value"] for k, v in line["metrics"].items()}
    # 12 transactions a rehearsed block: 5 keys and 2 events each
    assert m["index_attr_keys_per_block.events"] == 60
    assert m["events_per_block.events"] == 24
    assert 0 < m["index_attr_mb_per_block.events"] < m[
        "index_batch_mb_per_block.events"]
    assert m["response_mb_per_block.events"] > 12 * 2 * 1022e-6
    sums = [n for n in line["checks"] if "spans_attr_keys_and_events" in n]
    assert len(sums) == 1 and sums[0].endswith("_not_720_and_288")
    assert line["checks"][sums[0]]["ok"]
    assert "of its pages unused" in p.stdout or "no dbstat" in p.stdout


@pytest.mark.parametrize("fault", ("accept_all", "host_path"))
def test_the_controls_turn_correct_false(fault):
    _, line = run("--trace", "0", "--fault", fault)
    assert line["correct"] is False


def test_a_program_whose_application_cannot_emit_ends_at_once():
    """The driver's own guard, as the tree before this cell meets it: an
    application without the `events` argument, then types without Event."""
    for breakage in (
            "from cometbft_tpu.abci import kvstore\n"
            "init = kvstore.KVStoreApp.__init__\n"
            "kvstore.KVStoreApp.__init__ = lambda self, "
            "snapshot_interval=0, chunk_size=4096: init(self)\n",
            "from cometbft_tpu.abci import types\n"
            "del types.Event\n"):
        code = ("import sys; sys.path.insert(0, %r)\n%s"
                "from benchmark.drivers import catchup_replay_events as d\n"
                "d._program()\n" % (ROOT, breakage))
        p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                           text=True, timeout=300,
                           env=dict(os.environ, JAX_PLATFORMS="cpu"))
        assert p.returncode == 1, p.stderr[-2000:]
        assert "emits no events" in p.stderr and p.stdout == ""
