"""Flight-recorder merger tests: golden synthetic multi-node worlds.

The builder emits per-node JSONL sinks with CONTROLLED clock skews —
every node stamps ``true_time + skew[node]`` — so the tests can assert
the merger recovers the skews from send→recv pairs alone, orders the
merged timeline causally, attributes the per-height critical path, and
triages a reproduction of the rejoin stall (ROADMAP item: node stuck
at height H with rounds advancing while peers commit on — the
classifier must name the node and the missing catchup precommits)."""

import json
import os
import subprocess
import sys

import pytest

from cometbft_tpu.utils import traceview

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

LATENCY = 0.01  # symmetric one-way latency in the synthetic worlds


class WorldBuilder:
    """Synthetic N-node testnet emitting per-node trace records.

    All `t` arguments are TRUE time (seconds); each record lands in its
    node's sink stamped with ``t + skew[node]``."""

    def __init__(self, skews: dict[str, float]):
        self.names = list(skews)
        self.skews = skews
        # deterministic 40-hex node ids, node0 -> "0000...", etc.
        self.ids = {n: f"{i:02x}" * 20 for i, n in enumerate(self.names)}
        self.records: dict[str, list] = {n: [] for n in self.names}
        for n in self.names:
            self.emit(n, 0.0, "node.boot", moniker=n,
                      node_id=self.ids[n])

    def emit(self, node: str, t: float, name: str, kind="event", **fields):
        rec = {"ts": 1000.0 + t + self.skews[node], "pid": 1,
               "name": name, "kind": kind, "node": self.ids[node]}
        rec.update(fields)
        self.records[node].append(rec)

    def wire(self, src: str, dst: str, t: float, **meta):
        """One gossiped message: p2p.send at src, p2p.recv at dst."""
        self.emit(src, t, "p2p.send", peer=self.ids[dst], chan=0x21,
                  bytes=64, **meta)
        self.emit(dst, t + LATENCY, "p2p.recv", peer=self.ids[src],
                  chan=0x21, bytes=64, **meta)

    def commit_height(self, h: int, t: float, proposer: str | None = None,
                      nodes: list[str] | None = None):
        """One clean consensus height: proposal + part gossip from the
        proposer, prevote/precommit exchange, steps, commit, apply."""
        proposer = proposer or self.names[0]
        nodes = nodes or self.names
        for dst in nodes:
            if dst != proposer:
                self.wire(proposer, dst, t,
                          msg="proposal", height=h, round=0)
                self.wire(proposer, dst, t + 0.002,
                          msg="block_part", height=h, round=0, idx=0)
        for ty in ("prevote", "precommit"):
            off = 0.02 if ty == "prevote" else 0.04
            for i, src in enumerate(nodes):
                for dst in nodes:
                    if dst != src:
                        self.wire(src, dst, t + off,
                                  msg="vote", height=h, round=0,
                                  type=ty, idx=i)
        for n in nodes:
            self.emit(n, t + 0.055, "consensus.step", kind="span",
                      step="PROPOSE", height=h, round=0, dur_ms=20.0,
                      next="PREVOTE")
            self.emit(n, t + 0.075, "consensus.step", kind="span",
                      step="PREVOTE", height=h, round=0, dur_ms=20.0,
                      next="PRECOMMIT")
            self.emit(n, t + 0.095, "consensus.step", kind="span",
                      step="PRECOMMIT", height=h, round=0, dur_ms=20.0,
                      next="COMMIT")
            self.emit(n, t + 0.1, "consensus.finalize_commit",
                      height=h, round=0, txs=2)
            self.emit(n, t + 0.12, "state.apply_block", kind="span",
                      height=h, txs=2, dur_ms=15.0, validate_ms=9.0,
                      finalize_ms=3.0, commit_ms=2.0, save_events_ms=1.0)

    def write(self, root) -> str:
        for n in self.names:
            d = os.path.join(str(root), n, "data")
            os.makedirs(d, exist_ok=True)
            with open(os.path.join(d, "trace.jsonl"), "w") as f:
                for rec in self.records[n]:
                    f.write(json.dumps(rec) + "\n")
        return str(root)


SKEWS = {"node0": 0.0, "node1": 2.0, "node2": -1.5, "node3": 0.3}


def healthy_world(tmp_path, skews=SKEWS, heights=5):
    w = WorldBuilder(skews)
    for h in range(1, heights + 1):
        w.commit_height(h, 1.0 * h)
    return w, w.write(tmp_path)


def rejoin_stall_world(tmp_path):
    """ROADMAP rejoin-stall reproduction: node3 reboots after height 4
    and sticks at height 5 — rounds advance 0..8, the block data
    arrives, but NO precommits (catchup votes) ever do — while the
    other three commit on to height 12."""
    w = WorldBuilder(SKEWS)
    for h in range(1, 5):
        w.commit_height(h, 1.0 * h)
    # node3 reboots (new process) and is stuck at height 5 from t=20
    w.emit("node3", 20.0, "node.boot", moniker="node3",
           node_id=w.ids["node3"])
    live = ["node0", "node1", "node2"]
    for h in range(5, 13):
        w.commit_height(h, 5.0 + (h - 5) * 2.5, nodes=live)
    # the stuck node gets the proposal + parts for height 5 re-gossiped
    w.wire("node0", "node3", 21.0, msg="proposal", height=5, round=0)
    w.wire("node0", "node3", 21.01, msg="block_part", height=5,
           round=0, idx=0)
    # peers keep talking to it (so it is connected, not isolated) ...
    for i, src in enumerate(live):
        w.wire(src, "node3", 24.0 + i, msg="new_round_step",
               height=13, round=0, step=3)
    # ... while its own rounds churn in place until the end of the world
    for r in range(0, 9):
        t = 21.0 + r * 2.0
        w.emit("node3", t, "consensus.step", kind="span",
               step="PROPOSE", height=5, round=r, dur_ms=600.0,
               next="PREVOTE")
        w.emit("node3", t + 1.0, "consensus.step", kind="span",
               step="PREVOTE", height=5, round=r, dur_ms=400.0,
               next="NEW_ROUND")
    return w, w.write(tmp_path)


# ---------------------------------------------------------------- merge
def test_merge_recovers_controlled_skews(tmp_path):
    _, root = healthy_world(tmp_path)
    mt = traceview.merge([root])
    assert len(mt.traces) == 4
    names = {t.name for t in mt.traces}
    assert names == set(SKEWS)
    # offsets are relative to the reference node: pairwise differences
    # must match the planted skews (symmetric latency cancels exactly)
    off = {mt.display_name(k): v for k, v in mt.offsets.items()}
    for a in SKEWS:
        for b in SKEWS:
            want = SKEWS[a] - SKEWS[b]
            got = off[a] - off[b]
            assert abs(got - want) < 1e-6, (a, b, got, want)


def test_merge_aligns_large_skew(tmp_path):
    # ±30s skews: raw timestamps are wildly misordered across sinks,
    # adjusted ones must still be causal
    skews = {"node0": 0.0, "node1": 30.0, "node2": -30.0}
    w = WorldBuilder(skews)
    for h in range(1, 4):
        w.commit_height(h, 1.0 * h)
    mt = traceview.merge([w.write(tmp_path)])
    off = {mt.display_name(k): v for k, v in mt.offsets.items()}
    assert abs((off["node1"] - off["node2"]) - 60.0) < 1e-6
    # causality: every recv at/after the matching send on the merged clock
    sends = {}
    for r in mt.records:
        if r["name"] == "p2p.send":
            k = (r["_node"], r["peer"], r.get("msg"), r.get("height"),
                 r.get("type"), r.get("idx"))
            sends.setdefault(k, r["_t"])
    for r in mt.records:
        if r["name"] == "p2p.recv":
            k = (r["peer"], r["_node"], r.get("msg"), r.get("height"),
                 r.get("type"), r.get("idx"))
            if k in sends:
                assert r["_t"] >= sends[k] - 1e-9


def test_merged_timeline_and_heights(tmp_path):
    _, root = healthy_world(tmp_path)
    mt = traceview.merge([root])
    assert mt.heights() == [1, 2, 3, 4, 5]
    tl = mt.timeline(height=3)
    assert tl and all(r.get("height") == 3 for r in tl)
    # adjusted order is monotonic
    ts = [r["_t"] for r in tl]
    assert ts == sorted(ts)
    # the per-height view mixes all four nodes
    assert {mt.display_name(r["_node"]) for r in tl} == set(SKEWS)
    assert any(r["name"] == "p2p.recv" for r in tl)


# -------------------------------------------------------- critical path
def test_critical_path_attribution(tmp_path):
    _, root = healthy_world(tmp_path)
    mt = traceview.merge([root])
    cp = mt.critical_path(5)
    assert cp["committed"] is True
    assert cp["proposer"] == "node0"
    assert set(cp["per_node"]) == set(SKEWS)
    for name, nd in cp["per_node"].items():
        assert nd["verify_ms"] == pytest.approx(9.0)
        assert nd["apply_ms"] == pytest.approx(6.0)
        assert nd["prevote_ms"] == pytest.approx(20.0)
        if name != "node0":  # non-proposers saw the parts in flight
            assert 0.0 < nd["gossip_ms"] < 1000.0
    assert cp["wall_ms"] and cp["wall_ms"] > 0
    assert cp["phase_ms"]["verify_ms"] == pytest.approx(9.0)
    txt = traceview.render_critical_path(cp)
    assert "height 5" in txt and "node3" in txt


def test_critical_path_uncommitted_height(tmp_path):
    _, root = healthy_world(tmp_path)
    mt = traceview.merge([root])
    cp = mt.critical_path(99)
    assert cp["committed"] is False
    assert cp["per_node"] == {}


# ---------------------------------------------------------- stall triage
def test_stall_report_healthy_world_is_ok(tmp_path):
    _, root = healthy_world(tmp_path)
    mt = traceview.merge([root])
    rep = mt.stall_report()
    assert rep["status"] == "ok"
    assert rep["tip"] == 5
    assert rep["stalled"] == []


def test_stall_report_names_rejoin_stall(tmp_path):
    _, root = rejoin_stall_world(tmp_path)
    mt = traceview.merge([root])
    rep = mt.stall_report()
    assert rep["status"] == "stall"
    assert rep["tip"] == 12
    assert len(rep["stalled"]) == 1
    s = rep["stalled"][0]
    # names the stalled node, its stuck height, and the round churn
    assert s["node"] == "node3"
    assert s["height"] == 5
    assert s["max_round"] == 8
    # ... and the first absent message class: the catchup precommits
    assert s["first_missing"] == "precommit"
    assert "catchup" in s["detail"]
    # block data arrived; votes did not
    assert s["recv_counts"].get("block_part", 0) >= 1
    assert s["recv_counts"].get("precommit", 0) == 0
    # the connected-but-silent peers are named
    assert set(s["silent_peers"]) == {"node0", "node1", "node2"}
    txt = traceview.render_stall_report(rep)
    assert "STALLED node3" in txt
    assert "precommit" in txt


def test_stall_report_dead_node_not_flagged(tmp_path):
    # a node whose sink simply STOPS (crash) is dead, not stalled —
    # different triage, must not be reported as live-but-stuck
    w = WorldBuilder(SKEWS)
    for h in range(1, 5):
        w.commit_height(h, 1.0 * h)
    live = ["node0", "node1", "node2"]
    for h in range(5, 13):
        w.commit_height(h, 5.0 + (h - 5) * 2.5, nodes=live)
    mt = traceview.merge([w.write(tmp_path)])
    rep = mt.stall_report()
    assert rep["status"] == "ok"
    assert rep["nodes"]["node3"]["live"] is False


# -------------------------------------------------------------- the CLI
def _analyze(args, cwd):
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "trace_analyze.py"),
         *args],
        cwd=cwd, capture_output=True, text=True, timeout=60,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )


def test_cli_stall_exit_codes(tmp_path):
    _, root = rejoin_stall_world(tmp_path / "bad")
    p = _analyze(["stall", root], str(tmp_path))
    assert p.returncode == 1, p.stderr
    assert "STALLED node3" in p.stdout
    assert "precommit" in p.stdout

    _, ok_root = healthy_world(tmp_path / "good")
    p = _analyze(["stall", ok_root], str(tmp_path))
    assert p.returncode == 0, p.stderr
    assert "OK" in p.stdout


def test_cli_summary_timeline_critical_path(tmp_path):
    _, root = healthy_world(tmp_path)
    p = _analyze(["summary", root], str(tmp_path))
    assert p.returncode == 0, p.stderr
    assert "4 node(s)" in p.stdout

    p = _analyze(["timeline", root, "--height", "2", "--limit", "10"],
                 str(tmp_path))
    assert p.returncode == 0, p.stderr
    assert "p2p.recv" in p.stdout or "consensus.step" in p.stdout

    p = _analyze(["critical-path", root, "--json"], str(tmp_path))
    assert p.returncode == 0, p.stderr
    cp = json.loads(p.stdout)
    assert cp["height"] == 5 and cp["committed"] is True

    p = _analyze(["stall", root, "--json"], str(tmp_path))
    assert p.returncode == 0, p.stderr
    assert json.loads(p.stdout)["status"] == "ok"


# ----------------------------------------------------------------------
# ISSUE 24: the join of a span sink with a profiler trace, on a small
# recorded fixture (tests/data/device_join.json, in the form
# utils/xplane.load gives; ns since the session began)
# ----------------------------------------------------------------------
_DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
_SCOPES = ("ladder.scalar_reduce", "ladder.decompress", "ladder.compare",
           "ladder.double_scalar", "field_mul", "curve_decompress",
           "curve_ladder_sub_mul8")


def _fixture():
    with open(os.path.join(_DATA, "device_join.json")) as f:
        xp = json.load(f)
    recs = traceview.load_records(
        os.path.join(_DATA, "device_join.spans.jsonl"))
    return xp, recs


def test_device_join_books_idle_time_to_the_innermost_program_span():
    xp, recs = _fixture()
    j = traceview.device_join(xp, recs, scopes=_SCOPES)
    # device 0 is the busiest: [1000,6000) + [8000,9500) of [0,12000)
    assert j["busiest"] == "/device:TPU:0" and len(j["devices"]) == 2
    assert j["stretch_s"] == pytest.approx(12000e-9)
    assert j["busy_s"] == pytest.approx(6500e-9)
    assert j["idle_s"] == pytest.approx(5500e-9)
    idle = dict(j["idle_by_span"])
    root = "types.verify_commit"
    # [0,1000): root to 100, commit_items to 900, root to 950, batch_verify
    assert idle[f"{root} > types.commit_items"] == pytest.approx(800e-9)
    assert idle[f"{root} > crypto.batch_verify"] == pytest.approx(50e-9)
    # [6000,8000): root, verdict_wait [6200,7800), root
    assert idle[f"{root} > crypto.verdict_wait"] == pytest.approx(1600e-9)
    # [9500,12000): root to 10000, outside to 10500, then a span that the
    # sink does not hold (still open when it was read): its name alone
    assert idle[traceview.NO_SPAN] == pytest.approx(500e-9)
    assert idle[root] == pytest.approx(
        (100 + 50 + 200 + 200 + 500 + 1500) * 1e-9)
    assert sum(idle.values()) == pytest.approx(5500e-9)
    assert j["idle_named_share"] == pytest.approx(1 - 500 / 5500)
    assert (j["spans_in_trace"], j["spans_joined"]) == (5, 4)
    # without the sink the rows carry the span's name alone
    bare = dict(traceview.device_join(xp, scopes=_SCOPES)["idle_by_span"])
    assert bare["types.commit_items"] == pytest.approx(800e-9)
    assert "types.verify_commit > types.commit_items" not in bare


def test_device_join_books_device_time_to_kernel_scopes():
    xp, recs = _fixture()
    j = traceview.device_join(xp, recs, scopes=_SCOPES)
    busy = dict(j["busy_by_scope"])
    assert busy["ladder.scalar_reduce"] == pytest.approx(1000e-9)
    assert busy["ladder.decompress"] == pytest.approx(1000e-9)
    # the loop has no op_name of its own: it takes the scope most of its
    # children's time carries, and so does the child that has none; the
    # loop keeps only the time its children leave (4000 - 1000 - 2000)
    assert busy["ladder.compare"] == pytest.approx(4000e-9)
    assert busy["ladder.double_scalar"] == pytest.approx(500e-9)  # device 1
    assert busy[traceview.NO_SCOPE] == pytest.approx(500e-9)  # copy.4
    assert sum(busy.values()) == pytest.approx(7000e-9)  # both devices
    assert j["busy_scoped_share"] == pytest.approx(1 - 500 / 7000)
    how = dict(j["busy_booked_by"])
    assert how["own"] == pytest.approx(3500e-9)
    assert how["children"] == pytest.approx(1000e-9)
    assert how["enclosing"] == pytest.approx(2000e-9)
    assert how["none"] == pytest.approx(500e-9)
    ops = dict(j["ops_by_scope"]["ladder.compare"])
    # a pallas kernel goes by its name= (the innermost registered part of
    # its op_name), not by its HLO instruction (tpu_custom_call.3)
    assert ops == {"fusion.7": pytest.approx(2000e-9),
                   "field_mul": pytest.approx(1000e-9),
                   "while.9": pytest.approx(1000e-9)}
    assert dict(j["ops_by_scope"]["ladder.decompress"]) == {
        "curve_decompress": pytest.approx(1000e-9)}
    assert dict(j["ops_by_scope"]["ladder.double_scalar"]) == {
        "curve_ladder_sub_mul8": pytest.approx(500e-9)}


def test_device_join_of_a_stretch_and_its_rendering():
    xp, recs = _fixture()
    j = traceview.device_join(xp, recs, stretch=(2000.0, 8000.0),
                              scopes=_SCOPES)
    assert j["stretch_s"] == pytest.approx(6000e-9)
    assert j["busy_s"] == pytest.approx(4000e-9)
    assert dict(j["busy_by_scope"]) == {
        "ladder.compare": pytest.approx(4000e-9)}
    assert j["busy_scoped_share"] == 1.0
    text = traceview.render_device_join(j)
    assert "types.verify_commit > crypto.verdict_wait" in text
    assert "ladder.compare" in text and "while.9" in text
    with pytest.raises(ValueError):
        traceview.device_join({"start_ns": None, "planes": []})


def test_trace_analyze_device_command(tmp_path, capsys):
    """tools/trace_analyze.py device: refuses without --xplane, reads a
    sink, and says what it cannot find."""
    import importlib.util

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "trace_analyze", os.path.join(repo, "tools", "trace_analyze.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    sink = os.path.join(_DATA, "device_join.spans.jsonl")
    assert tool.main(["device", sink]) == 2
    assert tool.main(["device", sink, "--xplane", str(tmp_path)]) == 2
    err = capsys.readouterr().err
    assert "needs --xplane" in err and "no *.xplane.pb" in err


def test_xplane_reader_on_a_trace_recorded_on_a_v5e():
    """tests/data/v5e_probe.xplane.pb: the profiler's own file from one
    v5e chip (PR 24's first chip call): a tiny jitted program with two
    named scopes, a scan over a pallas kernel named probe_mul3, under two
    TraceAnnotations with span_id 7 and 8. The reader finds what
    jax.profiler.ProfileData hides: each operation's op_name. (PR 28
    renamed the probe's three scope names inside the file to names the
    tree still has, every enclosing length re-encoded; times, operations
    and spans are as recorded.)"""
    from cometbft_tpu.utils import xplane

    xp = xplane.load(os.path.join(_DATA, "v5e_probe.xplane.pb"))
    assert xp["start_ns"] == 1790546504926573769  # wall clock, ns
    planes = {p["name"]: p for p in xp["planes"]}
    assert set(planes) == {"/device:TPU:0", "/host:CPU"}
    dev = planes["/device:TPU:0"]
    assert [m["op"] for m in dev["modules"]] == [
        "jit_prog(2309410719311152149)"]
    assert len(dev["ops"]) == 25
    kernel = [o for o in dev["ops"] if o["op"] == "probe_mul3.3"]
    assert len(kernel) == 8  # one a scan step, inside the while's event
    assert kernel[0]["op_name"] == (
        "jit(prog)/ladder.double_scalar/while/body/closed_call/probe_mul3/"
        "pallas_call")
    loop = next(o for o in dev["ops"] if o["op"] == "while")
    assert loop["op_name"] == ""
    for k in kernel:
        assert loop["start_ns"] <= k["start_ns"]
        assert k["start_ns"] + k["dur_ns"] <= loop["start_ns"] + loop["dur_ns"]
    spans = {sp["span_id"]: sp for sp in planes["/host:CPU"]["spans"]}
    assert spans[7]["name"] == "types.verify_commit"
    assert spans[8]["name"] == "crypto.batch_verify"
    assert spans[7]["start_ns"] <= spans[8]["start_ns"]
    # and the join reads it: the loop and its unnamed fusions go to the
    # scope their kernel names
    j = traceview.device_join(
        xp, scopes=("ladder.decompress", "ladder.double_scalar",
                    "ladder.compare"))
    busy = dict(j["busy_by_scope"])
    assert busy["ladder.double_scalar"] == pytest.approx(
        loop["dur_ns"] * 1e-9, rel=1e-6)
    assert j["busiest"] == "/device:TPU:0"
    assert {"types.verify_commit", "crypto.batch_verify"} <= {
        k for k, _ in j["idle_by_span"]}


# ----------------------------------------------------------------------
# ISSUE 38: threads. A recorded two-thread fixture beside the one-thread
# one (tests/data/device_join_threads.json and .spans.jsonl): a caller
# whose short types.commit_items is open during the device's gap, and the
# drainer, which launches: in no span when the gap begins, then in
# crypto.pack. 1 ns of the profiler's trace is 1 us in the sink.
# ----------------------------------------------------------------------
def _threads_fixture():
    with open(os.path.join(_DATA, "device_join_threads.json")) as f:
        xp = json.load(f)
    recs = traceview.load_records(
        os.path.join(_DATA, "device_join_threads.spans.jsonl"))
    return xp, recs


def test_device_join_books_an_idle_gap_to_the_thread_that_launches():
    xp, recs = _threads_fixture()
    j = traceview.device_join(xp, recs, scopes=_SCOPES)
    # busy [3000,5000) + [9000,10000) of [0,12000)
    assert j["busy_s"] == pytest.approx(3000e-9)
    assert j["idle_s"] == pytest.approx(9000e-9)
    assert (j["threads"], j["launches"]) == (2, 2)
    assert dict(j["idle_by_thread"]) == {
        "verify-sched": pytest.approx(9000e-9)}
    idle = dict(j["idle_by_span"])
    bv = "crypto.sched_coalesce > crypto.batch_verify"
    assert idle == {
        # [0,2000) before the first launch, [10000,12000) behind the last
        "crypto.sched_collect": pytest.approx(4000e-9),
        "crypto.sched_coalesce": pytest.approx(200e-9),
        bv: pytest.approx(800e-9),
        f"{bv} > crypto.device_launch": pytest.approx(1000e-9),
        # the gap [5000,9000): the launching thread in no span to 6000,
        # then mostly in the pack
        traceview.NO_SPAN: pytest.approx(1000e-9),
        f"{bv} > crypto.pack": pytest.approx(2000e-9),
    }
    # nothing goes to the caller's span that happened to be shortest
    assert not any(k.startswith("types.") for k in idle)
    assert j["idle_named_share"] == pytest.approx(1 - 1000 / 9000)
    text = traceview.render_device_join(j)
    assert "verify-sched" in text and "host_device_skew_ms: 0.000" in text
    # without the sink the threads are still the trace's lines
    bare = traceview.device_join(xp, scopes=_SCOPES)
    assert dict(bare["idle_by_span"])["crypto.pack"] == pytest.approx(2000e-9)
    assert dict(bare["idle_by_thread"]) == {
        "thread 22": pytest.approx(9000e-9)}
    # ... and without a launch to go by, the old one-thread reading: the
    # shortest span over the stretch, whichever thread's
    for sp in xp["planes"][1]["spans"]:
        if sp["name"] == "crypto.device_launch":
            sp["name"] = "crypto.batch_verify"
    old = dict(traceview.device_join(xp, recs, scopes=_SCOPES)["idle_by_span"])
    assert old["types.verify_commit > types.commit_items"] == pytest.approx(
        800e-9)


def test_the_one_thread_fixture_reads_as_before_and_reports_no_skew():
    xp, recs = _fixture()
    j = traceview.device_join(xp, recs, scopes=_SCOPES)
    assert (j["threads"], j["launches"]) == (1, 0)
    assert j["host_device_skew_ms"] is None
    assert dict(j["idle_by_thread"]) == {
        "every thread": pytest.approx(5500e-9)}
    assert "no launch found" in traceview.render_device_join(j)
    # one thread that launches reads the same to the digit
    for p in xp["planes"]:
        for sp in p.get("spans", ()):
            if sp["name"] == "crypto.batch_verify":
                sp["name"] = "crypto.device_launch"
    k = traceview.device_join(xp, recs, scopes=_SCOPES)
    assert k["launches"] == 1 and k["idle_s"] == j["idle_s"]
    assert sorted(v for _n, v in k["idle_by_span"]) == sorted(
        v for _n, v in j["idle_by_span"])


def test_host_device_skew_moves_the_devices_timeline():
    """A program that begins before the annotation that launched it: the
    clocks disagree by at least that, and the device's timeline moves."""
    xp, recs = _threads_fixture()
    dev = xp["planes"][0]
    for ev in dev["ops"] + dev["modules"]:
        ev["start_ns"] -= 1000.0  # launches 2500, 8300; runs 2000, 8000
    j = traceview.device_join(xp, recs, scopes=_SCOPES)
    assert j["host_device_skew_ms"] == pytest.approx(500e-6)
    assert j["skew_pairs"] == 2
    # moved later by 500: busy [2500,4500) + [8500,9500)
    assert j["busy_s"] == pytest.approx(3000e-9)
    assert dict(j["idle_by_span"])["crypto.sched_collect"] == pytest.approx(
        (2000 + 2500) * 1e-9)
    # a second program behind each launch's first (a column decompressed)
    # begins where that one ends: queued, it answers no launch, and the
    # launches still find their own
    for m in list(dev["modules"]):
        dev["modules"].append(
            dict(m, start_ns=m["start_ns"] + m["dur_ns"] + 1.0))
    k = traceview.device_join(xp, recs, scopes=_SCOPES)
    assert k["host_device_skew_ms"] == pytest.approx(500e-6)
    assert k["skew_pairs"] == 2
    # a run queued behind another just before a launch is not that
    # launch's, however near: 8000 begins where [5000,7999) ends, so the
    # second launch (8300) takes the first run inside its own interval
    dev["modules"] = [dict(op="p", start_ns=2000.0, dur_ns=2000.0),
                      dict(op="p", start_ns=5000.0, dur_ns=2999.0),
                      dict(op="p", start_ns=8000.0, dur_ns=1000.0),
                      dict(op="p", start_ns=9000.5, dur_ns=1000.0)]
    k = traceview.device_join(xp, recs, scopes=_SCOPES)
    assert k["host_device_skew_ms"] == pytest.approx(500e-6)
    # no run at all: no skew, nothing moves
    dev["modules"] = []
    k = traceview.device_join(xp, recs, scopes=_SCOPES)
    assert k["host_device_skew_ms"] is None and k["skew_pairs"] == 0
    # as recorded (less the 1000): busy [2000,4000) + [8000,9000)
    assert k["busy_s"] == pytest.approx(3000e-9)
    assert dict(k["idle_by_span"])["crypto.sched_collect"] == pytest.approx(
        (2000 + 2700) * 1e-9)


def test_host_device_skew_on_the_trace_recorded_on_a_v5e():
    """The probe's program began 0.96 ms before the annotation around
    its call (PERF.md section 7); the reader keeps each span's line."""
    from cometbft_tpu.utils import xplane

    xp = xplane.load(os.path.join(_DATA, "v5e_probe.xplane.pb"))
    spans = [sp for p in xp["planes"] for sp in p.get("spans", ())]
    assert len({sp["line"] for sp in spans}) == 1 and spans[0]["line"]
    # the probe wrote no crypto.device_launch: as recorded nothing pairs
    assert traceview.device_join(xp)["host_device_skew_ms"] is None
    for sp in spans:
        if sp["name"] == "crypto.batch_verify":
            sp["name"] = "crypto.device_launch"
    j = traceview.device_join(xp)
    assert j["host_device_skew_ms"] == pytest.approx(0.964, abs=0.001)
    assert (j["threads"], j["launches"], j["skew_pairs"]) == (1, 1, 1)


def test_trace_analyze_threads_on_a_recorded_two_thread_sink(capsys):
    import importlib.util

    from cometbft_tpu.utils.trace import WAIT_SPANS

    sink = os.path.join(_DATA, "device_join_threads.spans.jsonl")
    rows = traceview.thread_table(traceview.load_records(sink), WAIT_SPANS)
    by = {r["thread"]: r for r in rows}
    assert set(by) == {"caller-0", "verify-sched"}
    d, c = by["verify-sched"], by["caller-0"]
    # the drainer: two collects, two dispatches and their children (the
    # crypto.sched_wait it wrote for a caller has no self time and no
    # part); on a CPU by its four roots, waiting in its collects
    assert (d["tid"], d["spans"]) == (502, 9)
    assert d["window_ms"] == 12.0 and d["in_spans_ms"] == 9.4
    assert (d["cpu_ms"], d["wait_ms"], d["other_ms"]) == (2.6, 4.7, 2.1)
    assert d["outside_ms"] == 2.6
    # the caller: its wait for the verdict is chosen, the rest of what
    # it did not run is the interpreter's
    assert (c["tid"], c["spans"], c["in_spans_ms"]) == (501, 3, 12.0)
    assert (c["cpu_ms"], c["wait_ms"], c["other_ms"]) == (1.5, 5.0, 5.5)
    for r in rows:
        assert r["cpu_ms"] + r["wait_ms"] + r["other_ms"] == pytest.approx(
            r["in_spans_ms"])
    # without the tuple no wall time is a chosen wait
    assert traceview.thread_table(
        traceview.load_records(sink))[0]["wait_ms"] == 0.0
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "trace_analyze", os.path.join(repo, "tools", "trace_analyze.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    assert tool.main(["threads", sink]) == 0
    text = capsys.readouterr().out
    assert "verify-sched" in text and "caller-0" in text
    assert tool.main(["threads", sink, "--json"]) == 0
    assert json.loads(capsys.readouterr().out) == rows
    # a sink of the tree before ISSUE 38 has no thread to tell
    assert tool.main(["threads", os.path.join(
        _DATA, "device_join.spans.jsonl")]) == 2
    assert "no span with a thread" in capsys.readouterr().err
