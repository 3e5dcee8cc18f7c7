"""Metrics, structured logging, and fail-point crash injection
(reference metrics.go bundles, libs/log, internal/fail)."""

import json
import os
import re
import subprocess
import sys
import time
import urllib.error
import urllib.request

import pytest

from cometbft_tpu.utils import log as cmtlog
from cometbft_tpu.utils import metrics as M
from cometbft_tpu.utils.metrics import (
    Counter,
    Gauge,
    Histogram,
    MetricsServer,
    Registry,
)

# ------------------------------------------------------- mini parser
# A small but honest prometheus text-format parser: enough to round-trip
# what Registry.expose_text() emits (HELP/TYPE metadata, escaped label
# values, histogram bucket series) and catch format regressions.
_SAMPLE_RE = re.compile(
    r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(?:\{(.*)\})?\s+(\S+)$"
)
_LABEL_RE = re.compile(r'([a-zA-Z_][a-zA-Z0-9_]*)="((?:[^"\\]|\\.)*)"')


def _unescape(v: str) -> str:
    out, i = [], 0
    while i < len(v):
        if v[i] == "\\" and i + 1 < len(v):
            out.append({"n": "\n", '"': '"', "\\": "\\"}.get(
                v[i + 1], v[i + 1]))
            i += 2
        else:
            out.append(v[i])
            i += 1
    return "".join(out)


def parse_exposition(text: str):
    """-> (helps, types, samples) with samples keyed
    (name, ((label, value), ...))."""
    helps, types, samples = {}, {}, {}
    for line in text.splitlines():
        if not line:
            continue
        if line.startswith("# HELP "):
            name, _, h = line[len("# HELP "):].partition(" ")
            helps[name] = h
            continue
        if line.startswith("# TYPE "):
            name, _, t = line[len("# TYPE "):].partition(" ")
            types[name] = t
            continue
        if line.startswith("#"):
            continue
        m = _SAMPLE_RE.match(line)
        assert m, f"unparseable sample line: {line!r}"
        name, raw_labels, value = m.groups()
        labels = tuple(
            (k, _unescape(v))
            for k, v in _LABEL_RE.findall(raw_labels or "")
        )
        samples[(name, labels)] = float(value)
    return helps, types, samples


def test_metrics_exposition_format():
    reg = Registry()
    c = reg.counter("consensus", "total_txs", "Total txs")
    g = reg.gauge("p2p", "peers", "Peers", labels=("dir",))
    h = reg.histogram("state", "block_processing_time", "ApplyBlock",
                      buckets=(0.1, 1.0))
    c.inc(); c.inc(2)
    g.set(4, "inbound"); g.set(2, "outbound")
    h.observe(0.05); h.observe(0.5); h.observe(5)
    text = reg.expose_text()
    assert "# TYPE cometbft_consensus_total_txs counter" in text
    assert "cometbft_consensus_total_txs 3.0" in text
    assert 'cometbft_p2p_peers{dir="inbound"} 4' in text
    assert 'cometbft_state_block_processing_time_bucket{le="0.1"} 1' in text
    assert 'cometbft_state_block_processing_time_bucket{le="+Inf"} 3' in text
    assert "cometbft_state_block_processing_time_count 3" in text


def test_metrics_server_serves_text():
    reg = Registry()
    reg.counter("test", "hits", "").inc(7)
    srv = MetricsServer(registry=reg)
    srv.start()
    try:
        host, port = srv.addr
        body = urllib.request.urlopen(
            f"http://{host}:{port}/metrics", timeout=5
        ).read().decode()
        assert "cometbft_test_hits 7.0" in body
    finally:
        srv.stop()


def test_metrics_exposition_round_trip():
    """expose_text() -> mini parser -> the exact values and label
    strings that went in (including prometheus escape sequences)."""
    reg = Registry()
    c = reg.counter("consensus", "total_txs", "Total transactions seen")
    g = reg.gauge("p2p", "peer_height", "Peer height", labels=("peer",))
    h = reg.histogram("crypto", "batch_size", "Batch sizes",
                      buckets=(1, 64, 256))
    c.inc(5)
    nasty = 'quote"back\\slash\nnewline'
    g.set(17, nasty)
    g.set(9, "plainpeer")
    for v in (1, 2, 200, 999):
        h.observe(v)
    helps, types, samples = parse_exposition(reg.expose_text())

    assert types["cometbft_consensus_total_txs"] == "counter"
    assert types["cometbft_p2p_peer_height"] == "gauge"
    assert types["cometbft_crypto_batch_size"] == "histogram"
    assert helps["cometbft_consensus_total_txs"] == (
        "Total transactions seen"
    )
    assert samples[("cometbft_consensus_total_txs", ())] == 5.0
    # label escaping round-trips bytes-for-bytes
    assert samples[
        ("cometbft_p2p_peer_height", (("peer", nasty),))
    ] == 17.0
    assert samples[
        ("cometbft_p2p_peer_height", (("peer", "plainpeer"),))
    ] == 9.0
    # histogram: cumulative buckets, +Inf == _count, _sum preserved
    buckets = {
        dict(labels)["le"]: v
        for (name, labels), v in samples.items()
        if name == "cometbft_crypto_batch_size_bucket"
    }
    assert buckets == {"1": 1.0, "64": 2.0, "256": 3.0, "+Inf": 4.0}
    cum = [buckets[le] for le in ("1", "64", "256", "+Inf")]
    assert cum == sorted(cum), "bucket counts must be cumulative"
    assert samples[("cometbft_crypto_batch_size_count", ())] == 4.0
    assert samples[("cometbft_crypto_batch_size_sum", ())] == 1202.0


def test_registry_duplicate_name_guard():
    reg = Registry()
    reg.counter("consensus", "height", "first registration")
    with pytest.raises(ValueError, match="already registered"):
        reg.counter("consensus", "height", "duplicate")
    with pytest.raises(ValueError, match="already registered"):
        # a different kind under the same name is just as wrong
        reg.gauge("consensus", "height", "duplicate as gauge")


def test_metrics_server_404_and_405():
    srv = MetricsServer(registry=Registry())
    srv.start()
    try:
        host, port = srv.addr
        base = f"http://{host}:{port}"
        with pytest.raises(urllib.error.HTTPError) as e404:
            urllib.request.urlopen(f"{base}/other", timeout=5)
        assert e404.value.code == 404
        with pytest.raises(urllib.error.HTTPError) as e405:
            urllib.request.urlopen(f"{base}/metrics", data=b"x", timeout=5)
        assert e405.value.code == 405
        # the real path still answers
        resp = urllib.request.urlopen(f"{base}/metrics", timeout=5)
        assert resp.status == 200
    finally:
        srv.stop()


def test_reset_bundles_gives_fresh_singletons():
    cm = M.consensus_metrics()
    cm.height.set(42)
    assert M.consensus_metrics() is cm
    text = M.DEFAULT_REGISTRY.expose_text()
    assert "cometbft_consensus_height 42" in text
    reg_before = M.DEFAULT_REGISTRY
    M.reset_bundles()
    # same Registry object (live MetricsServers keep serving it) but
    # emptied, and the next accessor call builds a fresh bundle
    assert M.DEFAULT_REGISTRY is reg_before
    assert M.consensus_metrics() is not cm
    assert M.consensus_metrics().height.values() == {}


def test_metrics_lint_all_bundles_driven():
    """tools/metrics_lint.py: every registered metric has a driver
    call site in the package (a zero-forever metric fails tier 1)."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    p = subprocess.run(
        [sys.executable, os.path.join(repo, "tools", "metrics_lint.py")],
        capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert p.returncode == 0, p.stderr


def test_trace_lint_registry_matches_call_sites():
    """tools/trace_lint.py: every emitted span name is declared in
    trace.SPAN_REGISTRY and every declared name has a live call site
    (the flight-recorder analyzers key on these literals)."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    p = subprocess.run(
        [sys.executable, os.path.join(repo, "tools", "trace_lint.py")],
        capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert p.returncode == 0, p.stderr


def test_trace_lint_covers_the_span_tree_and_the_kernel_scopes(tmp_path):
    """ISSUE 24's names are under the lint: the spans of the two measured
    paths, the tracer's own two records and trace.KERNEL_SCOPES (every
    jax.named_scope and pallas_call name in ops/), both directions."""
    import importlib.util
    import shutil

    from cometbft_tpu.utils.trace import KERNEL_SCOPES, SPAN_REGISTRY

    for name in ("trace.clock", "runtime.gc_pause", "types.verify_commit",
                 "types.commit_items", "types.verify_items_fill",
                 "crypto.materialize", "crypto.pack",
                 "crypto.device_launch", "crypto.native_verify",
                 "crypto.verdict_wait", "blocksync.replay",
                 "blocksync.window_load", "blocksync.window_queue",
                 "blocksync.window_fill", "blocksync.window_resolve",
                 "blocksync.window_apply"):
        assert name in SPAN_REGISTRY, name
    for name in ("ladder.decompress", "ladder.a_hi", "ladder.scalar_reduce",
                 "ladder.double_scalar", "ladder.compare",
                 "curve_decompress", "curve_ladder_sub_mul8",
                 "curve_mul_2_128", "field_mul", "field_sq"):
        assert name in KERNEL_SCOPES, name
    # the lint reads ops/ for scopes: a scope it does not know, and one
    # nothing uses, both fail it
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "trace_lint", os.path.join(repo, "tools", "trace_lint.py"))
    lint = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(lint)
    assert lint.main() == 0
    found = {next(filter(None, m.groups())) for m in lint.SCOPE_RE.finditer(
        'with jax.named_scope("ladder.new_phase"):\n'
        '    pl.pallas_call(k, name="new_kernel")\n'
        '    _pallas_binop(_mul_kernel, "field_new", a)\n')}
    assert found == {"ladder.new_phase", "new_kernel", "field_new"}
    assert not lint._agree("kernel scopes", "KERNEL_SCOPES",
                           {"ladder.new_phase": ["ops/curve.py"]}, KERNEL_SCOPES)


def test_trace_lint_holds_the_threads_names_and_the_wait_spans(monkeypatch,
                                                               capsys):
    """ISSUE 38: trace.thread and crypto.sched_collect are registered
    and in use, the eight spans nothing read are gone from the registry
    and from the tree (the lint is green both ways), and WAIT_SPANS may
    name registered spans only."""
    import importlib.util

    from cometbft_tpu.utils import trace

    assert {"trace.thread", "crypto.sched_collect"} <= set(
        trace.SPAN_REGISTRY)
    for name in ("consensus.cert_aggregate", "crypto.bls_aggregate",
                 "crypto.msm_opening", "p2p.zero_copy_send",
                 "light.mmr_append", "light.serve_proof",
                 "da.serve_sample", "da.sample_verify"):
        assert name not in trace.SPAN_REGISTRY, name
    for name in ("crypto.commit_partition", "crypto.mesh_submit",
                 "da.encode", "da.pc_commit", "watchtower.audit"):
        assert name in trace.SPAN_REGISTRY, name
    assert "crypto.sched_collect" in trace.WAIT_SPANS
    assert set(trace.WAIT_SPANS) <= set(trace.SPAN_REGISTRY)
    assert "collect_ms" not in trace.SPAN_REGISTRY["crypto.sched_coalesce"]
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "trace_lint", os.path.join(repo, "tools", "trace_lint.py"))
    lint = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(lint)
    assert lint.main() == 0
    # the tracer's own three records are call sites the lint sees
    assert {m.group(1) for m in lint.TRACER_RE.finditer(
        'event("trace.clock", a=1)\n_envelope("trace.thread", "event")\n'
        '_Span("runtime.gc_pause", {})')} == {
        "trace.clock", "trace.thread", "runtime.gc_pause"}
    capsys.readouterr()
    monkeypatch.setattr(trace, "WAIT_SPANS",
                        trace.WAIT_SPANS + ("crypto.no_such_wait",))
    assert lint.main() == 1
    assert "crypto.no_such_wait" in capsys.readouterr().err


def test_logger_levels_and_fields():
    records = []
    cmtlog.set_sink(lambda level, msg, fields: records.append((level, msg, fields)))
    try:
        cmtlog.set_level("consensus:debug,p2p:none,*:info")
        c = cmtlog.logger("consensus").with_fields(height=5)
        p = cmtlog.logger("p2p")
        o = cmtlog.logger("other")
        c.debug("step", round=1)
        p.error("dropped")  # p2p: none -> suppressed
        o.debug("noise")    # default info -> suppressed
        o.info("kept")
        assert len(records) == 2
        lvl, msg, fields = records[0]
        assert msg == "step" and fields["height"] == 5 and fields["round"] == 1
        assert records[1][1] == "kept"
    finally:
        cmtlog.set_sink(cmtlog._Config._stderr_sink)
        cmtlog.set_level("info")


def _mk_obs_node(tmp_path, name, key, genesis, peers="",
                 instrument=False):
    from cometbft_tpu.abci.kvstore import KVStoreApp
    from cometbft_tpu.config import Config
    from cometbft_tpu.node import Node

    home = os.path.join(str(tmp_path), name)
    os.makedirs(os.path.join(home, "config"), exist_ok=True)
    genesis.save(os.path.join(home, "config/genesis.json"))
    with open(os.path.join(home, "config/priv_validator_key.json"), "w") as f:
        json.dump(key, f)
    cfg = Config()
    cfg.base.home = home
    cfg.base.moniker = name
    cfg.base.db_backend = "mem"
    cfg.base.crypto_backend = "cpu"
    cfg.p2p.laddr = "tcp://127.0.0.1:0"
    cfg.rpc.laddr = "tcp://127.0.0.1:0"
    cfg.p2p.persistent_peers = peers
    cfg.consensus.timeout_propose = 0.6
    cfg.consensus.timeout_propose_delta = 0.2
    cfg.consensus.timeout_prevote = 0.3
    cfg.consensus.timeout_prevote_delta = 0.1
    cfg.consensus.timeout_precommit = 0.3
    cfg.consensus.timeout_precommit_delta = 0.1
    cfg.consensus.timeout_commit = 0.1
    if instrument:
        cfg.instrumentation.prometheus = True
        cfg.instrumentation.prometheus_listen_addr = "127.0.0.1:0"
        cfg.instrumentation.trace_sink = "data/trace.jsonl"
    return Node(cfg, app=KVStoreApp())


def test_node_serves_metrics_and_trace(tmp_path):
    """Full-node observability: a two-validator net with
    instrumentation on exposes live series from every subsystem on
    /metrics while it commits (2-signature commits cross the
    batch-verify threshold, so the crypto dispatch and per-peer gauges
    are all driven), writes consensus/ApplyBlock/crypto spans to the
    trace sink, and serves the tail over the dump_trace RPC."""
    import time as _time

    from cometbft_tpu.privval import FilePV
    from cometbft_tpu.types import Timestamp
    from cometbft_tpu.types.genesis import GenesisDoc, GenesisValidator
    from cometbft_tpu.utils import trace

    pvs = [FilePV.generate(None, None) for _ in range(2)]
    genesis = GenesisDoc(
        chain_id="obs-chain",
        genesis_time=Timestamp(1_700_000_000, 0),
        validators=[
            GenesisValidator(pv.pub_key().bytes(), 10, f"v{i}")
            for i, pv in enumerate(pvs)
        ],
    )
    keys = [
        {
            "address": pv.pub_key().address().hex(),
            "pub_key": pv.pub_key().bytes().hex(),
            "priv_key": pv._priv.bytes().hex(),
        }
        for pv in pvs
    ]
    n = _mk_obs_node(tmp_path, "n0", keys[0], genesis, instrument=True)
    n.start()
    phost, pport = n.listen_addr
    n1 = _mk_obs_node(tmp_path, "n1", keys[1], genesis,
                      peers=f"{phost}:{pport}")
    n1.start()
    home = n.config.base.home
    try:
        deadline = _time.monotonic() + 150
        while (_time.monotonic() < deadline
               and n.consensus.sm_state.last_block_height < 3):
            _time.sleep(0.2)
        assert n.consensus.sm_state.last_block_height >= 3, "chain stalled"

        host, port = n.metrics_server.addr
        text = urllib.request.urlopen(
            f"http://{host}:{port}/metrics", timeout=5
        ).read().decode()
        _helps, types, samples = parse_exposition(text)
        # live series from >= 6 subsystems
        height = samples[("cometbft_consensus_height", ())]
        assert height >= 3
        assert types["cometbft_consensus_step_duration_seconds"] == (
            "histogram"
        )
        assert samples[
            ("cometbft_consensus_step_duration_seconds_count",
             (("step", "COMMIT"),))
        ] >= 1
        assert ("cometbft_mempool_size", ()) in samples
        assert ("cometbft_p2p_peers", ()) in samples
        assert samples[
            ("cometbft_state_block_processing_time_count", ())
        ] >= 1
        assert ("cometbft_blocksync_syncing", ()) in samples
        # per-peer height gauge (VERDICT #3's rejoin-stall data)
        peer_heights = [
            labels for (name, labels) in samples
            if name == "cometbft_p2p_peer_height" and labels
        ]
        assert peer_heights, "connected peer must drive peer_height gauge"
        # 2-sig commits cross BATCH_VERIFY_THRESHOLD: a batch path
        # ("cpu"/"native") fires, plus "single" for gossiped votes
        crypto_paths = {
            dict(labels).get("path")
            for (name, labels) in samples
            if name == "cometbft_crypto_path_selected_total"
        }
        assert crypto_paths & {"cpu", "native"}, crypto_paths

        # the trace sink holds consensus-step, ApplyBlock and crypto
        # batch-verify spans
        sink = os.path.join(home, "data", "trace.jsonl")
        recs = [json.loads(line) for line in open(sink, encoding="utf-8")]
        steps = [r for r in recs if r["name"] == "consensus.step"]
        assert steps and all("height" in r and "round" in r for r in steps)
        assert any(r["name"] == "state.apply_block" for r in recs)
        crypto_spans = [
            r for r in recs if r["name"] == "crypto.batch_verify"
        ]
        assert crypto_spans, "batch verification must be traced"
        assert all(
            r["kind"] == "span" and r["path"] and r["n"] >= 1
            for r in crypto_spans
        )

        # flight-recorder records: node identity stamped once, and the
        # p2p wire hooks classified consensus messages in BOTH
        # directions with height/round and the sender/receiver peer id
        boots = [r for r in recs if r["name"] == "node.boot"]
        assert boots and boots[0]["node_id"] == n.node_key.node_id()
        assert any(r.get("node") == n.node_key.node_id() for r in recs)
        for direction in ("p2p.send", "p2p.recv"):
            wire = [r for r in recs if r["name"] == direction]
            assert wire, f"no {direction} records"
            assert all(
                "peer" in r and "msg" in r and "height" in r for r in wire
            )
        assert {r["msg"] for r in recs if r["name"] == "p2p.recv"} & {
            "vote", "proposal", "block_part", "new_round_step",
        }

        # dump_trace RPC serves the same tail (GET-URI dispatch)
        rhost, rport = n.rpc_addr
        out = json.loads(urllib.request.urlopen(
            f"http://{rhost}:{rport}/dump_trace?n=50", timeout=5
        ).read())
        res = out["result"]
        assert res["enabled"] is True
        assert res["path"].endswith("trace.jsonl")
        assert any(
            r["name"].startswith("consensus.") for r in res["records"]
        )
        # ?name= substring filter narrows to the wire hooks
        out = json.loads(urllib.request.urlopen(
            f"http://{rhost}:{rport}/dump_trace?n=20&name=p2p.recv",
            timeout=5,
        ).read())
        filt = out["result"]["records"]
        assert filt and all(r["name"] == "p2p.recv" for r in filt)
    finally:
        n1.stop()
        n.stop()
        trace.disable()


_CRASH_SCRIPT = r"""
import os, sys, tempfile
sys.path.insert(0, os.getcwd())
import jax
jax.config.update("jax_platforms", "cpu")
from cometbft_tpu.consensus.net import FAST_TIMEOUTS, InProcessNetwork

d = sys.argv[1]
net = InProcessNetwork(1, d, timeouts=FAST_TIMEOUTS)
net.start()
net.wait_for_height(3, timeout=60)
print("reached-3", flush=True)
# arm the fail point only now (the target env var is read per call):
# the 2nd fail_point() after this line kills the process mid-height
os.environ["FAIL_TEST_INDEX"] = "2"
net.wait_for_height(6, timeout=60)
print("reached-6", flush=True)
net.stop()
"""

_RECOVER_SCRIPT = r"""
import os, sys
sys.path.insert(0, os.getcwd())
import jax
jax.config.update("jax_platforms", "cpu")
os.environ.pop("FAIL_TEST_INDEX", None)
from cometbft_tpu.consensus.net import FAST_TIMEOUTS, InProcessNetwork

d = sys.argv[1]
net = InProcessNetwork(1, d, timeouts=FAST_TIMEOUTS)
net.start()
net.wait_for_height(6, timeout=60)
print("recovered-to-6", flush=True)
net.stop()
"""


def test_fail_point_crash_and_wal_recovery(tmp_path):
    """Kill the node at an injected ApplyBlock crash point, then restart
    WITHOUT the fail point: WAL + handshake replay must recover and keep
    committing (reference internal/consensus/replay_test.go crash table)."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("FAIL_TEST_INDEX", None)  # armed inside the script after h=3
    d = str(tmp_path)
    p1 = subprocess.run(
        [sys.executable, "-c", _CRASH_SCRIPT, d],
        capture_output=True, text=True, timeout=300, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert "reached-3" in p1.stdout, p1.stderr[-2000:]
    assert p1.returncode == 1, (
        f"process should die at the fail point, rc={p1.returncode}\n"
        f"{p1.stderr[-2000:]}"
    )

    p2 = subprocess.run(
        [sys.executable, "-c", _RECOVER_SCRIPT, d],
        capture_output=True, text=True, timeout=300, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert p2.returncode == 0, p2.stderr[-2000:]
    assert "recovered-to-6" in p2.stdout


def test_healthz_liveness_follows_height_advance():
    """/healthz: 200 while consensus height advances within the window
    (server start counts as an advance — boot grace), 503 once the
    height freezes past it, 200 again when it moves."""
    height = {"v": 0.0}
    srv = MetricsServer(registry=Registry(), health_window_s=0.3,
                        height_fn=lambda: height["v"])
    srv.start()
    try:
        host, port = srv.addr
        url = f"http://{host}:{port}/healthz"
        body = json.loads(urllib.request.urlopen(url, timeout=5).read())
        assert body["status"] == "ok"
        time.sleep(0.45)  # no advance past the window -> stalled
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(url, timeout=5)
        assert ei.value.code == 503
        assert json.loads(ei.value.read())["status"] == "stalled"
        height["v"] = 7.0  # consensus moved: liveness restored
        body = json.loads(urllib.request.urlopen(url, timeout=5).read())
        assert body["status"] == "ok"
        assert body["height"] == 7.0
    finally:
        srv.stop()


def test_exemplar_exposition_is_opt_in():
    """Histogram exemplars surface only on /metrics?exemplars=1 in
    OpenMetrics `# {trace_id=...}` syntax; the default classic-format
    scrape stays byte-compatible (strict parsers reject suffixes)."""
    reg = Registry()
    h = reg.histogram("mempool", "tx_stage_seconds_t", "stage spans",
                      labels=("stage",), buckets=(0.1, 1.0))
    h.observe(0.05, "verify", exemplar="00aa11bb22cc33dd")
    srv = MetricsServer(registry=reg)
    srv.start()
    try:
        host, port = srv.addr
        plain = urllib.request.urlopen(
            f"http://{host}:{port}/metrics", timeout=5).read().decode()
        assert "# {" not in plain
        parse_exposition(plain)  # strict classic parser stays happy
        om = urllib.request.urlopen(
            f"http://{host}:{port}/metrics?exemplars=1", timeout=5
        ).read().decode()
        assert 'trace_id="00aa11bb22cc33dd"' in om
        assert 'le="0.1"' in om
    finally:
        srv.stop()


def test_metrics_doc_is_current():
    """tools/metrics_doc.py --check: METRICS.md is generated from the
    registered bundles; a new or renamed metric without a regenerated
    doc fails tier 1 here."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    p = subprocess.run(
        [sys.executable, os.path.join(repo, "tools", "metrics_doc.py"),
         "--check"],
        capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert p.returncode == 0, p.stderr + p.stdout
