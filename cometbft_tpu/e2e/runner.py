"""Manifest-driven e2e testnet runner (reference test/e2e/runner).

One subprocess per node (`python -m cometbft_tpu.cli start`), real TCP
p2p + RPC. The runner generates homes, tightens consensus timeouts for
test speed, drives a tx load generator against the RPC, applies the
manifest's perturbation schedule keyed on observed chain height
(reference test/e2e/runner/perturb.go:31-90 — kill -9, restart,
SIGSTOP), and finally checks black-box invariants over RPC only:
every pair of nodes agrees on the block hash and app hash at every
common committed height, and the chain reached the target height
(reference test/e2e/tests/block_test.go TestBlock_Header).
"""

from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import threading
import time
import urllib.request

from .manifest import Manifest


class E2EError(Exception):
    pass


def _rpc(port: int, method: str, params: dict | None = None, timeout=3.0):
    body = json.dumps(
        {"jsonrpc": "2.0", "id": 1, "method": method, "params": params or {}}
    ).encode()
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}",
        data=body,
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        out = json.loads(resp.read())
    if "error" in out:
        raise E2EError(f"rpc {method}: {out['error']}")
    return out["result"]


class _ProcNode:
    def __init__(self, name: str, home: str, rpc_port: int,
                 command: list[str] | None = None, metrics_port: int = 0):
        self.name = name
        self.home = home
        self.rpc_port = rpc_port
        self.metrics_port = metrics_port
        self.proc: subprocess.Popen | None = None
        self.log = open(os.path.join(home, "node.log"), "ab")
        # per-node env overrides applied at (re)start — the "upgrade"
        # perturbation restarts a node as a newer build via
        # COMETBFT_TPU_VERSION
        self.extra_env: dict[str, str] = {}
        # alternate interpreter/module invocation (e.g. an OLD build
        # pip-installed in a venv — reference manifest.go Version);
        # None runs the current repo's build. The "upgrade"
        # perturbation clears this to swap builds mid-run.
        self.command = command
        # true once the node has run under a build that predates the
        # ABCI call log: its log then starts mid-life, so the grammar
        # checker must not demand a clean-start first execution
        self.pre_log_history = False

    def start(self) -> None:
        if self.log.closed:  # relaunch after stop_all closed the log
            self.log = open(os.path.join(self.home, "node.log"), "ab")
        env = dict(os.environ)
        # subprocess nodes run the CPU backend: many processes sharing
        # one test machine must not all grab the accelerator
        env["JAX_PLATFORMS"] = "cpu"
        env.update(self.extra_env)
        base = self.command or [sys.executable, "-m", "cometbft_tpu.cli"]
        self.proc = subprocess.Popen(
            [*base, "--home", self.home, "start"],
            stdout=self.log, stderr=self.log, env=env,
        )

    def height(self) -> int:
        try:
            st = _rpc(self.rpc_port, "status")
            return int(st["sync_info"]["latest_block_height"])
        except Exception:  # noqa: BLE001 — down/unreachable
            return -1

    def kill9(self) -> None:
        if self.proc is not None:
            self.proc.kill()
            self.proc.wait()
            self.proc = None

    def stop(self) -> None:
        if self.proc is None:
            return
        self.proc.send_signal(signal.SIGTERM)
        try:
            self.proc.wait(timeout=10)
        except subprocess.TimeoutExpired:
            self.proc.kill()
            self.proc.wait()
        self.proc = None

    def pause(self) -> None:
        if self.proc is not None:
            self.proc.send_signal(signal.SIGSTOP)

    def resume(self) -> None:
        if self.proc is not None:
            self.proc.send_signal(signal.SIGCONT)


class Runner:
    def __init__(self, manifest: Manifest, workdir: str,
                 starting_port: int = 0,
                 node_commands: dict[str, list[str]] | None = None,
                 trace: bool = True):
        self.manifest = manifest
        self.workdir = workdir
        # every node records a flight-recorder sink by default; the
        # overhead harness (tools/trace_overhead.py) turns it off for
        # its baseline world
        self.trace = trace
        # three ports per node: p2p (+2i), rpc (+2i+1), and a metrics
        # listener block after the p2p/rpc range (+2N+i)
        self.starting_port = starting_port or self._free_port_base(
            3 * len(manifest.nodes)
        )
        # per-node alternate build invocations (mixed-version nets);
        # environment-specific, so a Runner argument rather than a
        # manifest field
        self.node_commands = node_commands or {}
        self.nodes: dict[str, _ProcNode] = {}
        self._load_stop = threading.Event()
        self._load_thread: threading.Thread | None = None
        self.txs_sent = 0
        # in-process streaming auditor, attached over the nodes' feeds
        # when manifest.watchtower is set (watchtower/auditor.py)
        self.watchtower = None

    @staticmethod
    def _free_port_base(count: int) -> int:
        import socket

        socks = []
        ports = []
        for _ in range(count):
            s = socket.socket()
            s.bind(("127.0.0.1", 0))
            ports.append(s.getsockname()[1])
            socks.append(s)
        for s in socks:
            s.close()
        return min(ports) if ports else 26656

    # ------------------------------------------------------------- setup
    def setup(self) -> None:
        from ..cli import main as cli_main
        from ..config import Config

        m = self.manifest
        # homes are positional (node{i} = m.nodes[i]) and the testnet
        # generator emits seed homes after validator homes, so seed
        # specs must come last in the manifest
        n_seeds = sum(1 for s in m.nodes if s.seed)
        n_validators = len(m.nodes) - n_seeds
        if any(s.seed for s in m.nodes[:n_validators]):
            raise E2EError("seed nodes must come last in manifest.nodes")
        if any(s.seed and (s.start_at or s.state_sync) for s in m.nodes):
            raise E2EError("seed nodes start with the net (no late join)")
        rc = cli_main([
            "testnet", "--v", str(n_validators),
            "--seed-nodes", str(n_seeds),
            "--output", self.workdir,
            "--chain-id", m.chain_id,
            "--starting-port", str(self.starting_port),
            "--key-type", m.key_type,
        ])
        if rc != 0:
            raise E2EError("testnet generation failed")
        for i, spec in enumerate(m.nodes):
            home = os.path.join(self.workdir, f"node{i}")
            if m.vote_extensions_enable_height > 0:
                # params ride the genesis document to every process node
                # (reference types/genesis.go GenesisDoc.ConsensusParams)
                from ..state.types import ABCIParams, ConsensusParams
                from ..types.genesis import GenesisDoc

                gpath = os.path.join(home, "config", "genesis.json")
                gd = GenesisDoc.load(gpath)
                gd.consensus_params = ConsensusParams(abci=ABCIParams(
                    vote_extensions_enable_height=
                    m.vote_extensions_enable_height))
                gd.save(gpath)
            cfg_file = os.path.join(home, "config", "config.toml")
            cfg = Config.load(cfg_file)
            cfg.base.db_backend = m.db_backend
            cfg.base.crypto_backend = "cpu"
            cfg.consensus.timeout_propose = 0.6
            cfg.consensus.timeout_propose_delta = 0.2
            cfg.consensus.timeout_prevote = 0.3
            cfg.consensus.timeout_prevote_delta = 0.1
            cfg.consensus.timeout_precommit = 0.3
            cfg.consensus.timeout_precommit_delta = 0.1
            cfg.consensus.timeout_commit = m.timeout_commit
            cfg.p2p.fault_injection = True  # arm the partition channel
            # fast PEX cadence so a seed-only bootstrap converges well
            # inside the test budget (discovery needs a few round trips)
            cfg.p2p.pex_interval_s = 0.5
            # localhost nets aren't MTU-bound: bigger packets mean fewer
            # header+seal round trips per block part (ISSUE 11); mixed
            # sizes interop since receivers are frame-size-agnostic
            cfg.p2p.max_packet_payload_size = 8192
            # record ABCI call sequences for the post-run conformance
            # check (reference test/e2e/pkg/grammar/checker.go)
            cfg.base.abci_call_log = True
            # every node snapshots so statesync joiners find providers
            cfg.base.snapshot_interval = 2
            # DA manifests: every node encodes + enforces the header's
            # da_root (proposers and validators must agree on it, so
            # it's all-or-nothing across the net)
            cfg.da.enabled = m.da_enabled
            # prometheus endpoint per node so the runner can assert live
            # series mid-run (reference test/e2e enabling instrumentation)
            mport = self.starting_port + 2 * len(m.nodes) + i
            cfg.instrumentation.prometheus = True
            cfg.instrumentation.prometheus_listen_addr = f"127.0.0.1:{mport}"
            # per-node flight-recorder sink: on failure the runner
            # merges them into a stall-triage report (trace_report.txt)
            if self.trace:
                cfg.instrumentation.trace_sink = "data/trace.jsonl"
            # an audited world needs every node publishing its feed —
            # the watchtower is a feed consumer like any replica
            if m.watchtower:
                cfg.replication.serve = True
            cfg.save(cfg_file)
            port = self.starting_port + 2 * i + 1
            self.nodes[spec.name] = _ProcNode(
                spec.name, home, port,
                command=self.node_commands.get(spec.name),
                metrics_port=mport,
            )
        # byzantine fault schedule: the named node's privval is wrapped
        # to double-sign inside the window (privval/byzantine.py reads
        # the schedule from the environment at node boot)
        by_node: dict[str, list[dict]] = {}
        for entry in m.byzantine:
            e = dict(entry)
            name = e.pop("node")
            by_node.setdefault(name, []).append(e)
        for name, sched in by_node.items():
            if name not in self.nodes:
                raise E2EError(f"byzantine schedule names unknown {name}")
            self.nodes[name].extra_env["COMETBFT_TPU_BYZANTINE"] = (
                json.dumps(sched)
            )

    def _node_id(self, name: str) -> str:
        """Peer id of a testnet node, derived from its generated key
        (the partition control files identify peers by id)."""
        from ..p2p.key import NodeKey

        home = self.nodes[name].home
        nk = NodeKey.load_or_generate(
            os.path.join(home, "config", "node_key.json")
        )
        return nk.node_id()

    # ------------------------------------------------------------- drive
    def start(self) -> None:
        late = {s.name for s in self.manifest.nodes if s.start_at > 0}
        for name, n in self.nodes.items():
            if name not in late:
                n.start()
        if self.manifest.tx_rate > 0:
            self._load_thread = threading.Thread(
                target=self._load_loop, daemon=True
            )
            self._load_thread.start()

    def _load_loop(self) -> None:
        """Round-robin tx load over node RPCs (reference
        test/e2e/runner/load.go). Payloads carry the send timestamp so
        the post-run latency report (reference test/loadtime/report) can
        compute per-tx commit latency from block times alone."""
        i = 0
        interval = 1.0 / self.manifest.tx_rate
        # never target seed nodes: a seed holds no full peers, so a tx
        # sent to it has no gossip path and would silently vanish
        nodes = [
            n for name, n in self.nodes.items() if not self._spec(name).seed
        ]
        while not self._load_stop.is_set():
            node = nodes[i % len(nodes)]
            t_ns = time.time_ns()
            tx = f"load-{i}-{t_ns}={os.urandom(8).hex()}".encode().hex()
            try:
                _rpc(node.rpc_port, "broadcast_tx_async", {"tx": tx})
                self.txs_sent += 1
            except Exception:  # noqa: BLE001 — node may be perturbed
                pass
            i += 1
            self._load_stop.wait(interval)

    def latency_report(self) -> dict:
        """Commit-latency distribution of the timestamped load txs,
        computed from any stopped node's block store: latency = block
        header time - the send time embedded in the payload (reference
        test/loadtime/report/report.go). Call after run()/stop_all()."""
        from ..storage import BlockStore, open_kv

        lats: list[float] = []
        # read the TALLEST store: a perturbed node's store may stop
        # short of the tip, silently dropping exactly the txs whose
        # latency the perturbation inflated
        stores = []
        for n in self.nodes.values():
            path = os.path.join(n.home, "data", "blockstore.db")
            if os.path.exists(path):
                stores.append(BlockStore(open_kv(path)))
        stores.sort(key=lambda b: b.height(), reverse=True)
        for bs in stores[:1]:
            for h in range(1, bs.height()):
                blk = bs.load_block(h)
                nxt = bs.load_block(h + 1)
                if blk is None or nxt is None:
                    continue
                # BFT time: block h's own header time is the MEDIAN of
                # the previous commit's vote times — the moment block h
                # was actually committed is carried by block h+1's
                # header (types/block.go MedianTime), so latency is
                # measured against that (tip block's txs are skipped)
                commit_ns = nxt.header.time.unix_ns()
                for tx in blk.data.txs:
                    if not tx.startswith(b"load-"):
                        continue
                    try:
                        sent_ns = int(
                            tx.split(b"=", 1)[0].split(b"-")[2]
                        )
                    except (IndexError, ValueError):
                        continue
                    lats.append((commit_ns - sent_ns) / 1e9)
            break  # one store suffices: all nodes agree on blocks
        if not lats:
            return {"count": 0}
        lats.sort()

        def pct(p: float) -> float:
            return round(lats[min(int(p * len(lats)), len(lats) - 1)], 4)

        return {
            "count": len(lats),
            "p50_s": pct(0.50),
            "p95_s": pct(0.95),
            "p99_s": pct(0.99),
            "max_s": round(lats[-1], 4),
        }

    def sample_peer_counts(self, name: str, samples: int = 6,
                           interval_s: float = 0.5) -> list[int]:
        """Poll `name`'s net_info peer count (reference /net_info
        n_peers). A seed-mode node crawls-and-disconnects, so sampled
        over time its count must keep RETURNING to zero — the
        observable difference from a node holding full peers."""
        counts = []
        node = self.nodes[name]
        for _ in range(samples):
            try:
                r = _rpc(node.rpc_port, "net_info")
                counts.append(int(r["n_peers"]))
            except Exception:  # noqa: BLE001 — node may be perturbed
                counts.append(-1)
            time.sleep(interval_s)
        return counts

    def addrbook_doc(self, name: str) -> dict:
        """Parse `name`'s persisted address book (written on node stop
        and on every pex tick) for post-run assertions."""
        path = os.path.join(
            self.nodes[name].home, "config", "addrbook.json"
        )
        if not os.path.exists(path):
            return {}
        with open(path) as f:
            return json.load(f)

    def scrape_metrics(self, name: str, timeout: float = 3.0) -> str:
        """Fetch `name`'s prometheus exposition text (GET /metrics)."""
        node = self.nodes[name]
        url = f"http://127.0.0.1:{node.metrics_port}/metrics"
        with urllib.request.urlopen(url, timeout=timeout) as resp:
            return resp.read().decode()

    # series every live full node must expose once the chain is moving:
    # one representative per instrumented subsystem
    KEY_SERIES = (
        "cometbft_consensus_height",
        "cometbft_consensus_step_duration_seconds",
        "cometbft_mempool_size",
        "cometbft_p2p_peers",
        "cometbft_p2p_peer_height",
        "cometbft_state_block_processing_time",
        "cometbft_blocksync_syncing",
        "cometbft_crypto_path_selected_total",
    )

    def check_metrics(self) -> dict:
        """Scrape every live node's /metrics and assert the key series
        are present with sane values on at least one of them (perturbed
        or old-build nodes may legitimately not answer)."""
        per_node: dict[str, list[str]] = {}
        ok_nodes = []
        for name, n in self.nodes.items():
            if n.proc is None or n.command is not None:
                continue  # stopped, or an old build without /metrics
            try:
                text = self.scrape_metrics(name)
            except Exception:  # noqa: BLE001 — perturbed/paused node
                per_node[name] = ["<unreachable>"]
                continue
            missing = [s for s in self.KEY_SERIES if s not in text]
            height = 0.0
            for line in text.splitlines():
                if line.startswith("cometbft_consensus_height "):
                    height = float(line.split()[-1])
            if height <= 0:
                missing.append("cometbft_consensus_height>0")
            per_node[name] = missing
            if not missing:
                ok_nodes.append(name)
        if per_node and not ok_nodes:
            raise E2EError(f"no node passed the metrics check: {per_node}")
        return per_node

    def max_height(self) -> int:
        return max(
            (n.height() for name, n in self.nodes.items()
             if not self._spec(name).seed),
            default=-1,
        )

    def _spec(self, name: str):
        for s in self.manifest.nodes:
            if s.name == name:
                return s
        raise E2EError(f"unknown node {name}")

    def wait_for_height(self, h: int, timeout_s: float) -> None:
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            if self.max_height() >= h:
                return
            time.sleep(0.25)
        raise E2EError(
            f"testnet did not reach height {h} "
            f"(at {self.max_height()}) within {timeout_s}s"
        )

    def run(self) -> None:
        """Execute the manifest: start, perturb on schedule, reach the
        target height, stop, check invariants. On failure, merge every
        node's flight-recorder sink into ``<workdir>/trace_report.txt``
        and append the stall triage to the raised error."""
        try:
            self._run_inner()
        except E2EError as e:
            triage = self._write_trace_report()
            if triage:
                raise E2EError(
                    f"{e}\n--- flight recorder triage "
                    f"({os.path.join(self.workdir, 'trace_report.txt')}) "
                    f"---\n{triage}"
                ) from e
            raise

    def _attach_watchtower(self) -> None:
        """Tail every (non-seed) node's replication feed + trace sink
        with an in-process auditor; the run fails on any safety verdict
        it raises (fork / equivocation / certificate mismatch)."""
        from ..watchtower import Watchtower

        feeds = {
            name: f"http://127.0.0.1:{n.rpc_port}"
            for name, n in self.nodes.items() if not self._spec(name).seed
        }
        sinks = {}
        if self.trace:
            sinks = {
                name: os.path.join(n.home, "data", "trace.jsonl")
                for name, n in self.nodes.items()
                if not self._spec(name).seed
            }
        self.watchtower = Watchtower(
            feeds,
            chain_id=self.manifest.chain_id,
            trace_sinks=sinks,
            verdict_path=os.path.join(self.workdir, "verdicts.jsonl"),
        )
        self.watchtower.start()

    def check_watchtower(self) -> dict:
        """Post-run audit gate: any safety verdict fails the world."""
        if self.watchtower is None:
            return {}
        safety = self.watchtower.safety_verdicts()
        if safety:
            lines = "; ".join(
                f"[{v['check']}] {v.get('detail', '')}" for v in safety[:5]
            )
            raise E2EError(
                f"watchtower raised {len(safety)} safety verdict(s): "
                f"{lines}"
            )
        return self.watchtower.status()

    def _run_inner(self) -> None:
        m = self.manifest
        self.start()
        if m.watchtower:
            self._attach_watchtower()
        try:
            # one height-ordered schedule: perturbations + late joins
            pending = sorted(
                [(p.at_height, 0, p) for p in m.perturbations]
                + [(s.start_at, 1, s) for s in m.nodes if s.start_at > 0],
                key=lambda t: (t[0], t[1]),
            )
            deadline = time.monotonic() + m.timeout_s
            for at_height, kind, ev in pending:
                while self.max_height() < at_height:
                    if time.monotonic() > deadline:
                        raise E2EError(
                            f"timeout before event at {at_height}"
                        )
                    time.sleep(0.25)
                if kind == 0:
                    self._apply(ev)
                else:
                    self._start_late(ev)
            self.wait_for_height(
                m.target_height, max(deadline - time.monotonic(), 1.0)
            )
            # metrics invariant while the nodes are still live: at least
            # one node exposes every key series with a positive height
            self.check_metrics()
            if self.watchtower is not None:
                # give the auditor one last drain of the feeds/sinks
                # before the nodes go away, then gate on its verdicts
                deadline_wt = time.monotonic() + 5.0
                while (time.monotonic() < deadline_wt and any(
                        st["audited"] < m.target_height for st in
                        self.watchtower.status()["nodes"].values())):
                    time.sleep(0.2)
        finally:
            if self.watchtower is not None:
                self.watchtower.stop()
            self.stop_all()
        self.check_invariants()
        self.check_watchtower()

    # ----------------------------------------------------- flight recorder
    def trace_paths(self) -> dict[str, str]:
        """name -> existing per-node trace sink path."""
        out = {}
        for name, node in self.nodes.items():
            p = os.path.join(node.home, "data", "trace.jsonl")
            if os.path.isfile(p):
                out[name] = p
        return out

    def merged_trace(self):
        """Merge every node's sink (raises ValueError when none exist)."""
        from ..utils import traceview

        return traceview.merge(list(self.trace_paths().values()))

    def stall_report(self) -> dict:
        return self.merged_trace().stall_report()

    def _write_trace_report(self) -> str | None:
        """Best-effort failure triage: write summary + last critical path
        + stall report to ``<workdir>/trace_report.txt`` and return the
        stall-triage text. Must never raise — it runs on the error path
        and masking the original failure would be worse than no report
        (old-build nodes in upgrade tests have no sinks at all)."""
        try:
            from ..utils import traceview

            mt = self.merged_trace()
            stall = traceview.render_stall_report(mt.stall_report())
            parts = [traceview.render_summary(mt)]
            hs = mt.heights()
            if hs:
                parts.append(traceview.render_critical_path(
                    mt.critical_path(hs[-1])))
            parts.append(stall)
            with open(os.path.join(self.workdir, "trace_report.txt"),
                      "w", encoding="utf-8") as f:
                f.write("\n\n".join(parts) + "\n")
            return stall
        except Exception:
            return None

    def _apply(self, p) -> None:
        node = self.nodes[p.node]
        if p.op == "kill":
            node.kill9()
            time.sleep(p.down_s)
            node.start()
        elif p.op == "restart":
            node.stop()
            node.start()
        elif p.op == "pause":
            node.pause()
            time.sleep(p.down_s)
            node.resume()
        elif p.op == "partition":
            self._partition(p.node, True)
            time.sleep(p.down_s)
            self._partition(p.node, False)
        elif p.op == "split":
            # two-way net partition: p.group (plus p.node) vs the rest.
            # With the group sized to straddle the quorum boundary, no
            # side can commit — progress must resume only on heal
            # (reference perturb.go's netem-based splits).
            side_a = set(p.group) | {p.node}
            self._split(side_a, True)
            time.sleep(p.down_s)
            self._split(side_a, False)
        elif p.op == "upgrade":
            # restart as a newer build (reference perturb.go's binary
            # swap): a node launched from an alternate (older) build
            # swaps to the CURRENT repo build — wire, store, and WAL
            # must carry across for the chain to keep committing
            # through it. Nodes already on the current build restart
            # advertising a bumped software version (version-skew
            # interop; NodeInfo compatibility is network+channels only).
            node.stop()
            if node.command is not None:
                node.pre_log_history = True
            node.command = None  # current build from here on
            node.extra_env["COMETBFT_TPU_VERSION"] = "99.0.0-e2e-upgrade"
            node.start()
        else:
            raise E2EError(f"unknown perturbation op {p.op!r}")

    def _start_late(self, spec) -> None:
        """Start a late-joining node (reference manifest.go StartAt). A
        state_sync joiner is anchored at runtime: trust hash = a live
        node's header hash at a recent height, exactly how an operator
        would bootstrap one out-of-band."""
        from ..config import Config

        node = self.nodes[spec.name]
        if spec.state_sync:
            anchor_h, anchor_hash = self._trust_anchor()
            cfg_file = os.path.join(node.home, "config", "config.toml")
            cfg = Config.load(cfg_file)
            cfg.statesync.enable = True
            cfg.statesync.trust_height = anchor_h
            cfg.statesync.trust_hash = anchor_hash
            cfg.statesync.discovery_time_s = 1.0
            cfg.save(cfg_file)
        node.start()

    def _trust_anchor(self) -> tuple[int, str]:
        """(height, header hash hex) from the first live node that
        answers; anchored at height 1 (any committed header works — the
        light client skip-verifies forward from it)."""
        for n in self.nodes.values():
            try:
                r = _rpc(n.rpc_port, "block", {"height": 1})
                return 1, r["block_id"]["hash"].lower()
            except Exception:  # noqa: BLE001 — node may be down/perturbed
                continue
        raise E2EError("no live node to anchor state sync trust")

    def _split(self, side_a: set, up: bool) -> None:
        """Two-way partition: every node's partition.json lists the
        peer ids on the other side to drop/refuse (heal when up=False);
        the switches poll the file (p2p/switch.py
        watch_partition_file). Writes are atomic via os.replace so
        pollers never see a partial file."""
        ids = {name: self._node_id(name) for name in self.nodes}
        for name, n in self.nodes.items():
            if up:
                mine = name in side_a
                blocked = [
                    ids[o] for o in self.nodes
                    if o != name and (o in side_a) != mine
                ]
            else:
                blocked = []
            path = os.path.join(n.home, "data", "partition.json")
            tmp = path + ".tmp"
            with open(tmp, "w") as f:
                json.dump(blocked, f)
            os.replace(tmp, path)

    def _partition(self, name: str, up: bool) -> None:
        """Isolate `name` from every other node (or heal): the
        degenerate split {name} vs the rest."""
        self._split({name}, up)

    def stop_all(self) -> None:
        self._load_stop.set()
        if self._load_thread is not None:
            self._load_thread.join(timeout=5)
        for n in self.nodes.values():
            n.stop()
            n.log.close()

    # -------------------------------------------------------- invariants
    def check_invariants(self) -> dict:
        """Block-hash and app-hash agreement at every common height,
        checked from the stores the stopped nodes left behind (black-box:
        the same data the /block RPC serves). DA manifests additionally
        re-derive every header's da_root from the stored block payload —
        the commitment a sampling client trusts must match the data the
        chain actually carries."""
        from ..storage import BlockStore, open_kv

        da_check = None
        if self.manifest.da_enabled:
            from ..config import DAConfig
            from ..da import DAServe

            da_check = DAServe(DAConfig(enabled=True))
        cert_vals = None
        certs_checked = 0
        if self.manifest.key_type == "bls":
            # the e2e valset is static (KVStore app emits no updates):
            # the genesis set verifies every height's certificate
            from ..types.genesis import GenesisDoc

            gpath = os.path.join(
                self.workdir, "node0", "config", "genesis.json")
            cert_vals = GenesisDoc.load(gpath).validator_set()
        chains: dict[str, dict[int, tuple[bytes, bytes]]] = {}
        da_roots_checked = 0
        for name, n in self.nodes.items():
            bs = BlockStore(
                open_kv(os.path.join(n.home, "data", "blockstore.db"))
            )
            by_h = {}
            for h in range(1, bs.height() + 1):
                blk = bs.load_block(h)
                if blk is not None:
                    by_h[h] = (blk.hash(), bytes(blk.header.app_hash))
                    if da_check is not None:
                        if (blk.header.da_root
                                != da_check.da_root_for(blk.data)):
                            raise E2EError(
                                f"{name} height {h}: header da_root does "
                                "not re-derive from the stored payload"
                            )
                        da_roots_checked += 1
                if cert_vals is None:
                    continue
                # certificate re-derivation (ISSUE 17): every stored
                # commit on a BLS net must be certificate-native and its
                # one-pairing aggregate must verify against the valset
                for commit in (bs.load_block_commit(h),
                               bs.load_seen_commit(h)):
                    if commit is None or commit.height == 0:
                        continue  # genesis empty commit / not stored
                    cert = getattr(commit, "cert", None)
                    if cert is None:
                        raise E2EError(
                            f"{name} height {h}: BLS net stored a plain "
                            "signature column, not a certificate"
                        )
                    try:
                        cert.verify(self.manifest.chain_id, cert_vals)
                    except Exception as e:
                        raise E2EError(
                            f"{name} height {h}: stored certificate "
                            f"does not re-verify: {e}"
                        ) from e
                    certs_checked += 1
            chains[name] = by_h
        heights = [max(c) if c else 0 for c in chains.values()]
        if not heights or max(heights) < self.manifest.target_height:
            raise E2EError(
                f"no node reached target {self.manifest.target_height}: "
                f"{dict(zip(chains, heights))}"
            )
        names = list(chains)
        for i, a in enumerate(names):
            for b in names[i + 1:]:
                common = chains[a].keys() & chains[b].keys()
                for h in common:
                    if chains[a][h] != chains[b][h]:
                        raise E2EError(
                            f"hash divergence at height {h}: {a} vs {b}"
                        )
        grammar = self.check_abci_grammar()
        out = {
            "heights": dict(zip(chains, heights)),
            "txs_sent": self.txs_sent,
            "abci_executions": grammar,
        }
        if da_check is not None:
            out["da_roots_checked"] = da_roots_checked
        if cert_vals is not None:
            if certs_checked == 0:
                raise E2EError("BLS net stored no certificates to check")
            out["certs_checked"] = certs_checked
        return out

    def check_abci_grammar(self) -> dict:
        """Validate every node's recorded ABCI call sequence against the
        legal-sequence grammar (reference test/e2e/pkg/grammar); raises
        on any violation. Returns per-node execution counts."""
        from ..abci.grammar import check_node_log, read_executions

        counts = {}
        for name, n in self.nodes.items():
            log_path = os.path.join(n.home, "data", "abci_calls.log")
            errs = check_node_log(
                log_path, clean_start=not n.pre_log_history
            )
            if errs:
                raise E2EError(
                    f"ABCI grammar violations on {name}: " + "; ".join(errs)
                )
            counts[name] = len(read_executions(log_path))
        return counts
