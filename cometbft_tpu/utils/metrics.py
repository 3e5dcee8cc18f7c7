"""Prometheus-compatible metrics (reference per-subsystem metrics.go +
scripts/metricsgen).

A minimal registry with Counter / Gauge / Histogram supporting labels
and the text exposition format, served by `MetricsServer` at the
instrumentation listen address (reference node/node.go:537). Subsystem
metric bundles mirror the reference's generated structs; singleton
accessors (`consensus_metrics()` ...) hand the hot paths their bundle
against `DEFAULT_REGISTRY`, and `reset_bundles()` clears everything so
metric state cannot leak across tests.
"""

from __future__ import annotations

import json
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

NAMESPACE = "cometbft"


def set_namespace(ns: str) -> None:
    """Set the metric-name prefix (config [instrumentation] namespace).

    Affects metrics registered after the call; node startup invokes it
    before any subsystem bundle is created.
    """
    global NAMESPACE
    if ns:
        NAMESPACE = ns


def _escape_label(v) -> str:
    # Prometheus text format: backslash, double-quote and newline must
    # be escaped inside label values.
    return (
        str(v).replace("\\", "\\\\").replace('"', '\\"').replace("\n", "\\n")
    )


class _Metric:
    def __init__(self, name: str, help_: str, labels: tuple[str, ...]):
        self.name = name
        self.help = help_
        self.labels = labels
        self._lock = threading.Lock()
        self._values: dict[tuple, float] = {}

    def _key(self, label_values: tuple) -> tuple:
        if len(label_values) != len(self.labels):
            raise ValueError(
                f"{self.name}: expected labels {self.labels}, got {label_values}"
            )
        return label_values

    def _fmt_labels(self, key: tuple) -> str:
        if not self.labels:
            return ""
        pairs = ",".join(
            f'{k}="{_escape_label(v)}"' for k, v in zip(self.labels, key)
        )
        return "{" + pairs + "}"

    def values(self) -> dict[tuple, float]:
        """Snapshot of current samples keyed by label-value tuple."""
        with self._lock:
            return dict(self._values)


class Counter(_Metric):
    TYPE = "counter"

    def inc(self, amount: float = 1.0, *labels) -> None:
        key = self._key(tuple(labels))
        with self._lock:
            self._values[key] = self._values.get(key, 0.0) + amount

    def expose(self) -> list[str]:
        with self._lock:
            items = sorted(self._values.items())
        if not items and not self.labels:
            return [f"{self.name} 0"]
        return [f"{self.name}{self._fmt_labels(k)} {v}" for k, v in items]


class Gauge(_Metric):
    TYPE = "gauge"

    def set(self, value: float, *labels) -> None:
        with self._lock:
            self._values[self._key(tuple(labels))] = value

    def add(self, amount: float, *labels) -> None:
        key = self._key(tuple(labels))
        with self._lock:
            self._values[key] = self._values.get(key, 0.0) + amount

    def remove(self, *labels) -> None:
        """Drop one labelled series (e.g. a disconnected peer's gauge)."""
        key = self._key(tuple(labels))
        with self._lock:
            self._values.pop(key, None)

    def expose(self) -> list[str]:
        with self._lock:
            items = sorted(self._values.items())
        if not items and not self.labels:
            return [f"{self.name} 0"]
        return [f"{self.name}{self._fmt_labels(k)} {v}" for k, v in items]


class Histogram(_Metric):
    TYPE = "histogram"
    DEFAULT_BUCKETS = (0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0,
                       2.5, 5.0, 10.0)

    def __init__(self, name, help_, labels, buckets=None):
        super().__init__(name, help_, labels)
        self.buckets = tuple(buckets or self.DEFAULT_BUCKETS)
        self._counts: dict[tuple, list[int]] = {}
        self._sums: dict[tuple, float] = {}
        # per-(labelset, bucket index) exemplar: (id, value, epoch ts) —
        # latest observation wins, like the prometheus client libraries
        self._exemplars: dict[tuple, dict[int, tuple]] = {}

    def observe(self, value: float, *labels, exemplar: str | None = None
                ) -> None:
        key = self._key(tuple(labels))
        with self._lock:
            counts = self._counts.setdefault(key, [0] * (len(self.buckets) + 1))
            bucket_idx = len(self.buckets)  # +Inf
            for i, b in enumerate(self.buckets):
                if value <= b:
                    counts[i] += 1
                    bucket_idx = min(bucket_idx, i)
            counts[-1] += 1  # +Inf
            self._sums[key] = self._sums.get(key, 0.0) + value
            if exemplar is not None:
                self._exemplars.setdefault(key, {})[bucket_idx] = (
                    str(exemplar), value, time.time())

    def exemplars(self) -> dict[tuple, dict[int, tuple]]:
        """{labels: {bucket index: (id, value, ts)}} — bucket index
        len(buckets) is +Inf. For the OpenMetrics exposition and the
        latency-observatory tooling (a p99 bucket's exemplar names a
        concrete tx hash to look up in the trace sink)."""
        with self._lock:
            return {k: dict(v) for k, v in self._exemplars.items()}

    def expose_openmetrics(self) -> list[str]:
        """Bucket lines with `# {trace_id}` exemplar suffixes
        (OpenMetrics syntax). Only served when the scraper opts in
        (GET /metrics?exemplars=1): exemplar suffixes are not valid in
        the classic text format that default scrapes negotiate."""
        out = []
        with self._lock:
            items = sorted(self._counts.items())
            sums = dict(self._sums)
            exem = {k: dict(v) for k, v in self._exemplars.items()}
        for key, counts in items:
            base = self._fmt_labels(key)[1:-1] if self.labels else ""
            ex = exem.get(key, {})
            for i, b in enumerate(self.buckets):
                le = f'le="{b}"'
                lbl = "{" + (base + "," if base else "") + le + "}"
                line = f"{self.name}_bucket{lbl} {counts[i]}"
                e = ex.get(i)
                if e is not None:
                    line += (f' # {{trace_id="{_escape_label(e[0])}"}}'
                             f" {e[1]} {e[2]}")
                out.append(line)
            lbl = "{" + (base + "," if base else "") + 'le="+Inf"' + "}"
            line = f"{self.name}_bucket{lbl} {counts[-1]}"
            e = ex.get(len(self.buckets))
            if e is not None:
                line += (f' # {{trace_id="{_escape_label(e[0])}"}}'
                         f" {e[1]} {e[2]}")
            out.append(line)
            sfx = "{" + base + "}" if base else ""
            out.append(f"{self.name}_sum{sfx} {sums[key]}")
            out.append(f"{self.name}_count{sfx} {counts[-1]}")
        return out

    def snapshot(self) -> dict[tuple, dict]:
        """{labels: {"count": n, "sum": s}} for programmatic readers."""
        with self._lock:
            return {
                k: {"count": c[-1], "sum": self._sums.get(k, 0.0)}
                for k, c in self._counts.items()
            }

    def expose(self) -> list[str]:
        out = []
        with self._lock:
            for key, counts in sorted(self._counts.items()):
                base = self._fmt_labels(key)[1:-1] if self.labels else ""
                for i, b in enumerate(self.buckets):
                    le = f'le="{b}"'
                    lbl = "{" + (base + "," if base else "") + le + "}"
                    out.append(f"{self.name}_bucket{lbl} {counts[i]}")
                lbl = "{" + (base + "," if base else "") + 'le="+Inf"' + "}"
                out.append(f"{self.name}_bucket{lbl} {counts[-1]}")
                sfx = "{" + base + "}" if base else ""
                out.append(f"{self.name}_sum{sfx} {self._sums[key]}")
                out.append(f"{self.name}_count{sfx} {counts[-1]}")
        return out


class Registry:
    def __init__(self):
        self._metrics: list[_Metric] = []
        self._names: set[str] = set()
        self._lock = threading.Lock()

    def counter(self, subsystem: str, name: str, help_: str = "",
                labels: tuple = ()) -> Counter:
        return self._add(Counter(f"{NAMESPACE}_{subsystem}_{name}", help_,
                                 tuple(labels)))

    def gauge(self, subsystem: str, name: str, help_: str = "",
              labels: tuple = ()) -> Gauge:
        return self._add(Gauge(f"{NAMESPACE}_{subsystem}_{name}", help_,
                               tuple(labels)))

    def histogram(self, subsystem: str, name: str, help_: str = "",
                  labels: tuple = (), buckets=None) -> Histogram:
        return self._add(
            Histogram(f"{NAMESPACE}_{subsystem}_{name}", help_,
                      tuple(labels), buckets)
        )

    def _add(self, m: _Metric):
        with self._lock:
            if m.name in self._names:
                raise ValueError(f"metric {m.name!r} already registered")
            self._names.add(m.name)
            self._metrics.append(m)
        return m

    def clear(self) -> None:
        with self._lock:
            self._metrics.clear()
            self._names.clear()

    def expose_text(self, openmetrics: bool = False) -> str:
        """Text exposition; `openmetrics=True` adds exemplar suffixes to
        histogram bucket lines (served only on explicit opt-in —
        GET /metrics?exemplars=1 — since the classic format has no
        exemplar syntax)."""
        lines = []
        with self._lock:
            metrics = list(self._metrics)
        for m in metrics:
            if m.help:
                lines.append(f"# HELP {m.name} {m.help}")
            lines.append(f"# TYPE {m.name} {m.TYPE}")
            if openmetrics and isinstance(m, Histogram):
                lines.extend(m.expose_openmetrics())
            else:
                lines.extend(m.expose())
        return "\n".join(lines) + "\n"


DEFAULT_REGISTRY = Registry()


# -- subsystem bundles (reference */metrics.go) -----------------------------

# Sub-second buckets for the tx-lifecycle waterfall: single-node stage
# latencies live in the 0.5ms–2.5s band (admission windows are ~ms,
# consensus rounds ~100ms–1s); the default buckets start too coarse.
TX_STAGE_BUCKETS = (0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05,
                    0.1, 0.25, 0.5, 1.0, 2.5)


class ConsensusMetrics:
    def __init__(self, reg: Registry | None = None):
        reg = reg or DEFAULT_REGISTRY
        self.height = reg.gauge("consensus", "height", "Current height")
        self.rounds = reg.gauge("consensus", "rounds", "Round of the height")
        self.validators = reg.gauge("consensus", "validators",
                                    "Validator count")
        self.missing_validators = reg.gauge(
            "consensus", "missing_validators",
            "Validators absent from the last commit")
        self.block_interval_seconds = reg.histogram(
            "consensus", "block_interval_seconds",
            "Time between consecutive blocks")
        self.num_txs = reg.gauge("consensus", "num_txs", "Txs in last block")
        self.block_size_bytes = reg.gauge("consensus", "block_size_bytes",
                                          "Last block size")
        self.total_txs = reg.counter("consensus", "total_txs",
                                     "Total committed txs")
        self.step_duration_seconds = reg.histogram(
            "consensus", "step_duration_seconds",
            "Time spent in each consensus step", labels=("step",))
        # tx lifecycle observatory (utils/txlife.py): consensus-side
        # waterfall stages + the end-to-end arrival->commit latency,
        # bucket exemplars carrying sampled tx hashes
        self.tx_stage_seconds = reg.histogram(
            "consensus", "tx_stage_seconds",
            "Per-tx lifecycle stage latency, consensus-side stages "
            "(proposal_wait/consensus/apply/notify); sampled txs only",
            labels=("stage",), buckets=TX_STAGE_BUCKETS)
        self.tx_commit_seconds = reg.histogram(
            "consensus", "tx_commit_seconds",
            "Per-tx end-to-end arrival->commit latency; sampled txs only",
            buckets=TX_STAGE_BUCKETS)
        # speculative proposal assembly (ISSUE 11): hit = the block built
        # during the previous height's commit gap was consumed bit-exact
        # by enter_propose; discard = a round bump, valid_block lock,
        # late precommit, or mempool update invalidated it
        self.speculation_total = reg.counter(
            "consensus", "speculation_total",
            "Speculative proposal assemblies by outcome",
            labels=("outcome",))
        # certificate-native consensus (ISSUE 17): one AggregateCommit
        # frame replaces N precommit frames for catchup gossip
        self.cert_gossip_total = reg.counter(
            "consensus", "cert_gossip_total",
            "Aggregate-precommit certificates received via gossip, by "
            "outcome (applied/dup/redundant/stale/invalid/non_bls/"
            "disabled)", labels=("outcome",))


class MempoolMetrics:
    def __init__(self, reg: Registry | None = None):
        reg = reg or DEFAULT_REGISTRY
        self.size = reg.gauge("mempool", "size", "Pending txs")
        self.failed_txs = reg.counter("mempool", "failed_txs",
                                      "CheckTx rejections")
        self.recheck_times = reg.counter("mempool", "recheck_times",
                                         "Post-block rechecks")
        self.tx_bytes = reg.gauge(
            "mempool", "tx_bytes",
            "Total bytes of pending txs (running counter, not a scan)")
        # micro-batched admission pipeline (PR 8): windows amortize the
        # app round-trip + signature verify + lock acquisition
        self.admit_window_size = reg.histogram(
            "mempool", "admit_window_size",
            "Txs per admission window drained by the pipeline")
        self.admit_queue_depth = reg.gauge(
            "mempool", "admit_queue_depth",
            "Txs waiting in the admission queue")
        self.admit_latency = reg.histogram(
            "mempool", "admit_latency",
            "Seconds from enqueue to admission verdict")
        # tx lifecycle observatory (utils/txlife.py): mempool-side
        # waterfall stages, bucket exemplars carrying sampled tx hashes
        self.tx_stage_seconds = reg.histogram(
            "mempool", "tx_stage_seconds",
            "Per-tx lifecycle stage latency, mempool-side stages "
            "(admit_wait/verify/app_check); sampled txs only",
            labels=("stage",), buckets=TX_STAGE_BUCKETS)


class P2PMetrics:
    def __init__(self, reg: Registry | None = None):
        reg = reg or DEFAULT_REGISTRY
        self.peers = reg.gauge("p2p", "peers", "Connected peers")
        self.message_receive_bytes_total = reg.counter(
            "p2p", "message_receive_bytes_total", "Bytes received",
            labels=("chan",))
        self.message_send_bytes_total = reg.counter(
            "p2p", "message_send_bytes_total", "Bytes sent",
            labels=("chan",))
        # Per-peer reactor state (the rejoin-stall
        # debugging data) — fed from the consensus reactor's PeerState.
        self.peer_height = reg.gauge(
            "p2p", "peer_height", "Last known consensus height per peer",
            labels=("peer",))
        self.peer_round = reg.gauge(
            "p2p", "peer_round", "Last known consensus round per peer",
            labels=("peer",))
        # backpressure-aware broadcast queue (tx gossip off the
        # admission path): depth is load, drops are shed backlog
        self.broadcast_queue_depth = reg.gauge(
            "p2p", "broadcast_queue_depth",
            "Frames waiting in the async broadcast queue")
        self.broadcast_queue_dropped = reg.counter(
            "p2p", "broadcast_queue_dropped",
            "Frames dropped from a saturated broadcast queue")
        self.broadcast_queue_wait_seconds = reg.histogram(
            "p2p", "broadcast_queue_wait_seconds",
            "Enqueue->send wait of frames in the async broadcast queue",
            buckets=TX_STAGE_BUCKETS)
        # per-channel MConnection send backlog (ISSUE 11): messages
        # queued or mid-flight on the channel, summed across peers —
        # the instrument that shows where the zero-copy send path backs
        # up under sustained block-part fan-out
        self.send_queue_depth = reg.gauge(
            "p2p", "send_queue_depth",
            "Messages queued on an MConnection send channel",
            labels=("chan",))


class StateMetrics:
    def __init__(self, reg: Registry | None = None):
        reg = reg or DEFAULT_REGISTRY
        self.block_processing_time = reg.histogram(
            "state", "block_processing_time",
            "ApplyBlock wall time (reference execution.go:230)")
        self.block_verify_time = reg.histogram(
            "state", "block_verify_time",
            "Commit signature verification wall time (TPU kernel path)")
        self.valset_rotation_total = reg.counter(
            "state", "valset_rotation_total",
            "Proposer rotations of applied blocks by the arithmetic that "
            "ran: column (int64 numpy) or integer (Python ints, for a set "
            "whose priorities or powers could leave int64)",
            labels=("path",))
        self.valset_encode_total = reg.counter(
            "state", "valset_encode_total",
            "ValidatorSet.encode() calls: hit = a frozen set handed back "
            "the bytes it keeps, miss = the set was encoded afresh (one a "
            "block on ApplyBlock's path with a state store: the new "
            "next_validators)",
            labels=("memo",))
        self.state_save_seconds = reg.histogram(
            "state", "state_save_seconds",
            "Wall time of ApplyBlock's writes to the state store, their "
            "encoding included: the state with both validator sets, the "
            "results' hash and the encoded FinalizeBlockResponse (observed "
            "only with a state store)", buckets=TX_STAGE_BUCKETS)
        self.abci_events_total = reg.counter(
            "abci", "events_total",
            "ABCI events in the FinalizeBlockResponses of applied blocks: "
            "the block's own and every transaction result's (0 from an "
            "application that emits none)")


class StoreMetrics:
    # commit bytes span ~100 B certificates to multi-MB signature
    # columns at 10k validators
    COMMIT_BUCKETS = (128, 512, 2048, 8192, 32768, 131072, 524288, 2097152)

    def __init__(self, reg: Registry | None = None):
        reg = reg or DEFAULT_REGISTRY
        self.commit_bytes = reg.histogram(
            "store", "commit_bytes",
            "Encoded canonical-commit bytes written per block "
            "(certificate-native BLS heights shrink this ~N/1)",
            buckets=StoreMetrics.COMMIT_BUCKETS)


class IndexerMetrics:
    def __init__(self, reg: Registry | None = None):
        reg = reg or DEFAULT_REGISTRY
        self.txs_indexed_total = reg.counter(
            "indexer", "txs_indexed_total",
            "Transactions the indexer service wrote to the tx index "
            "([tx_index] indexer = \"kv\"), one batch a block")
        self.blocks_indexed_total = reg.counter(
            "indexer", "blocks_indexed_total",
            "Blocks whose events the indexer service wrote: one batch to "
            "the tx index and one record to the block index")
        self.events_dropped_total = reg.counter(
            "indexer", "events_dropped_total",
            "Events (a block's own and one a transaction) published to "
            "the indexer service that the index does not hold: the block "
            "whose write failed, the blocks queued behind it, a block "
            "published after the service stopped. A slow indexer drops "
            "none: it holds ApplyBlock back")
        self.attr_keys_total = reg.counter(
            "indexer", "attr_keys_total",
            "Attribute keys the indexer service wrote to the tx index: one "
            "a distinct (type.key, value) a transaction's events carried "
            "marked for indexing (0 from an application that emits no "
            "events)")
        self.blocks_held = reg.gauge(
            "indexer", "blocks_held",
            "Blocks published to the indexer service and not yet written "
            "(at most storage/indexer.MAX_BLOCKS_HELD: ApplyBlock waits "
            "at that many)")


class BlockSyncMetrics:
    # a window of empty 1000-validator blocks is 20 MB, one of blocks
    # that carry 400 transactions of 1 KB 33 MB
    WINDOW_BYTES_BUCKETS = tuple(1 << k for k in range(16, 30, 2))

    def __init__(self, reg: Registry | None = None):
        reg = reg or DEFAULT_REGISTRY
        self.syncing = reg.gauge("blocksync", "syncing",
                                 "1 while block sync is running")
        self.latest_block_height = reg.gauge(
            "blocksync", "latest_block_height",
            "Highest height applied by block sync")
        self.num_peers = reg.gauge("blocksync", "num_peers",
                                   "Peers in the block pool")
        self.pending_requests = reg.gauge(
            "blocksync", "pending_requests",
            "In-flight block requests without a block yet")
        self.peer_height = reg.gauge(
            "blocksync", "peer_height",
            "Reported chain height per pool peer", labels=("peer",))
        self.blocks_applied_total = reg.counter(
            "blocksync", "blocks_applied_total",
            "Blocks verified and applied by block sync")
        self.bad_blocks_total = reg.counter(
            "blocksync", "bad_blocks_total",
            "Blocks that failed verification (request redone)")
        self.set_change_total = reg.counter(
            "blocksync", "set_change_total",
            "Boundaries at which replay drained its pipeline and queued "
            "anew: the validator set changed, or speculation failed",
            labels=("reason",))
        self.window_blocks = reg.histogram(
            "blocksync", "window_blocks",
            "Blocks in a replay window as loaded (a change of the "
            "validator set ends one early)",
            buckets=(1, 2, 4, 8, 16, 32, 64, 128))
        self.window_bytes = reg.histogram(
            "blocksync", "window_bytes",
            "Stored block bytes read for a replay window",
            buckets=BlockSyncMetrics.WINDOW_BYTES_BUCKETS)
        self.txs_applied_total = reg.counter(
            "blocksync", "txs_applied_total",
            "Transactions of the blocks that replay verified and applied")
        self.cert_verify_seconds = reg.histogram(
            "blocksync", "cert_verify_seconds",
            "Certificate (one-pairing) commit verification wall time "
            "during replay, per commit", buckets=TX_STAGE_BUCKETS)


class StateSyncMetrics:
    def __init__(self, reg: Registry | None = None):
        reg = reg or DEFAULT_REGISTRY
        self.syncing = reg.gauge("statesync", "syncing",
                                 "1 while state sync is running")
        self.snapshots_discovered_total = reg.counter(
            "statesync", "snapshots_discovered_total",
            "Snapshots offered by peers")
        self.chunks_applied_total = reg.counter(
            "statesync", "chunks_applied_total",
            "Snapshot chunks accepted by the app")


class LightClientMetrics:
    PROOF_BUCKETS = (64, 128, 256, 512, 1024, 2048, 4096)

    def __init__(self, reg: Registry | None = None):
        reg = reg or DEFAULT_REGISTRY
        self.headers_verified_total = reg.counter(
            "light", "headers_verified_total",
            "Light blocks verified (sequential + skipping)")
        self.bisections_total = reg.counter(
            "light", "bisections_total",
            "Bisection steps taken during skipping verification")
        self.serve_subscribers = reg.gauge(
            "light", "serve_subscribers",
            "Live /light_stream subscribers on the serving surface")
        self.verify_cache_hits_total = reg.counter(
            "light", "verify_cache_hits_total",
            "Verified-commit cache hits (fan-out amortized over one "
            "VerifyCommitLight per height)")
        self.verify_cache_misses_total = reg.counter(
            "light", "verify_cache_misses_total",
            "Verified-commit cache misses (each pays one batch verify)")
        self.proof_bytes = reg.histogram(
            "light", "proof_bytes",
            "Encoded MMR ancestry proof sizes served to light clients",
            buckets=self.PROOF_BUCKETS)
        self.stream_dropped_total = reg.counter(
            "light", "stream_dropped_total",
            "Stream payloads dropped oldest-first on slow subscribers")


class DAMetrics:
    # DA openings carry a whole chunk, so the buckets run larger than
    # the light-client MMR proof sizes
    PROOF_BUCKETS = (128, 256, 512, 1024, 2048, 4096, 8192, 16384)

    def __init__(self, reg: Registry | None = None):
        reg = reg or DEFAULT_REGISTRY
        self.samples_served_total = reg.counter(
            "da", "samples_served_total",
            "Chunk+proof samples served to DAS clients")
        self.proof_bytes = reg.histogram(
            "da", "proof_bytes",
            "Per-sample opening sizes (chunk + Merkle path) served",
            buckets=self.PROOF_BUCKETS)
        self.reconstruct_total = reg.counter(
            "da", "reconstruct_total",
            "Reed-Solomon reconstructions attempted from sampled shards")
        self.pc_commits_total = reg.counter(
            "da", "pc_commits_total",
            "Payloads committed on the 2D polynomial-commitment track")
        self.pc_samples_served_total = reg.counter(
            "da", "pc_samples_served_total",
            "Multiproof (row, columns) samples served to DAS clients")
        self.pc_proof_bytes = reg.histogram(
            "da", "pc_proof_bytes",
            "Per-sample multiproof response sizes (evals + one opening)",
            buckets=self.PROOF_BUCKETS)


class CryptoMetrics:
    BATCH_BUCKETS = (1, 64, 256, 1024, 4096, 10240, 16384, 65536)

    def __init__(self, reg: Registry | None = None):
        reg = reg or DEFAULT_REGISTRY
        self.batch_size = reg.histogram(
            "crypto", "batch_size", "Ed25519 batch-verify sizes",
            buckets=self.BATCH_BUCKETS)
        self.path_selected_total = reg.counter(
            "crypto", "path_selected_total",
            "Dispatch decisions per verify path "
            "(native/ladder/mesh/cpu/single) and curve",
            labels=("path", "curve"))
        self.pack_total = reg.counter(
            "crypto", "pack_total",
            "Wire packs (R||S||k rows of one device batch) by what the "
            "packer did: run (chunks over the C++ worker pool), busy "
            "(another engine held the pool: packed on the caller's "
            "thread), small (too few lanes to split), python (no native "
            "library)",
            labels=("mode",))
        self.a_cache_total = reg.counter(
            "crypto", "a_cache_total",
            "Ladder and mesh launches by whether the batch's pubkey "
            "column was already decompressed on the device "
            "(crypto/ed25519.py _A_CACHE, a mesh's shards: "
            "parallel/mesh.py; keyed by the whole column and the "
            "bucket): hit, or miss (the column ships and decompresses "
            "again)",
            labels=("result",))
        self.commit_path_total = reg.counter(
            "crypto", "commit_path_total",
            "verify_commit / verify_commit_light calls by how the "
            "commit became lanes: columnar (from its decode columns) or "
            "per_slot, with the gate that declined (no_columns/"
            "no_native/shape/key_type/address)",
            labels=("path", "reason"))
        self.verify_seconds = reg.histogram(
            "crypto", "verify_seconds",
            "Batch-verify wall time submit→result",
            labels=("path", "curve"))
        self.gave_way_total = reg.counter(
            "crypto", "gave_way_total",
            "Device work handed to another engine. No path of the "
            "tree hands work over since PR 28 removed the two that did "
            "(the RLC layout's rlc_declined, on-device SHA's oversize): "
            "every reason reads 0, which the benchmark's checks expect",
            labels=("reason",))
        self.msm_native_total = reg.counter(
            "crypto", "msm_native_total",
            "G1 multi-scalar multiplications run on the native "
            "Pippenger engine")
        self.msm_oracle_total = reg.counter(
            "crypto", "msm_oracle_total",
            "G1 multi-scalar multiplications that fell back to the "
            "Python oracle")
        self.mesh_devices = reg.gauge(
            "crypto", "mesh_devices",
            "Device count of the active verify mesh (0/absent = mesh off)")
        self.mesh_batches_total = reg.counter(
            "crypto", "mesh_batches_total",
            "Batches placed per mesh device: sharded mega-batch shards "
            "and streamed whole-commit placements (skew attribution)",
            labels=("device", "mode"))
        self.sched_queue_depth = reg.gauge(
            "crypto", "sched_queue_depth",
            "Verify requests queued in the shared scheduler, per tenant",
            labels=("tenant",))
        self.sched_coalesced_total = reg.counter(
            "crypto", "sched_coalesced_total",
            "Verify requests that shared a coalesced mega-batch dispatch, "
            "per request source (consensus/blocksync/light/admission)",
            labels=("source",))
        self.sched_overlap_total = reg.counter(
            "crypto", "sched_overlap_total",
            "Scheduler dispatches by how many earlier batches were on the "
            "device and unanswered when the drainer took them "
            "(crypto/sched.py keeps at most two unanswered; a host-engine "
            "batch holds a slot until answered and is not counted here): "
            "0 = no device batch was out, 1 = merged, packed and launched "
            "while another was on the device",
            labels=("inflight",))
        self.sched_batch_sigs = reg.histogram(
            "crypto", "sched_batch_sigs",
            "Signatures per coalesced scheduler dispatch",
            buckets=CryptoMetrics.BATCH_BUCKETS)


class ReplicationMetrics:
    def __init__(self, reg: Registry | None = None):
        reg = reg or DEFAULT_REGISTRY
        self.feed_subscribers = reg.gauge(
            "replication", "feed_subscribers",
            "Live replication-feed subscribers (serving replicas)")
        self.feed_frames_total = reg.counter(
            "replication", "feed_frames_total",
            "Frames emitted on the replication feed")
        self.feed_bytes_total = reg.counter(
            "replication", "feed_bytes_total",
            "Frame bytes fanned out to feed subscribers")
        self.feed_lag_heights = reg.gauge(
            "replication", "feed_lag_heights",
            "Replica apply lag behind the core tip, in heights "
            "(readiness input for the replica /healthz)")
        self.replica_applied_total = reg.counter(
            "replication", "replica_applied_total",
            "Feed frames applied into replica serving state")
        self.replica_apply_seconds = reg.histogram(
            "replication", "replica_apply_seconds",
            "Per-frame replica apply latency (decode + DA re-encode + "
            "MMR append)", buckets=TX_STAGE_BUCKETS)
        self.forwarded_txs_total = reg.counter(
            "replication", "forwarded_txs_total",
            "broadcast_tx_* forwarded replica->core by tenant and outcome "
            "(ok/rejected/error)", labels=("tenant", "outcome"))


class WatchtowerMetrics:
    """Streaming safety auditor bundle (watchtower/auditor.py)."""

    def __init__(self, reg: Registry | None = None):
        reg = reg or DEFAULT_REGISTRY
        self.checks_total = reg.counter(
            "watchtower", "checks_total",
            "Audit checks run, by check (fork/equivocation/cert/da/"
            "stall) and outcome (ok/violation/error)",
            labels=("check", "outcome"))
        self.alarm = reg.gauge(
            "watchtower", "alarm",
            "1 while a check's alarm is raised, 0 once clear "
            "(safety alarms latch for the life of the auditor)",
            labels=("check",))
        self.feed_lag_heights = reg.gauge(
            "watchtower", "feed_lag_heights",
            "Audit lag behind each watched node's feed tip, in heights",
            labels=("node",))
        self.audit_seconds = reg.histogram(
            "watchtower", "audit_seconds",
            "Per-height audit latency (all checks against one frame)",
            labels=("check",), buckets=TX_STAGE_BUCKETS)
        self.evidence_submitted_total = reg.counter(
            "watchtower", "evidence_submitted_total",
            "DuplicateVoteEvidence submissions back to watched nodes "
            "over RPC, by outcome (ok/rejected/error)",
            labels=("outcome",))


_BUNDLES: dict[str, object] = {}
_BUNDLES_LOCK = threading.Lock()


def _bundle(name: str, cls):
    b = _BUNDLES.get(name)
    if b is None:
        with _BUNDLES_LOCK:
            b = _BUNDLES.get(name)
            if b is None:
                b = _BUNDLES[name] = cls()
    return b


def consensus_metrics() -> ConsensusMetrics:
    return _bundle("consensus", ConsensusMetrics)


def mempool_metrics() -> MempoolMetrics:
    return _bundle("mempool", MempoolMetrics)


def p2p_metrics() -> P2PMetrics:
    return _bundle("p2p", P2PMetrics)


def state_metrics() -> StateMetrics:
    return _bundle("state", StateMetrics)


def blocksync_metrics() -> BlockSyncMetrics:
    return _bundle("blocksync", BlockSyncMetrics)


def indexer_metrics() -> IndexerMetrics:
    return _bundle("indexer", IndexerMetrics)


def store_metrics() -> StoreMetrics:
    return _bundle("store", StoreMetrics)


def statesync_metrics() -> StateSyncMetrics:
    return _bundle("statesync", StateSyncMetrics)


def light_metrics() -> LightClientMetrics:
    return _bundle("light", LightClientMetrics)


def da_metrics() -> DAMetrics:
    return _bundle("da", DAMetrics)


def crypto_metrics() -> CryptoMetrics:
    return _bundle("crypto", CryptoMetrics)


def replication_metrics() -> ReplicationMetrics:
    return _bundle("replication", ReplicationMetrics)


def watchtower_metrics() -> WatchtowerMetrics:
    return _bundle("watchtower", WatchtowerMetrics)


def reset_bundles() -> None:
    """Test hook: drop all bundles and empty DEFAULT_REGISTRY in place.

    In-place (`Registry.clear`) so references held by a live
    `MetricsServer` keep working; the duplicate-name guard permits
    re-registration after the clear.
    """
    with _BUNDLES_LOCK:
        _BUNDLES.clear()
        DEFAULT_REGISTRY.clear()


def _default_height_fn() -> float:
    """Consensus height as the liveness signal for /healthz: the bundle
    gauge is set by `_finalize_commit` on every decided block."""
    return consensus_metrics().height.values().get((), 0.0)


class MetricsServer:
    """Serves the registry at /metrics (reference prometheus listener).

    Routes:

    * ``GET /metrics`` — classic text exposition. Append
      ``?exemplars=1`` for OpenMetrics-style exemplar suffixes on
      histogram buckets (opt-in: classic scrapes must stay parseable).
    * ``GET /healthz`` — liveness for e2e drivers and soak loops: 200
      while consensus height has advanced within `health_window_s`
      seconds, 503 once it stalls longer than that. The server start is
      treated as an advance (grace window for boot/genesis). JSON body
      with height / seconds-since-advance either way. An optional
      ``ready_fn() -> (bool, dict)`` gates readiness on top of the
      stall check (serving replicas report 503 while snapshot-
      bootstrapping or lagging the feed); its detail dict is merged
      into the JSON body.

    Other paths get 404, other methods 405 — matching what a prometheus
    scraper expects from a metrics endpoint.
    """

    def __init__(self, registry: Registry | None = None,
                 host: str = "127.0.0.1", port: int = 0,
                 health_window_s: float = 30.0, height_fn=None,
                 ready_fn=None):
        reg = registry or DEFAULT_REGISTRY
        height_fn = height_fn or _default_height_fn
        # health state shared with handler threads: last observed height
        # and the monotonic instant it last changed
        health = {"height": None, "advanced": time.monotonic()}
        health_lock = threading.Lock()
        window_s = float(health_window_s)

        def health_probe() -> tuple[bool, dict]:
            try:
                h = float(height_fn())
            except Exception:  # noqa: BLE001 — probe must not 500
                h = 0.0
            now = time.monotonic()
            with health_lock:
                if health["height"] is None or h != health["height"]:
                    health["height"] = h
                    health["advanced"] = now
                idle = now - health["advanced"]
            ok = idle <= window_s
            info = {"status": "ok" if ok else "stalled",
                    "height": h,
                    "since_advance_s": round(idle, 3),
                    "window_s": window_s}
            if ready_fn is not None:
                try:
                    ready, detail = ready_fn()
                except Exception:  # noqa: BLE001 — probe must not 500
                    ready, detail = False, {"ready_error": True}
                info.update(detail)
                if not ready:
                    ok = False
                    info["status"] = "not_ready"
            return ok, info

        class Handler(BaseHTTPRequestHandler):
            def log_message(self, *a):
                pass

            def _refuse(self, code: int, msg: str):
                body = msg.encode()
                self.send_response(code)
                self.send_header("Content-Type", "text/plain")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def do_GET(self):
                path, _, query = self.path.partition("?")
                if path == "/healthz":
                    ok, info = health_probe()
                    body = (json.dumps(info) + "\n").encode()
                    self.send_response(200 if ok else 503)
                    self.send_header("Content-Type", "application/json")
                    self.send_header("Content-Length", str(len(body)))
                    self.end_headers()
                    self.wfile.write(body)
                    return
                if path != "/metrics":
                    self._refuse(404, "not found; metrics at /metrics\n")
                    return
                om = "exemplars=1" in query.split("&")
                body = reg.expose_text(openmetrics=om).encode()
                self.send_response(200)
                self.send_header("Content-Type",
                                 "text/plain; version=0.0.4")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def _method_not_allowed(self):
                self._refuse(405, "method not allowed\n")

            do_POST = _method_not_allowed
            do_PUT = _method_not_allowed
            do_DELETE = _method_not_allowed
            do_PATCH = _method_not_allowed
            do_HEAD = _method_not_allowed

        self._httpd = ThreadingHTTPServer((host, port), Handler)
        self.addr = self._httpd.server_address
        self._thread = threading.Thread(
            target=self._httpd.serve_forever, daemon=True
        )

    def start(self) -> None:
        self._thread.start()

    def stop(self) -> None:
        self._httpd.shutdown()
        self._httpd.server_close()
