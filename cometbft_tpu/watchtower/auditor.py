"""The watchtower auditor: stateless online safety + liveness checks.

Shaped like a serving replica (replication/replica.py) but holding no
serving state at all: one feed-tail thread per watched core node folds
frames into a bounded per-node window, every ingested height is
audited against the OTHER nodes' windows (fork detection, cross-feed
equivocation) and against itself (certificate consistency), a
background sampler fleet probes data availability over `da_sample`,
and an online stall classifier runs over the nodes' streaming trace
sinks. Findings become structured verdicts:

- `trace.event("watchtower.verdict", ...)` + optional JSONL file
- `watchtower_*` metrics (checks_total{check,outcome}, a latching
  alarm gauge per check, per-node feed lag, audit latency)
- in-memory `verdicts` / `safety_verdicts()` — what the e2e runner
  fails an audited world on.

Check classes (the `check` label everywhere):

==============  ======  ==============================================
check           safety  trigger
==============  ======  ==============================================
fork            yes     conflicting commits at one height across
                        feeds; culprits = signer-set intersection
equivocation    yes     DuplicateVoteEvidence built from conflicting-
                        vote trace records or cross-feed commit
                        columns, verified, and submitted back to every
                        watched node over broadcast_evidence
cert            yes     a frame's BLS certificate fails re-derivation
                        against the valset, or disagrees with the
                        retained signature column in the window
da              no      sampling confidence stalled / withheld chunks
                        for `da_alarm_after` consecutive sweeps
stall           no      live node not finalizing (online traceview
                        triage: first missing class + silent peers)
==============  ======  ==============================================

Every decoded object is verified before it can raise a safety verdict
— an unverifiable candidate is dropped, not reported — which is what
keeps the clean-world false-positive rate at zero by construction.
"""

from __future__ import annotations

import base64
import json
import threading
import time
import urllib.error
import urllib.request
from collections import OrderedDict

from ..crypto import merkle
from ..da.commit import DACommitment
from ..da.sampler import Sampler
from ..light.store import _decode_vals
from ..rpc.client import HTTPClient
from ..types import Header
from ..types.agg_commit import (
    AggCommitError,
    AggregateCommit,
    CertCommit,
    decode_commit_any,
)
from ..types.block import Commit
from ..utils import trace
from ..utils.metrics import watchtower_metrics
from ..utils.trace import TailReader
from . import checks
from .stall import OnlineStallClassifier

SAFETY_CHECKS = ("fork", "equivocation", "cert")


class _Frame:
    """One decoded feed frame: everything the checks need, nothing the
    serving plane would (no payloads, no MMR)."""

    __slots__ = ("height", "header", "last", "seen", "vals",
                 "cert_kind", "cert", "da_root", "da_k", "da_m")

    def __init__(self, height):
        self.height = height
        self.header = None
        self.last = None
        self.seen = None
        self.vals = None
        self.cert_kind = "none"
        self.cert = None  # AggregateCommit when the frame carried one
        self.da_root = None
        self.da_k = 0
        self.da_m = 0


class _WatchedNode:
    def __init__(self, name: str, url: str, retain: int):
        self.name = name
        self.url = url
        self.retain = max(2, int(retain))
        self.frames: OrderedDict[int, _Frame] = OrderedDict()
        self.tip = 0  # feed control-record tip
        self.cursor = 0  # highest ingested frame height
        self.feed_connects = 0
        self.lock = threading.Lock()

    def put(self, frame: _Frame) -> None:
        with self.lock:
            self.frames[frame.height] = frame
            while len(self.frames) > self.retain:
                self.frames.popitem(last=False)
            if frame.height > self.cursor:
                self.cursor = frame.height
            if frame.height > self.tip:
                self.tip = frame.height

    def get(self, height: int) -> _Frame | None:
        with self.lock:
            return self.frames.get(height)


class Watchtower:
    """Audit N core nodes' replication feeds + trace sinks online.

    `nodes` maps node name -> RPC base url (http://host:port);
    `trace_sinks` maps node name -> JSONL sink path (optional — without
    it the stall and trace-equivocation checks idle). All checks can
    also be driven synchronously through `ingest_frame` /
    `handle_trace_record` / `da_sweep`, which is how the adversarial
    fixtures pin them without a network.
    """

    def __init__(self, nodes: dict[str, str], *,
                 chain_id: str = "",
                 trace_sinks: dict[str, str] | None = None,
                 full_commit_window: int = 16,
                 da_interval_s: float = 2.0,
                 da_samples: int = 4,
                 da_alarm_after: int = 2,
                 stall_interval_s: float = 1.0,
                 verdict_path: str = "",
                 feed_timeout_s: float = 5.0,
                 retain: int = 512,
                 submit_evidence: bool = True,
                 client_factory=None):
        self.chain_id = chain_id
        self.full_commit_window = int(full_commit_window)
        self.da_interval_s = float(da_interval_s)
        self.da_samples = int(da_samples)
        self.da_alarm_after = int(da_alarm_after)
        self.stall_interval_s = float(stall_interval_s)
        self.verdict_path = verdict_path
        self.feed_timeout_s = float(feed_timeout_s)
        self.submit_evidence = submit_evidence
        self._client_factory = client_factory or HTTPClient

        self.nodes: dict[str, _WatchedNode] = {
            name: _WatchedNode(name, url, retain)
            for name, url in nodes.items()
        }
        self.trace_sinks = dict(trace_sinks or {})
        self.stall = OnlineStallClassifier()

        self.verdicts: list[dict] = []
        self._verdict_keys: set = set()
        self._verdict_lock = threading.Lock()
        self._verdict_fh = None

        self._submitted_evidence: set[bytes] = set()
        self._da_fail_streak: dict[str, int] = {}
        self._da_alarmed: set[str] = set()
        self._stalled_seen: set = set()

        self._stop = threading.Event()
        self._threads: list[threading.Thread] = []
        self._resps: list = []

    # ------------------------------------------------------------------
    # verdicts
    # ------------------------------------------------------------------
    def _verdict(self, check: str, key, **fields) -> bool:
        """Record one finding (deduplicated by `key`); returns True when
        it is new. Safety verdicts latch the alarm gauge for the life
        of the auditor — a fork does not un-happen."""
        m = watchtower_metrics()
        with self._verdict_lock:
            if (check, key) in self._verdict_keys:
                return False
            self._verdict_keys.add((check, key))
            rec = {"check": check, "safety": check in SAFETY_CHECKS,
                   "ts": time.time(), **fields}
            self.verdicts.append(rec)
            if self.verdict_path:
                if self._verdict_fh is None:
                    self._verdict_fh = open(self.verdict_path, "a",
                                            encoding="utf-8")
                self._verdict_fh.write(
                    json.dumps(rec, separators=(",", ":"), default=str)
                    + "\n")
                self._verdict_fh.flush()
        m.checks_total.inc(1.0, check, "violation")
        m.alarm.set(1.0, check)
        trace.event("watchtower.verdict", **rec)
        return True

    def _ok(self, check: str) -> None:
        watchtower_metrics().checks_total.inc(1.0, check, "ok")

    def _error(self, check: str) -> None:
        watchtower_metrics().checks_total.inc(1.0, check, "error")

    def safety_verdicts(self) -> list[dict]:
        with self._verdict_lock:
            return [v for v in self.verdicts if v["safety"]]

    def clear_alarm(self, check: str) -> None:
        """Non-safety alarms (da) clear when the condition passes."""
        watchtower_metrics().alarm.set(0.0, check)

    # ------------------------------------------------------------------
    # frame ingestion + per-height audit
    # ------------------------------------------------------------------
    def ingest_frame(self, node_name: str, raw: dict) -> _Frame:
        """Decode one feed frame dict and audit its height."""
        node = self.nodes[node_name]
        f = _Frame(int(raw["h"]))
        t0 = time.perf_counter()
        f.header = Header.decode(bytes.fromhex(raw["hdr"]))
        if not self.chain_id:
            self.chain_id = f.header.chain_id
        if raw.get("vals"):
            f.vals = _decode_vals(bytes.fromhex(raw["vals"]))
        if raw.get("last"):
            f.last = decode_commit_any(bytes.fromhex(raw["last"]))
        if raw.get("seen"):
            f.seen = decode_commit_any(bytes.fromhex(raw["seen"]))
        cert = raw.get("cert") or {}
        f.cert_kind = cert.get("kind", "none")
        if f.cert_kind in ("cert_native", "bls_agg") and cert.get("data"):
            f.cert = AggregateCommit.decode(bytes.fromhex(cert["data"]))
        da = raw.get("da")
        if da is not None:
            f.da_k = int(da.get("k", 0))
            f.da_m = int(da.get("m", 0))
            if da.get("root"):
                f.da_root = bytes.fromhex(da["root"])
        node.put(f)
        with trace.span("watchtower.audit", node=node_name,
                        height=f.height) as sp:
            n_checks = self._audit_height(node, f)
            sp.add(checks=n_checks)
        watchtower_metrics().audit_seconds.observe(
            time.perf_counter() - t0, "frame")
        self._set_lag(node)
        return f

    def _set_lag(self, node: _WatchedNode) -> None:
        lag = float(max(0, node.tip - node.cursor))
        watchtower_metrics().feed_lag_heights.set(lag, node.name)

    def _audit_height(self, node: _WatchedNode, f: _Frame) -> int:
        n = 0
        n += self._check_cert(node, f)
        n += self._check_fork(node, f)
        n += self._check_column_equivocation(node, f)
        return n

    # -- certificate consistency ----------------------------------------
    def _check_cert(self, node: _WatchedNode, f: _Frame) -> int:
        """Re-derive the frame's certificate against the valset, and —
        when the frame also retains the full signature column — against
        the column (the PR-17 full_commit_window seam, audited from
        outside the node)."""
        if f.cert is None:
            return 0
        ran = 0
        vals = f.vals
        try:
            if vals is not None:
                ran += 1
                try:
                    f.cert.verify(self.chain_id, vals)
                    self._ok("cert")
                except AggCommitError as e:
                    self._verdict(
                        "cert", ("verify", node.name, f.height),
                        node=node.name, height=f.height,
                        kind=f.cert_kind, detail=str(e))
            # column cross-check: only meaningful while the store still
            # retains the full column next to the fold (bls_agg frames
            # inside the window); cert-native frames carry no column
            seen = f.seen
            if (isinstance(seen, Commit) and seen.signatures
                    and vals is not None
                    and node.tip - f.height <= self.full_commit_window):
                ran += 1
                probs = checks.cert_commit_matches_column(
                    CertCommit(f.cert, len(vals)), seen, vals)
                if probs:
                    self._verdict(
                        "cert", ("column", node.name, f.height),
                        node=node.name, height=f.height,
                        kind=f.cert_kind, detail="; ".join(probs))
                else:
                    self._ok("cert")
        except Exception as e:  # noqa: BLE001 — audit must not die
            self._error("cert")
            trace.event("watchtower.audit", node=node.name,
                        height=f.height, error=f"cert: {e}")
        return ran

    # -- fork detection ---------------------------------------------------
    def _check_fork(self, node: _WatchedNode, f: _Frame) -> int:
        """Compare this node's commit at `f.height` against every other
        watched node's. Two commits for different block ids at one
        height = fork; the culprits are the validators in BOTH signer
        sets (>= 1/3 by quorum intersection)."""
        mine = f.seen
        if mine is None:
            return 0
        ran = 0
        for other in self.nodes.values():
            if other is node:
                continue
            of = other.get(f.height)
            if of is None or of.seen is None:
                continue
            ran += 1
            try:
                if of.seen.block_id.key() == mine.block_id.key():
                    self._ok("fork")
                    continue
                vals = f.vals or of.vals
                culprits = checks.fork_culprits(mine, of.seen, vals)
                pair = tuple(sorted((node.name, other.name)))
                self._verdict(
                    "fork", (pair, f.height),
                    height=f.height, nodes=list(pair),
                    block_a=mine.block_id.hash.hex(),
                    block_b=of.seen.block_id.hash.hex(),
                    culprits=[a.hex() for a in culprits],
                    detail=(f"conflicting commits at height {f.height}: "
                            f"{len(culprits)} overlapping signer(s)"))
            except Exception as e:  # noqa: BLE001
                self._error("fork")
                trace.event("watchtower.audit", node=node.name,
                            height=f.height, error=f"fork: {e}")
        return ran

    # -- equivocation -----------------------------------------------------
    def _check_column_equivocation(self, node: _WatchedNode,
                                   f: _Frame) -> int:
        """Cross-feed commit-column scan: a validator COMMIT-signing
        different block ids at one height/round across two nodes' seen
        commits is equivocation provable from the columns alone."""
        if not isinstance(f.seen, Commit) or not f.seen.signatures:
            return 0
        vals = f.vals
        if vals is None:
            return 0
        ran = 0
        for other in self.nodes.values():
            if other is node:
                continue
            of = other.get(f.height)
            if of is None or not isinstance(of.seen, Commit):
                continue
            ran += 1
            try:
                evs = checks.cross_column_equivocations(
                    f.seen, of.seen, vals, self.chain_id)
                if not evs:
                    self._ok("equivocation")
                for ev in evs:
                    self._report_equivocation(ev, source="column")
            except Exception as e:  # noqa: BLE001
                self._error("equivocation")
                trace.event("watchtower.audit", node=node.name,
                            height=f.height, error=f"equivocation: {e}")
        return ran

    def handle_trace_record(self, node_name: str, rec: dict) -> None:
        """One streamed trace record: feed the stall classifier, and
        turn `consensus.conflicting_vote` records — the only place both
        SIGNED votes of an equivocation pair surface — into verified
        DuplicateVoteEvidence."""
        self.stall.ingest(node_name, rec)
        if rec.get("name") != "consensus.conflicting_vote":
            return
        pair = checks.decode_conflicting_vote_record(rec)
        if pair is None:
            return
        vote_a, vote_b = pair
        vals = self._vals_at(vote_a.height)
        if vals is None:
            return
        ev = checks.build_duplicate_vote_evidence(
            vote_a, vote_b, vals, self.chain_id)
        if ev is None:
            self._ok("equivocation")
            return
        self._report_equivocation(ev, source=f"trace:{node_name}")

    def _vals_at(self, height: int):
        for node in self.nodes.values():
            f = node.get(height)
            if f is not None and f.vals is not None:
                return f.vals
        return None

    def _report_equivocation(self, ev, source: str) -> None:
        h = ev.hash()
        with self._verdict_lock:
            if h in self._submitted_evidence:
                return
            self._submitted_evidence.add(h)
        self._verdict(
            "equivocation", h.hex(),
            height=ev.height,
            validator=ev.address().hex(),
            vote_type=int(ev.vote_a.type),
            round=ev.vote_a.round,
            source=source,
            detail=(f"validator {ev.address().hex()[:12]} double-signed "
                    f"type {int(ev.vote_a.type)} at height {ev.height} "
                    f"round {ev.vote_a.round}"))
        if self.submit_evidence:
            self.submit_duplicate_vote(ev)

    def submit_duplicate_vote(self, ev) -> dict[str, str]:
        """Push verified evidence back into every watched node's pool —
        the accountability leg: the pool gossips + commits it, so the
        equivocator is slashed by the chain itself, not just logged."""
        m = watchtower_metrics()
        results: dict[str, str] = {}
        wire = ev.wrapped().hex()
        for node in self.nodes.values():
            try:
                self._client_factory(node.url).broadcast_evidence(
                    evidence=wire)
                results[node.name] = "ok"
                m.evidence_submitted_total.inc(1.0, "ok")
            except RuntimeError:
                # the pool rejects duplicates/known evidence — expected
                # once any one submission has gossiped ahead of us
                results[node.name] = "rejected"
                m.evidence_submitted_total.inc(1.0, "rejected")
            except Exception:  # noqa: BLE001 — node down mid-audit
                results[node.name] = "error"
                m.evidence_submitted_total.inc(1.0, "error")
        return results

    # ------------------------------------------------------------------
    # DA withholding watchdog
    # ------------------------------------------------------------------
    def da_sweep(self, node_name: str, fetch=None) -> object | None:
        """One sampling sweep against `node_name`'s newest DA-carrying
        frame. Withheld/unverifiable samples (or no reachable samples
        at all while a root is advertised) count toward a consecutive-
        failure streak; the alarm raises at `da_alarm_after` and clears
        on the next confident sweep."""
        node = self.nodes[node_name]
        target = None
        with node.lock:
            for f in reversed(node.frames.values()):
                if f.da_root is not None and f.da_k > 0:
                    target = f
                    break
        if target is None:
            return None
        t0 = time.perf_counter()
        n = target.da_k + target.da_m
        sampler = Sampler(
            client_id=hash(node_name) & 0x7FFFFFFF,
            n=n, k=target.da_k, samples=self.da_samples,
            seed=target.height,
        )
        if fetch is None:
            fetch = lambda h, i: self._rpc_fetch_sample(node, h, i)  # noqa: E731
        try:
            res = sampler.run(target.height, target.da_root, fetch)
        except Exception as e:  # noqa: BLE001 — transport died mid-sweep
            self._error("da")
            trace.event("watchtower.audit", node=node_name,
                        height=target.height, error=f"da: {e}")
            return None
        watchtower_metrics().audit_seconds.observe(
            time.perf_counter() - t0, "da")
        bad = res.detected_withholding or res.samples_ok == 0
        if bad:
            streak = self._da_fail_streak.get(node_name, 0) + 1
            self._da_fail_streak[node_name] = streak
            if streak >= self.da_alarm_after:
                self._da_alarmed.add(node_name)
                self._verdict(
                    "da", (node_name, target.height),
                    node=node_name, height=target.height,
                    samples_ok=res.samples_ok,
                    samples_failed=res.samples_failed,
                    failed_indices=res.failed_indices,
                    confidence=round(res.confidence, 4),
                    detail=(f"availability confidence stalled at "
                            f"{res.confidence:.2%} after {streak} "
                            f"consecutive failing sweeps"))
        else:
            self._da_fail_streak[node_name] = 0
            if node_name in self._da_alarmed:
                self._da_alarmed.discard(node_name)
                if not self._da_alarmed:
                    self.clear_alarm("da")
            self._ok("da")
        return res

    def _rpc_fetch_sample(self, node: _WatchedNode, height: int,
                          index: int):
        """da_sample over RPC, parsed into the Sampler's (chunk, proof,
        commitment) transport triple; None = withheld/unknown."""
        try:
            r = self._client_factory(node.url).da_sample(
                height=str(height), index=index)
        except RuntimeError:
            return None  # RPC-level error: no sample for that index
        chunk = bytes.fromhex(r["chunk"])
        pr = r["proof"]
        proof = merkle.Proof(
            total=int(pr["total"]), index=int(pr["index"]),
            leaf_hash=base64.b64decode(pr["leaf_hash"]),
            aunts=[base64.b64decode(a) for a in pr["aunts"]],
        )
        cm = r["commitment"]
        com = DACommitment(
            n=int(cm["shards"]), k=int(cm["data_shards"]),
            payload_len=int(cm["payload_len"]),
            chunks_root=bytes.fromhex(cm["chunks_root"]),
        )
        return chunk, proof, com

    # ------------------------------------------------------------------
    # live stall classification
    # ------------------------------------------------------------------
    def stall_pass(self) -> dict:
        """Classify current per-node trace state; new stalls verdict."""
        t0 = time.perf_counter()
        rep = self.stall.classify()
        watchtower_metrics().audit_seconds.observe(
            time.perf_counter() - t0, "stall")
        for s in rep["stalled"]:
            key = (s["node"], s["height"])
            if key in self._stalled_seen:
                continue
            self._stalled_seen.add(key)
            self._verdict(
                "stall", key,
                node=s["node"], height=s["height"],
                committed=s["committed"], max_round=s["max_round"],
                first_missing=s["first_missing"],
                silent_peers=s["silent_peers"],
                stalled_for_s=s["stalled_for_s"],
                detail=s["detail"])
        if rep["status"] == "ok" and rep["nodes"]:
            self._ok("stall")
        return rep

    # ------------------------------------------------------------------
    # background loops
    # ------------------------------------------------------------------
    def _tail_feed_once(self, node: _WatchedNode) -> None:
        url = (f"{node.url}/replication_feed"
               f"?cursor={node.cursor}&timeout_s={self.feed_timeout_s}")
        with urllib.request.urlopen(
                url, timeout=self.feed_timeout_s + 10) as resp:
            self._resps.append(resp)
            node.feed_connects += 1
            try:
                for raw in resp:
                    if self._stop.is_set():
                        return
                    line = raw.strip()
                    if not line:
                        continue
                    obj = json.loads(line)
                    if "h" not in obj:  # control record {"tip", "min"}
                        if int(obj.get("tip", 0)) > node.tip:
                            node.tip = int(obj["tip"])
                        self._set_lag(node)
                        continue
                    self.ingest_frame(node.name, obj)
            finally:
                try:
                    self._resps.remove(resp)
                except ValueError:
                    pass

    def _feed_loop(self, node: _WatchedNode) -> None:
        while not self._stop.is_set():
            try:
                self._tail_feed_once(node)
            except urllib.error.HTTPError as e:
                if self._stop.is_set():
                    return
                if e.code == 409:
                    # cursor out of the retention window: an auditor has
                    # no snapshot to restore — jump to the live tip and
                    # audit from there (heights skipped are recorded as
                    # a gap in status(), never as a verdict)
                    try:
                        st = self._client_factory(
                            node.url).replication_status()
                        node.cursor = max(node.cursor,
                                          int(st.get("tip", 0)) - 1)
                    except Exception:  # noqa: BLE001
                        pass
                self._stop.wait(0.5)
            except Exception:  # noqa: BLE001 — node restarting
                if self._stop.is_set():
                    return
                self._stop.wait(0.3)

    def _da_loop(self) -> None:
        while not self._stop.wait(self.da_interval_s):
            for name in list(self.nodes):
                if self._stop.is_set():
                    return
                try:
                    self.da_sweep(name)
                except Exception:  # noqa: BLE001
                    self._error("da")

    def _stall_loop(self) -> None:
        readers = {name: TailReader(path)
                   for name, path in self.trace_sinks.items()}
        while not self._stop.is_set():
            for name, reader in readers.items():
                for rec in reader.poll():
                    self.handle_trace_record(name, rec)
            self.stall_pass()
            self._stop.wait(self.stall_interval_s)

    # ------------------------------------------------------------------
    # lifecycle
    # ------------------------------------------------------------------
    def start(self) -> None:
        self._stop.clear()
        for node in self.nodes.values():
            t = threading.Thread(target=self._feed_loop, args=(node,),
                                 name=f"wt-feed-{node.name}", daemon=True)
            t.start()
            self._threads.append(t)
        t = threading.Thread(target=self._da_loop, name="wt-da",
                             daemon=True)
        t.start()
        self._threads.append(t)
        if self.trace_sinks:
            t = threading.Thread(target=self._stall_loop, name="wt-stall",
                                 daemon=True)
            t.start()
            self._threads.append(t)

    def stop(self) -> None:
        self._stop.set()
        for resp in list(self._resps):
            try:
                resp.close()  # unblock a live chunked read
            except Exception:  # noqa: BLE001
                pass
        for t in self._threads:
            t.join(timeout=5.0)
        self._threads.clear()
        with self._verdict_lock:
            if self._verdict_fh is not None:
                self._verdict_fh.close()
                self._verdict_fh = None

    # ------------------------------------------------------------------
    def ready(self) -> tuple[bool, dict]:
        """healthz readiness: every watched feed has delivered at least
        one frame (the auditor cannot audit what it cannot see)."""
        per_node = {n.name: n.cursor for n in self.nodes.values()}
        ok = all(c > 0 for c in per_node.values()) if per_node else False
        return ok, {"watchtower": True, "audited": per_node,
                    "verdicts": len(self.verdicts)}

    def status(self) -> dict:
        with self._verdict_lock:
            by_check: dict[str, int] = {}
            for v in self.verdicts:
                by_check[v["check"]] = by_check.get(v["check"], 0) + 1
            n_verdicts = len(self.verdicts)
            n_safety = sum(1 for v in self.verdicts if v["safety"])
        return {
            "chain_id": self.chain_id,
            "nodes": {
                n.name: {"url": n.url, "tip": n.tip, "audited": n.cursor,
                         "frames": len(n.frames),
                         "feed_connects": n.feed_connects}
                for n in self.nodes.values()
            },
            "verdicts": n_verdicts,
            "safety_verdicts": n_safety,
            "verdicts_by_check": by_check,
            "evidence_submitted": len(self._submitted_evidence),
        }
