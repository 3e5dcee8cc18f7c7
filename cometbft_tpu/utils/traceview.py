"""Cross-node flight recorder: merge per-node trace sinks into one
correlated timeline, attribute per-height wall time, and triage stalls.

Each node writes its own JSONL sink (utils/trace.py) with records
stamped by a stable node id and, via the consensus reactor's wire
hooks, one ``p2p.send``/``p2p.recv`` event per consensus message. This
module is the read side:

* `merge(paths)` loads N sinks and aligns their wall clocks. Every
  matched send→recv pair of the same wire message gives one inequality
  ``recv - send = latency + skew(dst) - skew(src)`` with latency > 0;
  taking the **minimum** delta per directed pair approaches
  ``latency_min + skew(dst) - skew(src)``, and when both directions
  exist the classic NTP trick cancels the (symmetric) latency:
  ``theta = (d_ab - d_ba) / 2 = skew(b) - skew(a)``. Offsets propagate
  breadth-first from a reference node, so any connected world aligns
  even if some pairs only ever talked one way.
* `critical_path(h)` reconstructs the commit pipeline for one height —
  proposal broadcast → prevote quorum → precommit quorum → commit →
  apply — and attributes each node's wall time to gossip (proposal +
  parts in flight), verify (commit-sig crypto inside ApplyBlock) and
  apply (the rest of ApplyBlock).
* `stall_report()` detects live-but-not-finalizing nodes: the process
  still emits records (live) but its height stopped while peers' tip
  moved on or its rounds churn in place. The classifier walks the
  message pipeline in causal order and names the first class of
  message the stuck node never received at its stuck height — which
  peer/message to go look at, not just "it's stuck".

Pure stdlib, no tracer dependency at runtime: analysis must run on a
laptop against sinks scp'd out of a broken testnet.
"""

from __future__ import annotations

import bisect
import json
import os
from collections import Counter, defaultdict

# Wire-message classes in causal pipeline order for one height: a node
# cannot prevote before it has the proposal + parts, cannot precommit
# before prevotes, cannot commit before precommits. The stall
# classifier reports the FIRST absent class, which is the earliest
# broken link in the chain.
PIPELINE_ORDER = ("proposal", "block_part", "prevote", "precommit")

# A node whose newest record is older than this (scaled by world span)
# is "dead" — crashed or shut down — and belongs to a different triage
# (restart it) than a live-but-stalled node (debug its message flow).
_LIVE_SLACK_S = 2.0
_ADVANCE_SLACK_S = 3.0


def load_records(path: str) -> list[dict]:
    """Parse one JSONL sink, skipping unparseable lines (a killed node
    may leave a truncated final record)."""
    out = []
    with open(path, "rb") as f:
        for line in f:
            try:
                rec = json.loads(line)
            except ValueError:
                continue
            if isinstance(rec, dict) and "ts" in rec and "name" in rec:
                out.append(rec)
    return out


def discover(paths) -> list[str]:
    """Expand files/directories into trace sink paths. A directory is
    searched for the runner layout (``node*/data/trace.jsonl``), a bare
    ``data/trace.jsonl`` and top-level ``*.jsonl`` files."""
    found: list[str] = []
    for p in paths:
        if os.path.isfile(p):
            found.append(p)
            continue
        if not os.path.isdir(p):
            continue
        direct = os.path.join(p, "data", "trace.jsonl")
        if os.path.isfile(direct):
            found.append(direct)
        for ent in sorted(os.listdir(p)):
            sub = os.path.join(p, ent)
            if os.path.isdir(sub):
                cand = os.path.join(sub, "data", "trace.jsonl")
                if os.path.isfile(cand):
                    found.append(cand)
            elif ent.endswith(".jsonl"):
                found.append(sub)
    # De-dup, preserve order.
    seen: set[str] = set()
    uniq = []
    for f in found:
        ap = os.path.abspath(f)
        if ap not in seen:
            seen.add(ap)
            uniq.append(f)
    return uniq


class NodeTrace:
    """One node's records plus the identity used to join them."""

    __slots__ = ("key", "name", "path", "records", "offset_s")

    def __init__(self, key: str, name: str, path: str, records: list[dict]):
        self.key = key
        self.name = name
        self.path = path
        self.records = records
        self.offset_s = 0.0


def _node_key(records: list[dict], path: str) -> str:
    for r in records:
        nid = r.get("node")
        if nid:
            return str(nid)
    pids = Counter(r.get("pid") for r in records if r.get("pid") is not None)
    if pids:
        return f"pid{pids.most_common(1)[0][0]}"
    return os.path.basename(os.path.dirname(path) or path)


def _node_name(records: list[dict], path: str, key: str) -> str:
    for r in records:
        if r.get("name") == "node.boot" and r.get("moniker"):
            mk = str(r["moniker"])
            if mk != "node":  # the config default is not a name
                return mk
    # Runner layout: .../node3/data/trace.jsonl -> "node3".
    parts = os.path.abspath(path).split(os.sep)
    for part in reversed(parts[:-1]):
        if part and part != "data":
            return part
    return key[:8]


def _match_key(r: dict):
    """Identity of one wire message as seen from both ends: the sender's
    p2p.send and the receiver's p2p.recv of the SAME frame carry the
    same classifier fields, which is what lets the merger pair them."""
    return (
        r.get("msg"), r.get("height"), r.get("round"),
        r.get("type"), r.get("idx"), r.get("step"), r.get("chan"),
        r.get("n"),
    )


def _estimate_offsets(traces: list[NodeTrace]) -> dict[str, float]:
    """Per-node clock offsets (seconds to SUBTRACT from raw ts)."""
    # Earliest send/recv per (src, dst, message identity). Min matters:
    # gossip can re-send the same vote after a reconnect, and pairing
    # a first send with a later re-delivery would inflate the delta.
    sends: dict[tuple, float] = {}
    recvs: dict[tuple, float] = {}
    for t in traces:
        for r in t.records:
            nm = r.get("name")
            if nm == "p2p.send":
                k = (t.key, r.get("peer"), _match_key(r))
                ts = r["ts"]
                if k not in sends or ts < sends[k]:
                    sends[k] = ts
            elif nm == "p2p.recv":
                k = (r.get("peer"), t.key, _match_key(r))
                ts = r["ts"]
                if k not in recvs or ts < recvs[k]:
                    recvs[k] = ts
    # Min delta per directed pair ~= latency_min + skew(dst) - skew(src).
    deltas: dict[tuple[str, str], float] = {}
    for k, sts in sends.items():
        rts = recvs.get(k)
        if rts is None:
            continue
        pair = (k[0], k[1])
        d = rts - sts
        if pair not in deltas or d < deltas[pair]:
            deltas[pair] = d
    fwd: dict[str, dict[str, float]] = defaultdict(dict)
    for (a, b), d in deltas.items():
        fwd[a][b] = d
    # Reference: the busiest sink (most records) — ties broken by key so
    # repeated merges of the same world pick the same reference.
    ref = max(traces, key=lambda t: (len(t.records), t.key)).key
    offsets = {ref: 0.0}
    queue = [ref]
    while queue:
        a = queue.pop(0)
        neighbors = set(fwd.get(a, ())) | {x for x in fwd if a in fwd[x]}
        for b in sorted(neighbors):
            if b in offsets:
                continue
            d_ab = fwd.get(a, {}).get(b)
            d_ba = fwd.get(b, {}).get(a)
            if d_ab is not None and d_ba is not None:
                theta = (d_ab - d_ba) / 2.0  # latency cancels
            elif d_ab is not None:
                theta = d_ab  # one-way: off by min latency, best we have
            else:
                theta = -d_ba
            offsets[b] = offsets[a] + theta
            queue.append(b)
    for t in traces:
        offsets.setdefault(t.key, 0.0)
    return offsets


class MergedTrace:
    """N aligned node traces plus the unified, time-sorted record list.

    Merged records are the loaded dicts with two additions: ``_node``
    (the owning node's key) and ``_t`` (skew-adjusted timestamp)."""

    def __init__(self, traces: list[NodeTrace]):
        self.traces = traces
        self.by_key = {t.key: t for t in traces}
        offsets = _estimate_offsets(traces)
        self.offsets = offsets
        self.records: list[dict] = []
        for t in traces:
            t.offset_s = offsets[t.key]
            for r in t.records:
                r["_node"] = t.key
                r["_t"] = r["ts"] - t.offset_s
                self.records.append(r)
        self.records.sort(key=lambda r: r["_t"])

    # -- naming ---------------------------------------------------------
    def display_name(self, key: str) -> str:
        t = self.by_key.get(key)
        return t.name if t is not None else str(key)[:8]

    def _peer_name(self, peer_id) -> str:
        """Map a wire peer id back to a merged node's display name."""
        if peer_id in self.by_key:
            return self.display_name(peer_id)
        return str(peer_id)[:8] if peer_id else "?"

    # -- basic queries ---------------------------------------------------
    def heights(self) -> list[int]:
        """All heights some node committed (consensus or blocksync)."""
        hs: set[int] = set()
        for r in self.records:
            if r.get("name") in ("consensus.finalize_commit", "blocksync.block"):
                h = r.get("height")
                if isinstance(h, int):
                    hs.add(h)
        return sorted(hs)

    def tx_lifecycles(self) -> dict[str, list[dict]]:
        """tx hex -> that tx's ``tx.lifecycle`` records across every
        node, in aligned time order (tools/latency_analyze.py input).
        Records carry the merge additions ``_node``/``_t`` plus the
        emitter's ``stage`` and within-process ``mono`` clock."""
        out: dict[str, list[dict]] = defaultdict(list)
        for r in self.records:
            if r.get("name") == "tx.lifecycle" and r.get("tx"):
                out[str(r["tx"])].append(r)
        return dict(out)

    def timeline(self, height: int | None = None,
                 names: set[str] | None = None) -> list[dict]:
        out = []
        for r in self.records:
            if height is not None and r.get("height") != height:
                continue
            if names is not None and r.get("name") not in names:
                continue
            out.append(r)
        return out

    # -- critical path ---------------------------------------------------
    def critical_path(self, height: int) -> dict:
        """Reconstruct the commit pipeline for one height.

        Anchor is the proposer's earliest ``p2p.send`` of the proposal
        (fallback: first block part). Per node, the consensus step
        spans for the height give propose/prevote/precommit durations,
        the apply_block span splits into verify (validate_ms — the
        commit-sig crypto) and apply (the rest), and gossip is the
        in-flight time from the anchor to the node's last proposal/part
        receipt. The slowest committer defines the wall clock."""
        rep: dict = {
            "height": height, "committed": False, "proposer": None,
            "anchor_t": None, "wall_ms": None, "per_node": {},
            "phase_ms": {}, "slowest": None,
        }
        # self.records is time-sorted, so the first matching send is the
        # earliest; a proposal anchor is preferred over a bare part (a
        # restarting node may re-gossip parts before any proposal).
        anchor = None
        for r in self.records:
            if (r.get("name") == "p2p.send" and r.get("height") == height
                    and r.get("msg") in ("proposal", "block_part")):
                if anchor is None or (anchor["msg"] != "proposal"
                                      and r["msg"] == "proposal"):
                    anchor = r
        if anchor is not None:
            rep["anchor_t"] = anchor["_t"]
            rep["proposer"] = self.display_name(anchor["_node"])

        phase_max: dict[str, float] = {}
        commit_ts: dict[str, float] = {}
        for t in self.traces:
            nd: dict = {}
            last_data_recv = None
            step_ms: dict[str, float] = {}
            apply_rec = None
            commit_t = None
            commit_round = None
            for r in t.records:
                if r.get("height") != height:
                    continue
                nm = r.get("name")
                if nm == "consensus.step":
                    step = r.get("step")
                    if step:
                        step_ms[step] = step_ms.get(step, 0.0) + \
                            float(r.get("dur_ms") or 0.0)
                elif nm == "consensus.finalize_commit":
                    commit_t = r["_t"]
                    commit_round = r.get("round")
                elif nm == "state.apply_block":
                    apply_rec = r
                elif nm == "blocksync.block":
                    if commit_t is None:
                        commit_t = r["_t"]
                    if apply_rec is None:
                        apply_rec = r
                elif nm == "p2p.recv" and r.get("msg") in (
                        "proposal", "block_part"):
                    if last_data_recv is None or r["_t"] > last_data_recv:
                        last_data_recv = r["_t"]
            for step, label in (("PROPOSE", "propose_ms"),
                                ("PREVOTE", "prevote_ms"),
                                ("PRECOMMIT", "precommit_ms")):
                if step in step_ms:
                    nd[label] = round(step_ms[step], 3)
            if anchor is not None and last_data_recv is not None:
                nd["gossip_ms"] = round(
                    max(0.0, (last_data_recv - anchor["_t"]) * 1e3), 3)
            if apply_rec is not None:
                if apply_rec.get("name") == "state.apply_block":
                    verify = float(apply_rec.get("validate_ms") or 0.0)
                    total = float(apply_rec.get("dur_ms") or 0.0)
                    nd["verify_ms"] = round(verify, 3)
                    nd["apply_ms"] = round(max(0.0, total - verify), 3)
                else:  # blocksync span has its own split
                    nd["verify_ms"] = round(
                        float(apply_rec.get("verify_ms") or 0.0), 3)
                    nd["apply_ms"] = round(
                        float(apply_rec.get("apply_ms") or 0.0), 3)
            if commit_t is not None:
                commit_ts[t.key] = commit_t
                nd["commit_t"] = commit_t
                if commit_round is not None:
                    nd["commit_round"] = commit_round
                if anchor is not None:
                    nd["commit_latency_ms"] = round(
                        max(0.0, (commit_t - anchor["_t"]) * 1e3), 3)
            if nd:
                rep["per_node"][t.name] = nd
                for k, v in nd.items():
                    if k.endswith("_ms"):
                        phase_max[k] = max(phase_max.get(k, 0.0), v)
        rep["committed"] = bool(commit_ts)
        rep["phase_ms"] = {k: round(v, 3) for k, v in phase_max.items()}
        if commit_ts:
            slowest_key = max(commit_ts, key=lambda k: commit_ts[k])
            rep["slowest"] = self.display_name(slowest_key)
            if anchor is not None:
                rep["wall_ms"] = round(
                    max(0.0, (commit_ts[slowest_key] - anchor["_t"]) * 1e3), 3)
        return rep

    # -- stall triage ----------------------------------------------------
    def stall_report(self) -> dict:
        """Classify live-but-not-finalizing nodes.

        A node is STALLED when it is still emitting records (live) but
        its committed height lags the world tip by >= 2 or its rounds
        churn (round >= 2) at a height it cannot finish, and it has not
        advanced for a while. For each stalled node the classifier
        walks PIPELINE_ORDER at the stuck height and names the first
        message class with zero receipts — plus, when peers are already
        past that height, which connected peers never sent the catchup
        (stored-commit precommit) votes it needs."""
        if not self.records:
            return {"status": "empty", "tip": None, "nodes": {},
                    "stalled": []}
        world_start = self.records[0]["_t"]
        world_end = self.records[-1]["_t"]
        span = max(0.0, world_end - world_start)
        live_slack = max(_LIVE_SLACK_S, 0.1 * span)
        advance_slack = max(_ADVANCE_SLACK_S, 0.2 * span)

        nodes: dict[str, dict] = {}
        tip = 0
        for t in self.traces:
            last_t = world_start
            committed = 0
            advance_t = None
            cur_height = None
            cur_height_t = None
            for r in t.records:
                if r["_t"] > last_t:
                    last_t = r["_t"]
                nm = r.get("name")
                if nm in ("consensus.finalize_commit", "blocksync.block"):
                    h = r.get("height")
                    if isinstance(h, int) and h > committed:
                        committed = h
                        advance_t = r["_t"]
                elif nm == "consensus.step":
                    h = r.get("height")
                    if isinstance(h, int) and (
                            cur_height_t is None or r["_t"] >= cur_height_t):
                        cur_height = h
                        cur_height_t = r["_t"]
            if cur_height is None:
                cur_height = committed + 1 if committed else None
            max_round = 0
            if cur_height is not None:
                for r in t.records:
                    if (r.get("name") == "consensus.step"
                            and r.get("height") == cur_height):
                        rd = r.get("round")
                        if isinstance(rd, int) and rd > max_round:
                            max_round = rd
            tip = max(tip, committed)
            nodes[t.key] = {
                "name": t.name, "committed": committed,
                "height": cur_height, "max_round": max_round,
                "last_t": last_t, "advance_t": advance_t,
                "offset_s": round(t.offset_s, 6),
                "records": len(t.records),
            }

        stalled = []
        for t in self.traces:
            info = nodes[t.key]
            live = (world_end - info["last_t"]) <= live_slack
            info["live"] = live
            gap = world_end - (info["advance_t"]
                               if info["advance_t"] is not None
                               else world_start)
            lagging = tip - info["committed"] >= 2
            churning = info["max_round"] >= 2
            if not (live and gap > advance_slack and (lagging or churning)):
                continue
            h = info["height"]
            recv_counts: Counter = Counter()
            votes_by_peer: Counter = Counter()
            peers_seen: set = set()
            for r in t.records:
                if r.get("name") != "p2p.recv":
                    continue
                peers_seen.add(r.get("peer"))
                if r.get("height") != h:
                    continue
                msg = r.get("msg")
                cls = r.get("type") if msg == "vote" else msg
                if cls in PIPELINE_ORDER:
                    recv_counts[cls] += 1
                    if cls == "precommit":
                        votes_by_peer[r.get("peer")] += 1
            missing = [c for c in PIPELINE_ORDER if recv_counts[c] == 0]
            first_missing = missing[0] if missing else None
            silent_peers = sorted(
                self._peer_name(p) for p in peers_seen
                if p is not None and votes_by_peer[p] == 0)
            if tip > (info["committed"] or 0) and recv_counts["precommit"] == 0:
                # Peers are past this height: finishing it needs the
                # stored commit's precommits (catchup votes), and none
                # arrived. That beats an earlier missing class for
                # triage because the block data may simply be what the
                # node already has from before it stalled.
                if "precommit" in missing:
                    first_missing = "precommit"
                detail = (
                    f"peers are at height {tip} but no catchup precommit "
                    f"votes for height {h} ever arrived"
                    + (f"; connected peers never gossiping them: "
                       f"{', '.join(silent_peers)}" if silent_peers else "")
                )
            elif first_missing is not None:
                detail = (f"no {first_missing} received at height {h} "
                          f"(rounds reached {info['max_round']})")
            else:
                detail = (f"all message classes seen at height {h} yet no "
                          f"commit; rounds reached {info['max_round']}")
            stalled.append({
                "node": info["name"], "node_id": t.key, "height": h,
                "committed": info["committed"], "max_round": info["max_round"],
                "first_missing": first_missing, "missing": missing,
                "recv_counts": dict(recv_counts),
                "silent_peers": silent_peers,
                "stalled_for_s": round(gap, 3), "detail": detail,
            })
        return {
            "status": "stall" if stalled else "ok",
            "tip": tip or None,
            "span_s": round(span, 3),
            "nodes": {nodes[k]["name"]: {kk: vv for kk, vv in nodes[k].items()
                                         if kk != "name"}
                      for k in nodes},
            "stalled": stalled,
        }

    def summary(self) -> dict:
        hs = self.heights()
        return {
            "nodes": {
                t.name: {
                    "node_id": t.key, "path": t.path,
                    "records": len(t.records),
                    "offset_s": round(t.offset_s, 6),
                } for t in self.traces
            },
            "records": len(self.records),
            "heights": {"min": hs[0], "max": hs[-1]} if hs else None,
            "tenants": self.tenant_rollup() or None,
        }

    def tenant_rollup(self) -> dict:
        """Per-tenant share of the shared verify scheduler's coalesced
        dispatches (crypto.sched_coalesce spans): how many dispatches
        each tenant rode in, its signature volume, and the dispatch
        wall it shared. Empty when no scheduler spans were recorded."""
        out: dict[str, dict] = {}
        for r in self.records:
            if r.get("name") != "crypto.sched_coalesce":
                continue
            per = r.get("per_tenant_sigs") or {}
            dur = float(r.get("dur_ms", 0.0) or 0.0)
            for tenant, sigs in per.items():
                agg = out.setdefault(
                    tenant, {"dispatches": 0, "sigs": 0, "ms": 0.0})
                agg["dispatches"] += 1
                agg["sigs"] += int(sigs)
                agg["ms"] += dur
        for agg in out.values():
            agg["ms"] = round(agg["ms"], 3)
        return out


def merge(paths) -> MergedTrace:
    """Load + align the sinks under `paths` (files or directories)."""
    files = discover(paths)
    traces = []
    for f in files:
        records = load_records(f)
        if not records:
            continue
        key = _node_key(records, f)
        name = _node_name(records, f, key)
        traces.append(NodeTrace(key, name, f, records))
    if not traces:
        raise ValueError(f"no trace records found under {list(paths)!r}")
    # Two sinks claiming the same key (in-process worlds sharing one
    # tracer) stay separate traces; suffix for unique dict keys.
    seen: dict[str, int] = {}
    for t in traces:
        n = seen.get(t.key, 0)
        seen[t.key] = n + 1
        if n:
            t.key = f"{t.key}#{n}"
    return MergedTrace(traces)


# ----------------------------------------------------------------------
# the join of a span sink with a profiler trace (ISSUE 24): why was the
# chip idle, and which phase of which program kept it busy
# ----------------------------------------------------------------------
NO_SPAN = "host:outside any span"
NO_SCOPE = "(no scope)"
# the annotations inside which a device program is launched: an idle gap
# of the device is the business of the thread that runs them
LAUNCH_SPANS = ("crypto.device_launch", "crypto.mesh_submit")


def _union(intervals) -> list[list[float]]:
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _innermost_segments(spans: list[dict]) -> list[tuple]:
    """[(t0, t1, span)] over the stretches where some span of ONE thread
    is open, the span being the innermost one: the shortest that covers
    the stretch (spans of one thread nest, so the shortest is the
    deepest; spans of several threads do not, and device_join calls
    this a thread at a time)."""
    edges = sorted({t for sp in spans
                    for t in (sp["start_ns"], sp["start_ns"] + sp["dur_ns"])})
    starts = sorted(spans, key=lambda sp: sp["start_ns"])
    out, active, nxt = [], [], 0
    for t0, t1 in zip(edges, edges[1:]):
        while nxt < len(starts) and starts[nxt]["start_ns"] <= t0:
            active.append(starts[nxt])
            nxt += 1
        active = [sp for sp in active
                  if sp["start_ns"] + sp["dur_ns"] >= t1]
        if active:
            out.append((t0, t1, min(active, key=lambda sp: sp["dur_ns"])))
    return out


def _scope_of(op_name: str, scopes) -> tuple[str | None, str | None]:
    """(phase, kernel) of an operation's op_name: its outermost and its
    innermost component that is a registered kernel scope. The phase is
    what device time is booked to; a pallas kernel's name= lies inside
    its phase ("jit(f)/ladder.double_scalar/curve_ladder_sub_mul8/
    pallas_call") and names the operation in place of its HLO
    instruction ("tpu_custom_call.27"). kernel is None where the two are
    one."""
    found = [part for part in op_name.split("/") if part in scopes]
    if not found:
        return None, None
    return found[0], found[-1] if found[-1] != found[0] else None


def _device_time_by_scope(ops: list[dict], lo: float, hi: float,
                          scopes) -> tuple[dict, dict]:
    """Self time of every operation inside [lo, hi) by kernel scope, and
    by (scope, op). An operation that encloses others on its line (a
    while loop and its body) keeps only the time its children leave. A
    scope is the operation's own (from its op_name), else the enclosing
    operation's, else the one most of the time of its nearest scoped
    descendants carries (which its unscoped children then inherit); the
    second result says how much time was booked each way."""
    ops = sorted((o for o in ops if o["start_ns"] < hi
                  and o["start_ns"] + o["dur_ns"] > lo),
                 key=lambda o: (o["start_ns"], -o["dur_ns"]))
    nodes, stack = [], []
    for o in ops:
        own, kernel = _scope_of(o.get("op_name") or "", scopes)
        node = {"op": kernel or o["op"], "s": max(o["start_ns"], lo),
                "e": min(o["start_ns"] + o["dur_ns"], hi),
                "own": own, "kids": []}
        while stack and stack[-1]["e"] <= node["s"]:
            stack.pop()
        (stack[-1]["kids"] if stack else nodes).append(node)
        stack.append(node)
    by_scope: dict[str, float] = defaultdict(float)
    by_op: dict[tuple, float] = defaultdict(float)
    how: dict[str, float] = defaultdict(float)

    def carried(node) -> dict:
        """Time by scope of the nearest descendants that name one."""
        out: dict[str, float] = defaultdict(float)
        for kid in node["kids"]:
            if kid["own"]:
                out[kid["own"]] += kid["e"] - kid["s"]
            else:
                for k, v in carried(kid).items():
                    out[k] += v
        return out

    def book(node, inherited) -> None:
        scope, way = node["own"] or inherited, "own"
        if not node["own"]:
            way = "enclosing"
            if scope is None:
                sub = carried(node)
                if sub:
                    scope = max(sub.items(), key=lambda kv: kv[1])[0]
                    way = "children"
        self_ns = (node["e"] - node["s"]
                   - sum(k["e"] - k["s"] for k in node["kids"]))
        by_scope[scope or NO_SCOPE] += self_ns
        by_op[(scope or NO_SCOPE, node["op"])] += self_ns
        how[way if scope else "none"] += self_ns
        for kid in node["kids"]:
            book(kid, scope)

    for node in nodes:
        book(node, None)
    return ({"scope": dict(by_scope), "op": dict(by_op)}, dict(how))


def _host_device_skew(launches: list[dict], planes: list[dict]):
    """(ns, pairs): the least by which the device's clock runs ahead of
    the host's, and how many launches that rests on; (None, 0) where no
    launch finds a program run. Each launch annotation looks for its
    run among one device's (the "XLA Modules" line) around itself,
    never by count:

    * the last run that began BEFORE it, behind the launch before it, if
      the device had been idle for longer than the run is early (a run
      that begins where another ends was queued behind that one: the
      second program of a launch, a batch behind a batch): the clocks
      disagree by at least that lead;
    * else the first run that began inside the launch's interval, up to
      the next launch: it follows its launch as it should.

    A run answers one launch; the skew is the largest lead."""
    starts = [sp["start_ns"] for sp in launches]
    for p in planes:
        runs = sorted(p.get("modules", ()), key=lambda m: m["start_ns"])
        at = [m["start_ns"] for m in runs]
        taken: set[int] = set()
        lead, pairs = 0.0, 0
        for i, s in enumerate(starts):
            before = starts[i - 1] if i else float("-inf")
            k = bisect.bisect_left(at, s) - 1  # the last run before s
            if k >= 0 and k not in taken and at[k] > before and (
                    k == 0 or at[k] - (at[k - 1] + runs[k - 1]["dur_ns"])
                    > s - at[k]):
                lead = max(lead, s - at[k])
            else:
                k += 1
                while k in taken:
                    k += 1
                nxt = starts[i + 1] if i + 1 < len(starts) else float("inf")
                if k >= len(at) or at[k] >= nxt:
                    continue
            taken.add(k)
            pairs += 1
        if pairs:
            return lead, pairs
    return None, 0


def device_join(xp: dict, records: list[dict] | None = None,
                stretch: tuple[float, float] | None = None,
                scopes=()) -> dict:
    """Join a profiler trace (utils/xplane.load) with a span sink.

    For the stretch [lo, hi) in ns since the session began (default:
    first to last event kept): the busiest device's idle time by the
    innermost PROGRAM span of the thread that LAUNCHES, and every
    device's busy time by kernel scope (`scopes`: trace.KERNEL_SCOPES).
    An idle gap is booked to one thread: that of the launch which ended
    it (the last annotation of LAUNCH_SPANS that began before the
    device operation closing the gap; the trailing gap goes to the last
    launch's thread, a gap before every launch to the first's), by that
    thread's innermost span over the gap, and to "outside any span"
    where that thread was in none: what a caller's thread was in says
    nothing of why the device is empty. Threads are the host plane's
    lines (a span's `line`), else the sink's `tid`; a trace without a
    launch annotation is booked as one thread. With `records` an idle
    row is labelled by the span's whole ancestry ("root > ... > leaf",
    joined to the sink by span id), which tells a crypto.batch_verify
    under verify_commit from one under a replay window, and a thread
    by its name (trace.thread); without, by the span's name alone.

    The two clocks: `host_device_skew_ms` is the least the device's
    clock must run ahead of the host's, read from `skew_pairs` launches
    that found their program's run (_host_device_skew); the device's
    timeline is moved later by it before anything is booked. Where no
    launch finds a run it is None and nothing moves."""
    dev = [p for p in xp["planes"] if p.get("ops")]
    spans = [sp for p in xp["planes"] for sp in p.get("spans", [])]
    launched = sorted((sp for sp in spans if sp["name"] in LAUNCH_SPANS),
                      key=lambda sp: sp["start_ns"])
    skew, skew_pairs = _host_device_skew(launched, dev)
    if skew:
        dev = [dict(p, ops=[dict(o, start_ns=o["start_ns"] + skew)
                            for o in p["ops"]]) for p in dev]
    times = [t for p in dev for o in p["ops"]
             for t in (o["start_ns"], o["start_ns"] + o["dur_ns"])]
    times += [t for sp in spans
              for t in (sp["start_ns"], sp["start_ns"] + sp["dur_ns"])]
    if not times:
        raise ValueError("the profiler trace holds no device operation "
                         "and no program span")
    lo, hi = stretch if stretch is not None else (min(times), max(times))

    busiest, per_dev = None, []
    scope_ns: dict[str, float] = defaultdict(float)
    op_ns: dict[tuple, float] = defaultdict(float)
    how_ns: dict[str, float] = defaultdict(float)
    for p in dev:
        busy = _union((max(o["start_ns"], lo),
                       min(o["start_ns"] + o["dur_ns"], hi))
                      for o in p["ops"] if o["start_ns"] < hi
                      and o["start_ns"] + o["dur_ns"] > lo)
        total = sum(e - s for s, e in busy)
        if total <= 0:
            continue
        per_dev.append((p["name"], total))
        if busiest is None or total > busiest[1]:
            busiest = (p["name"], total, busy)
        by, how = _device_time_by_scope(p["ops"], lo, hi, scopes)
        for k, v in by["scope"].items():
            scope_ns[k] += v
        for k, v in by["op"].items():
            op_ns[k] += v
        for k, v in how.items():
            how_ns[k] += v

    by_id = {r["id"]: r for r in records or ()
             if r.get("kind") == "span" and "id" in r}
    thread_names = {r["tid"]: r.get("thread") for r in records or ()
                    if r.get("name") == "trace.thread"}

    def label(sp: dict) -> str:
        names, rec = [sp["name"]], by_id.get(sp["span_id"])
        while rec is not None and rec.get("parent") is not None:
            rec = by_id.get(rec["parent"])
            if rec is not None:
                names.append(rec["name"])
        return " > ".join(reversed(names))

    def thread_of(sp: dict):
        if sp.get("line") is not None:
            return sp["line"]
        return by_id.get(sp["span_id"], {}).get("tid")

    # one thread's spans nest; without a launch to go by, every span is
    # booked as if one thread had written it
    by_thread: dict = defaultdict(list)
    for sp in spans:
        by_thread[thread_of(sp) if launched else None].append(sp)
    segs = {th: _innermost_segments(sps) for th, sps in by_thread.items()}
    ends = {th: [t1 for _t0, t1, _sp in sg] for th, sg in segs.items()}
    launch_starts = [sp["start_ns"] for sp in launched]

    idle: dict[str, float] = defaultdict(float)
    idle_thread: dict = defaultdict(float)
    if busiest is not None:
        edges = [lo] + [t for iv in busiest[2] for t in iv] + [hi]
        for g0, g1 in zip(edges[0::2], edges[1::2]):
            if g1 <= g0:
                continue
            th = None
            if launched:
                k = bisect.bisect_right(launch_starts, g1) - 1
                th = thread_of(launched[max(k, 0)])
            idle_thread[th] += g1 - g0
            covered = 0.0
            first = bisect.bisect_right(ends.get(th, ()), g0)
            for t0, t1, sp in segs.get(th, ())[first:]:
                if t0 >= g1:
                    break
                part = min(t1, g1) - max(t0, g0)
                if part > 0:
                    idle[label(sp)] += part
                    covered += part
            idle[NO_SPAN] += (g1 - g0) - covered
    idle_ns = sum(idle.values())
    busy_ns = sum(scope_ns.values())

    def thread_label(th) -> str:
        if not launched:
            return "every thread"
        tids = {by_id[sp["span_id"]].get("tid") for sp in by_thread[th]
                if sp["span_id"] in by_id} - {None}
        tid = tids.pop() if len(tids) == 1 else None
        return thread_names.get(tid) or f"thread {tid or th}"

    def top(d: dict, n: int = 0) -> list:
        rows = sorted(d.items(), key=lambda kv: -kv[1])
        return [[k, v / 1e9] for k, v in (rows[:n] if n else rows)
                if v > 0]

    return {
        "stretch_s": (hi - lo) / 1e9,
        "devices": [[name, ns / 1e9] for name, ns in per_dev],
        "busiest": busiest[0] if busiest else None,
        "busy_s": busiest[1] / 1e9 if busiest else 0.0,
        "idle_s": idle_ns / 1e9,
        "idle_by_span": top(idle),
        "idle_named_share": (1.0 - idle.get(NO_SPAN, 0.0) / idle_ns
                             if idle_ns else None),
        "idle_by_thread": top({thread_label(th): v
                               for th, v in idle_thread.items()}),
        "launches": len(launched),
        "threads": len(by_thread),
        "host_device_skew_ms": None if skew is None else skew / 1e6,
        "skew_pairs": skew_pairs,
        "busy_by_scope": top(scope_ns),
        "busy_scoped_share": (1.0 - scope_ns.get(NO_SCOPE, 0.0) / busy_ns
                              if busy_ns else None),
        "busy_booked_by": top(how_ns),
        "ops_by_scope": {
            scope: top({op: v for (sc, op), v in op_ns.items()
                        if sc == scope}, 5)
            for scope in scope_ns},
        "spans_joined": sum(1 for sp in spans if sp["span_id"] in by_id),
        "spans_in_trace": len(spans),
    }


def render_device_join(j: dict) -> str:
    lines = ["stretch %.4f s; %d device(s) ran operations; busiest %s: "
             "busy %.4f s, idle %.4f s (%.1f%%)" % (
                 j["stretch_s"], len(j["devices"]), j["busiest"],
                 j["busy_s"], j["idle_s"],
                 100 * j["idle_s"] / j["stretch_s"] if j["stretch_s"] else 0)]
    lines.append("program spans in the trace: %d on %d thread(s), of them "
                 "in the sink: %d; launches: %d" % (
                     j["spans_in_trace"], j["threads"], j["spans_joined"],
                     j["launches"]))
    lines.append(
        "host_device_skew_ms: none (no launch found its program's run; "
        "nothing moved)" if j["host_device_skew_ms"] is None else
        "host_device_skew_ms: %.3f (the most a program began before the "
        "annotation that launched it, of %d launches that found their "
        "run; the device's timeline is moved later by it)" % (
            j["host_device_skew_ms"], j["skew_pairs"]))
    if j["idle_named_share"] is not None:
        lines.append("idle time of the busiest device by the thread whose "
                     "launch ended the gap:")
        for name, s in j["idle_by_thread"]:
            lines.append("  %9.4f s  %5.1f%%  %s" % (
                s, 100 * s / j["idle_s"], name))
        lines.append("... and by the innermost program span that thread "
                     "was in (%.1f%% inside a span):"
                     % (100 * j["idle_named_share"]))
        for name, s in j["idle_by_span"]:
            lines.append("  %9.4f s  %5.1f%%  %s" % (
                s, 100 * s / j["idle_s"], name))
    if j["busy_scoped_share"] is not None:
        busy = sum(s for _k, s in j["busy_by_scope"])
        lines.append("device time by kernel scope, all devices (%.1f%% "
                     "under a registered scope; booked by: %s):" % (
                         100 * j["busy_scoped_share"],
                         ", ".join("%s %.1f%%" % (k, 100 * s / busy)
                                   for k, s in j["busy_booked_by"])))
        for scope, s in j["busy_by_scope"]:
            ops = ", ".join("%s %.4f" % (op, t)
                            for op, t in j["ops_by_scope"][scope])
            lines.append("  %9.4f s  %5.1f%%  %-20s %s" % (
                s, 100 * s / busy, scope, ops))
    return "\n".join(lines)


# ----------------------------------------------------------------------
# a thread's wall time by what it was doing (ISSUE 38): the sink alone,
# no profiler
# ----------------------------------------------------------------------
def thread_table(records: list[dict], wait_spans=()) -> list[dict]:
    """One row a thread that wrote spans (`tid`, named by its
    trace.thread record), over the window its spans cover, first t0_ns
    to last t1_ns: `in_spans_ms`, the wall time inside its root spans,
    of which `cpu_ms` on a CPU (the roots' cpu_ms) and `wait_ms` inside
    the spans named in `wait_spans` (trace.WAIT_SPANS: the thread only
    waits there, and chose to); `other_ms` is what is left of
    in_spans_ms: off a CPU anywhere else, which is another thread's turn
    at the interpreter, the OS, or native code's threads working for
    this one. `outside_ms` is the rest of the window. Only spans made by
    trace.span() count (they carry self_ms). The few percent a wait span
    spends on a CPU (its wake-ups) are in both columns and missing from
    other_ms."""
    names = {(r.get("pid"), r["tid"]): r.get("thread") for r in records
             if r.get("name") == "trace.thread" and "tid" in r}
    rows: dict = {}
    for r in records:
        if r.get("kind") != "span" or "self_ms" not in r or "tid" not in r:
            continue
        key = (r.get("pid"), r["tid"])
        row = rows.get(key)
        if row is None:
            row = rows[key] = {
                "pid": key[0], "tid": key[1], "thread": names.get(key),
                "spans": 0, "t0_ns": r["t0_ns"], "t1_ns": r["t1_ns"],
                "in_spans_ms": 0.0, "cpu_ms": 0.0, "wait_ms": 0.0}
        row["spans"] += 1
        row["t0_ns"] = min(row["t0_ns"], r["t0_ns"])
        row["t1_ns"] = max(row["t1_ns"], r["t1_ns"])
        row["in_spans_ms"] += r["self_ms"]
        row["cpu_ms"] += r.get("cpu_ms", 0.0)
        if r["name"] in wait_spans:
            row["wait_ms"] += r["self_ms"]
    out = []
    for row in sorted(rows.values(), key=lambda x: x["t0_ns"]):
        row["other_ms"] = row["in_spans_ms"] - row["cpu_ms"] - row["wait_ms"]
        row["window_ms"] = (row.pop("t1_ns") - row.pop("t0_ns")) / 1e6
        row["outside_ms"] = row["window_ms"] - row["in_spans_ms"]
        out.append({k: round(v, 3) if isinstance(v, float) else v
                    for k, v in row.items()})
    return out


def render_thread_table(rows: list[dict]) -> str:
    lines = ["a thread's window (first to last span) by what it was in; "
             "percent of the window",
             "%-24s %9s %7s %10s | %8s %8s %8s %8s" % (
                 "thread", "tid", "spans", "window s", "on CPU",
                 "waits", "interp/OS", "no span")]
    for r in rows:
        w = r["window_ms"] or 1.0
        lines.append("%-24s %9s %7d %10.3f | %7.1f%% %7.1f%% %7.1f%% "
                     "%7.1f%%" % (
                         r["thread"] or "?", r["tid"], r["spans"],
                         r["window_ms"] / 1e3, 100 * r["cpu_ms"] / w,
                         100 * r["wait_ms"] / w, 100 * r["other_ms"] / w,
                         100 * r["outside_ms"] / w))
    return "\n".join(lines)


# ----------------------------------------------------------------------
# text renderers (tools/trace_analyze.py and the e2e runner's report)
# ----------------------------------------------------------------------
def render_summary(mt: MergedTrace) -> str:
    s = mt.summary()
    lines = ["flight recorder: %d records from %d node(s)" % (
        s["records"], len(s["nodes"]))]
    if s["heights"]:
        lines.append("heights committed: %d..%d" % (
            s["heights"]["min"], s["heights"]["max"]))
    for name, info in s["nodes"].items():
        lines.append("  %-12s id=%s.. offset=%+.3fms records=%d" % (
            name, str(info["node_id"])[:8], info["offset_s"] * 1e3,
            info["records"]))
    if s.get("tenants"):
        lines.append("verify scheduler tenants:")
        for tenant, agg in sorted(s["tenants"].items()):
            lines.append(
                "  %-16s dispatches=%d sigs=%d shared_wall=%.1fms" % (
                    tenant, agg["dispatches"], agg["sigs"], agg["ms"]))
    return "\n".join(lines)


def render_timeline(records: list[dict], mt: MergedTrace,
                    limit: int = 0) -> str:
    if not records:
        return "(no records)"
    shown = records[-limit:] if limit else records
    t0 = records[0]["_t"]
    lines = []
    if limit and len(records) > limit:
        lines.append(f"... ({len(records) - limit} earlier records elided)")
    for r in shown:
        extra = []
        for k in ("height", "round", "step", "msg", "type", "idx",
                  "dur_ms", "validate_ms", "verify_ms", "txs"):
            if k in r:
                extra.append(f"{k}={r[k]}")
        if "peer" in r:
            extra.append(f"peer={mt._peer_name(r['peer'])}")
        lines.append("%10.3fs %-10s %-24s %s" % (
            r["_t"] - t0, mt.display_name(r["_node"]), r["name"],
            " ".join(extra)))
    return "\n".join(lines)


def render_critical_path(cp: dict) -> str:
    h = cp["height"]
    if not cp["per_node"]:
        return f"height {h}: no records"
    lines = [
        "height %d: %s  wall=%s  proposer=%s  slowest=%s" % (
            h, "committed" if cp["committed"] else "NOT COMMITTED",
            ("%.1fms" % cp["wall_ms"]) if cp["wall_ms"] is not None else "?",
            cp["proposer"] or "?", cp["slowest"] or "?"),
    ]
    cols = ("gossip_ms", "propose_ms", "prevote_ms", "precommit_ms",
            "verify_ms", "apply_ms", "commit_latency_ms")
    lines.append("  %-12s %s" % ("node", " ".join("%11s" % c.replace("_ms", "")
                                                  for c in cols)))
    for name in sorted(cp["per_node"]):
        nd = cp["per_node"][name]
        cells = " ".join(
            "%11s" % (("%.1f" % nd[c]) if c in nd else "-") for c in cols)
        lines.append("  %-12s %s" % (name, cells))
    if cp["phase_ms"]:
        lines.append("  worst-node phase maxima: " + "  ".join(
            "%s=%.1fms" % (k.replace("_ms", ""), v)
            for k, v in sorted(cp["phase_ms"].items())))
    return "\n".join(lines)


def render_stall_report(rep: dict) -> str:
    if rep["status"] == "empty":
        return "stall triage: no records"
    lines = ["stall triage: %s (tip height %s, world span %.1fs)" % (
        rep["status"].upper(), rep["tip"], rep["span_s"])]
    for name, info in sorted(rep["nodes"].items()):
        lines.append(
            "  %-12s committed=%-5s at_height=%-5s max_round=%-3s "
            "live=%s" % (name, info["committed"], info["height"],
                         info["max_round"], info.get("live")))
    for s in rep["stalled"]:
        lines.append("  STALLED %s: stuck at height %s for %.1fs "
                     "(rounds up to %s)" % (
                         s["node"], s["height"], s["stalled_for_s"],
                         s["max_round"]))
        lines.append("    first missing message class: %s" %
                     (s["first_missing"] or "none"))
        lines.append("    %s" % s["detail"])
        if s["recv_counts"]:
            lines.append("    received at stuck height: " + ", ".join(
                "%s=%d" % (k, v) for k, v in sorted(s["recv_counts"].items())))
    if rep["status"] == "ok":
        lines.append("  no live-but-stalled node detected")
    return "\n".join(lines)
