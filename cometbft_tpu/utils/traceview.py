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


def _union(intervals) -> list[list[float]]:
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _innermost_segments(spans: list[dict]) -> list[tuple]:
    """[(t0, t1, span)] over the stretches where some span is open, the
    span being the innermost one: the shortest that covers the stretch
    (spans of one thread nest, so the shortest is the deepest)."""
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


def device_join(xp: dict, records: list[dict] | None = None,
                stretch: tuple[float, float] | None = None,
                scopes=()) -> dict:
    """Join a profiler trace (utils/xplane.load) with a span sink.

    For the stretch [lo, hi) in ns since the session began (default:
    first to last event kept): the busiest device's idle time by the
    innermost PROGRAM span the host was in, and every device's busy time
    by kernel scope (`scopes`: trace.KERNEL_SCOPES). With `records` an
    idle row is labelled by the span's whole ancestry ("root > ... >
    leaf", joined to the sink by span id), which tells a
    crypto.batch_verify under verify_commit from one under a replay
    window; without, by the span's name alone."""
    dev = [p for p in xp["planes"] if p.get("ops")]
    spans = [sp for p in xp["planes"] for sp in p.get("spans", [])]
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

    def label(sp: dict) -> str:
        names, rec = [sp["name"]], by_id.get(sp["span_id"])
        while rec is not None and rec.get("parent") is not None:
            rec = by_id.get(rec["parent"])
            if rec is not None:
                names.append(rec["name"])
        return " > ".join(reversed(names))

    idle: dict[str, float] = defaultdict(float)
    if busiest is not None:
        edges = [lo] + [t for iv in busiest[2] for t in iv] + [hi]
        gaps = [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]
        segs = _innermost_segments(spans)
        i = 0
        for g0, g1 in gaps:
            covered = 0.0
            while i < len(segs) and segs[i][1] <= g0:
                i += 1
            j = i
            while j < len(segs) and segs[j][0] < g1:
                t0, t1, sp = segs[j]
                part = min(t1, g1) - max(t0, g0)
                if part > 0:
                    idle[label(sp)] += part
                    covered += part
                j += 1
            idle[NO_SPAN] += (g1 - g0) - covered
    idle_ns = sum(idle.values())
    busy_ns = sum(scope_ns.values())

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
    lines.append("program spans in the trace: %d, of them in the sink: %d"
                 % (j["spans_in_trace"], j["spans_joined"]))
    if j["idle_named_share"] is not None:
        lines.append("idle time of the busiest device by the innermost "
                     "program span the host was in (%.1f%% inside a span):"
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
