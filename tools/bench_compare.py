#!/usr/bin/env python3
"""Compare fresh bench/workload JSON against the last committed round.

Loads the working-tree copies of the benchmark artifacts (default:
WORKLOADS.json) and their committed baselines via
``git show <ref>:<file>``, flattens every numeric leaf to a dotted key,
and reports relative changes that move in the WRONG direction past a
threshold. Direction is inferred from the key name:

  higher-better: *per_sec, *per_sec*, throughput, speedup, improvement,
                 txs_per_app_call, blocks_per_s, sigs_per_sec, ...
  lower-better:  *ms, *latency*, p50/p99, seconds, elapsed, overhead,
                 degradation, *wait*, relative_error, sink_bytes
  neutral:       everything else (counts, heights, config echoes) —
                 reported in the diff but never a regression

This is an ADVISORY guardrail, not a CI gate: bench numbers on a
shared/1-core host swing with scheduler interleaving, so tier-1 invokes
it with --advisory (always exit 0) and humans read the table. Without
--advisory it exits 1 on regressions, for use on quiet dedicated boxes.

    python tools/bench_compare.py [--files F...] [--ref HEAD]
        [--threshold 0.10] [--advisory] [--json]

Missing baselines (file not in the ref, not a git checkout, git absent)
are skipped gracefully — a fresh artifact is not a regression.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DEFAULT_FILES = ("WORKLOADS.json",)

_HIGHER = ("per_sec", "per_s", "throughput", "speedup", "improvement",
           "per_app_call", "per_core", "headers_per", "txs_per",
           "sigs_per", "blocks_per", "bytes_ratio")
_LOWER = ("_ms", "ms.", "latency", "p50", "p99", "seconds", "elapsed",
          "overhead", "degradation", "wait", "relative_error",
          "sink_bytes", "duration")


def direction(key: str) -> str:
    k = key.lower()
    # lower-better wins ties like "commit_latency_ms.p99" vs a stray
    # "per" substring; latency keys are the ones regressions hide in
    if any(t in k for t in _LOWER):
        return "lower"
    if any(t in k for t in _HIGHER):
        return "higher"
    return "neutral"


def _load(text: str):
    """Whole-file JSON, else JSONL keyed by each record's `metric`."""
    try:
        return json.loads(text)
    except json.JSONDecodeError:
        out = {}
        for ln in text.splitlines():
            ln = ln.strip()
            if not ln:
                continue
            try:
                rec = json.loads(ln)
            except json.JSONDecodeError:
                continue
            if isinstance(rec, dict):
                out[str(rec.get("metric", len(out)))] = rec
        return out


def _flatten(obj, prefix: str = "", out: dict | None = None) -> dict:
    if out is None:
        out = {}
    if isinstance(obj, dict):
        for k, v in obj.items():
            _flatten(v, f"{prefix}{k}.", out)
    elif isinstance(obj, bool):
        pass  # bools are flags, not measurements
    elif isinstance(obj, (int, float)):
        out[prefix.rstrip(".")] = float(obj)
    return out


def _git_show(ref: str, relpath: str) -> str | None:
    try:
        p = subprocess.run(
            ["git", "show", f"{ref}:{relpath}"],
            capture_output=True, text=True, timeout=30, cwd=REPO,
        )
    except (OSError, subprocess.TimeoutExpired):
        return None
    return p.stdout if p.returncode == 0 else None


def diff_flat(base: dict, cur: dict, threshold: float) -> dict:
    """Directional diff of two flattened numeric-leaf dicts."""
    regressions, improvements, changed = [], [], 0
    for key in sorted(set(cur) & set(base)):
        b, c = base[key], cur[key]
        if b == c:
            continue
        changed += 1
        d = direction(key)
        if d == "neutral" or b == 0:
            continue
        rel = (c - b) / abs(b)
        worse = rel < -threshold if d == "higher" else rel > threshold
        better = rel > threshold if d == "higher" else rel < -threshold
        row = {"key": key, "direction": d, "baseline": b, "current": c,
               "change_pct": round(rel * 100, 1)}
        if worse:
            regressions.append(row)
        elif better:
            improvements.append(row)
    return {
        "compared": len(set(cur) & set(base)),
        "changed": changed, "only_current": len(set(cur) - set(base)),
        "regressions": regressions, "improvements": improvements,
    }


def compare_file(relpath: str, ref: str, threshold: float) -> dict:
    cur_path = os.path.join(REPO, relpath)
    if not os.path.exists(cur_path):
        return {"file": relpath, "skipped": "no working-tree copy"}
    base_text = _git_show(ref, relpath)
    if base_text is None:
        return {"file": relpath,
                "skipped": f"no baseline at {ref} (or git unavailable)"}
    with open(cur_path) as f:
        cur = _flatten(_load(f.read()))
    base = _flatten(_load(base_text))
    return {"file": relpath, **diff_flat(base, cur, threshold)}


def _ingest_record(flat_src: str):
    """The ingest_sustained_load record (dict) from a WORKLOADS.json
    body, or None."""
    data = _load(flat_src)
    if isinstance(data, dict):
        rec = data.get("ingest_sustained_load")
        if isinstance(rec, dict):
            return rec
    return None


def compare_ingest(ref: str, threshold: float,
                   relpath: str = "WORKLOADS.json") -> dict:
    """Stage-by-stage diff of the sustained-ingest waterfall (ISSUE 11).

    proposal_wait and commit-latency p99 are the first-class numbers —
    the pipelined-proposer work exists to move exactly these — followed
    by every waterfall stage's p50/p99. All stage keys are lower-better;
    the direction machinery still runs so a renamed key can never
    silently flip polarity."""
    cur_path = os.path.join(REPO, relpath)
    if not os.path.exists(cur_path):
        return {"file": relpath, "skipped": "no working-tree copy"}
    base_text = _git_show(ref, relpath)
    if base_text is None:
        return {"file": relpath,
                "skipped": f"no baseline at {ref} (or git unavailable)"}
    with open(cur_path) as f:
        cur = _ingest_record(f.read())
    base = _ingest_record(base_text)
    if cur is None or base is None:
        return {"file": relpath,
                "skipped": "no ingest_sustained_load record on one side"}

    def stage_rows():
        rows = []
        b_stages = (base.get("stage_waterfall") or {}).get("stages") or {}
        c_stages = (cur.get("stage_waterfall") or {}).get("stages") or {}
        for name in c_stages:
            if name not in b_stages:
                continue
            for q in ("p50_ms", "p99_ms"):
                b = b_stages[name].get(q)
                c = c_stages[name].get(q)
                if not isinstance(b, (int, float)) or b == 0 \
                        or not isinstance(c, (int, float)):
                    continue
                rel = (c - b) / abs(b)
                rows.append({
                    "stage": name, "quantile": q, "baseline": b,
                    "current": c, "change_pct": round(rel * 100, 1),
                    "direction": direction(q),
                    "worse": rel > threshold,
                    "better": rel < -threshold,
                })
        return rows

    def headline(path: tuple, label: str):
        b, c = base, cur
        for p in path:
            b = (b or {}).get(p) if isinstance(b, dict) else None
            c = (c or {}).get(p) if isinstance(c, dict) else None
        if not isinstance(b, (int, float)) or not isinstance(c, (int, float)):
            return None
        rel = (c - b) / abs(b) if b else 0.0
        return {"key": label, "baseline": b, "current": c,
                "change_pct": round(rel * 100, 1),
                "worse": b != 0 and rel > threshold,
                "better": b != 0 and rel < -threshold}

    headlines = [h for h in (
        headline(("stage_waterfall", "stages", "proposal_wait", "p99_ms"),
                 "proposal_wait_p99_ms"),
        headline(("commit_latency_ms", "p99"), "commit_p99_ms"),
        headline(("commit_latency_ms", "p50"), "commit_p50_ms"),
        headline(("txs_per_sec",), "txs_per_sec"),
    ) if h is not None]
    # throughput is higher-better: flip the verdict computed above
    for h in headlines:
        if h["key"] == "txs_per_sec":
            h["worse"], h["better"] = h["better"], h["worse"]
    stages = stage_rows()
    return {
        "file": relpath, "mode": "ingest_waterfall",
        "dominant_stage_p99": {
            "baseline": (base.get("stage_waterfall") or {}).get(
                "dominant_stage_p99"),
            "current": (cur.get("stage_waterfall") or {}).get(
                "dominant_stage_p99"),
        },
        "headlines": headlines,
        "stages": stages,
        "regressions": [r for r in headlines + stages if r.get("worse")],
        "improvements": [r for r in headlines + stages if r.get("better")],
    }


def _bls_record(flat_src: str):
    """The megacommit_bls_* record (dict) from a WORKLOADS.json body, or
    None. Matched by prefix so a size change (500v quick vs 10000v full)
    still finds the record."""
    data = _load(flat_src)
    if isinstance(data, dict):
        for key, rec in data.items():
            if key.startswith("megacommit_bls_") and isinstance(rec, dict):
                return rec
    return None


def compare_bls(ref: str, threshold: float,
                relpath: str = "WORKLOADS.json") -> dict:
    """Point-by-point diff of the ed25519-vs-BLS crossover table
    (ISSUE 13). Latency keys (*_ms) are lower-better, byte ratios and
    speedups higher-better — the shared direction machinery decides, so
    a renamed key can never silently flip polarity. The crossover point
    itself is first-class: it moving UP (BLS winning later) is the
    regression the aggregate track exists to prevent."""
    cur_path = os.path.join(REPO, relpath)
    if not os.path.exists(cur_path):
        return {"file": relpath, "skipped": "no working-tree copy"}
    base_text = _git_show(ref, relpath)
    if base_text is None:
        return {"file": relpath,
                "skipped": f"no baseline at {ref} (or git unavailable)"}
    with open(cur_path) as f:
        cur = _bls_record(f.read())
    base = _bls_record(base_text)
    if cur is None or base is None:
        return {"file": relpath,
                "skipped": "no megacommit_bls record on one side"}

    rows = []
    b_pts = base.get("points") or {}
    c_pts = cur.get("points") or {}
    for n in sorted(c_pts, key=int):
        if n not in b_pts:
            continue
        for key in c_pts[n]:
            b, c = b_pts[n].get(key), c_pts[n].get(key)
            if not isinstance(b, (int, float)) or b == 0 \
                    or not isinstance(c, (int, float)) \
                    or isinstance(b, bool) or isinstance(c, bool):
                continue
            d = direction(key)
            if d == "neutral":
                continue
            rel = (c - b) / abs(b)
            rows.append({
                "point": f"{n}v", "key": key, "baseline": b, "current": c,
                "change_pct": round(rel * 100, 1), "direction": d,
                "worse": (rel > threshold if d == "lower"
                          else rel < -threshold),
                "better": (rel < -threshold if d == "lower"
                           else rel > threshold),
            })
    b_x, c_x = base.get("crossover_validators"), cur.get("crossover_validators")
    crossover = {"baseline": b_x, "current": c_x,
                 # None = never crossed: treat as +inf so gaining a
                 # crossover is an improvement, losing one a regression
                 "worse": (b_x is not None
                           and (c_x is None or c_x > b_x)),
                 "better": (c_x is not None
                            and (b_x is None or c_x < b_x))}
    regs = [r for r in rows if r["worse"]]
    if crossover["worse"]:
        regs.append({"key": "crossover_validators", **crossover})
    return {
        "file": relpath, "mode": "bls_crossover",
        "crossover": crossover,
        "rows": rows,
        "regressions": regs,
        "improvements": [r for r in rows if r["better"]],
    }


def _das_record(flat_src: str):
    """The das_sampling_* record from a WORKLOADS.json body, or None."""
    data = _load(flat_src)
    if isinstance(data, dict):
        for key, rec in data.items():
            if key.startswith("das_sampling_") and isinstance(rec, dict):
                return rec
    return None


# polarity the suffix heuristics would get wrong (or miss): per-sample
# wire bytes LOOK like a "per_s" throughput key but are a cost, and the
# MB/s codec rates carry no recognized suffix at all
_DAS_DIRECTIONS = {
    "honest.proof_bytes_per_sample": "lower",
    "codec.native_mb_s": "higher",
    "codec.oracle_mb_s": "higher",
}
# noisy / non-measurement leaves: per-leg snapshots, run geometry,
# counters that scale with wall time rather than efficiency
_DAS_SKIP = ("honest_legs.", "withholding.", "gate.", "http_", "heights_",
             "blocks_encoded", "samples_served", "withheld_hits",
             "duration_s", "data_shards", "parity_shards",
             "honest.clients", "honest.samples_total",
             "honest.proof_bytes_bound", "honest.clients_confident",
             "codec.payload_bytes", "codec.rs_threads")


def compare_das(ref: str, threshold: float,
                relpath: str = "WORKLOADS.json") -> dict:
    """Diff of the data-availability sampling workload (ISSUE 14):
    fleet verify throughput, per-sample wire cost, and the native codec
    rates go through the directional machinery (with explicit polarity
    for the keys the suffix heuristics would misread); the withholding
    detection fraction is first-class — it dropping is the regression
    the adversarial leg exists to catch."""
    cur_path = os.path.join(REPO, relpath)
    if not os.path.exists(cur_path):
        return {"file": relpath, "skipped": "no working-tree copy"}
    base_text = _git_show(ref, relpath)
    if base_text is None:
        return {"file": relpath,
                "skipped": f"no baseline at {ref} (or git unavailable)"}
    with open(cur_path) as f:
        cur = _das_record(f.read())
    base = _das_record(base_text)
    if cur is None or base is None:
        return {"file": relpath,
                "skipped": "no das_sampling record on one side"}

    b_flat, c_flat = _flatten(base), _flatten(cur)
    rows = []
    for key in sorted(c_flat):
        if key not in b_flat or b_flat[key] == 0:
            continue
        if any(key.startswith(p) or p in key for p in _DAS_SKIP):
            continue
        d = _DAS_DIRECTIONS.get(key) or direction(key)
        if d == "neutral":
            continue
        b, c = b_flat[key], c_flat[key]
        rel = (c - b) / abs(b)
        rows.append({
            "key": key, "baseline": b, "current": c,
            "change_pct": round(rel * 100, 1), "direction": d,
            "worse": (rel > threshold if d == "lower"
                      else rel < -threshold),
            "better": (rel < -threshold if d == "lower"
                       else rel > threshold),
        })

    def frac(rec):
        adv = rec.get("withholding") or {}
        n = adv.get("clients") or 0
        return (adv.get("clients_detected_withholding", 0) / n) if n else None

    b_f, c_f = frac(base), frac(cur)
    detect = {"baseline": b_f, "current": c_f,
              "worse": (b_f is not None and c_f is not None
                        and c_f < b_f - 0.02),
              "better": (b_f is not None and c_f is not None
                         and c_f > b_f + 0.02)}
    regs = [r for r in rows if r["worse"]]
    if detect["worse"]:
        regs.append({"key": "withholding_detect_frac", **detect})
    return {
        "file": relpath, "mode": "das_sampling",
        "withholding_detect": detect,
        "rows": rows,
        "regressions": regs,
        "improvements": [r for r in rows if r["better"]],
    }


def _pc_record(flat_src: str):
    """The das_pc_* record from a WORKLOADS.json body, or None."""
    data = _load(flat_src)
    if isinstance(data, dict):
        for key, rec in data.items():
            if key.startswith("das_pc_") and isinstance(rec, dict):
                return rec
    return None


# polarity the suffix heuristics would misread: per-sample wire bytes
# and opening latencies are costs, openings-per-second and the native
# speedup factor are wins
_PC_DIRECTIONS = {
    "honest.bytes_per_sample": "lower",
    "openings.native_open_ms": "lower",
    "openings.oracle_open_ms": "lower",
    "openings.verify_ms": "lower",
    "openings.native_openings_per_s": "higher",
    "openings.oracle_openings_per_s": "higher",
    "openings.native_speedup": "higher",
    "oneD_blind_confident_fraction": "higher",
}
# noisy / non-measurement leaves: per-leg snapshots, run geometry,
# wall-time-scaled counters
_PC_SKIP = ("honest_legs.", "withholding.", "lying_encoder.", "gate.",
            "http_", "heights_", "blocks_encoded", "pc_samples_served",
            "pc_skipped_rows", "duration_s", "pc_data_cols",
            "pc_parity_cols", "grid_rows", "honest.clients",
            "honest.samples_total", "honest.clients_confident",
            "rs_proof_bytes_bound", "openings.quotient_degree",
            "openings.cols_per_opening", "openings.msm_threads")


def compare_pc(ref: str, threshold: float,
               relpath: str = "WORKLOADS.json") -> dict:
    """Diff of the polynomial-commitment DAS workload (ISSUE 19):
    multiproof wire cost, fleet throughput, and the native-vs-oracle
    MSM opening rates go through the directional machinery (with
    explicit polarity for the keys the suffix heuristics would
    misread); the lying-encoder parity-fail fraction is first-class —
    detection is deterministic, so anything below 1.0 is the
    regression the adversarial leg exists to catch."""
    cur_path = os.path.join(REPO, relpath)
    if not os.path.exists(cur_path):
        return {"file": relpath, "skipped": "no working-tree copy"}
    base_text = _git_show(ref, relpath)
    if base_text is None:
        return {"file": relpath,
                "skipped": f"no baseline at {ref} (or git unavailable)"}
    with open(cur_path) as f:
        cur = _pc_record(f.read())
    base = _pc_record(base_text)
    if cur is None or base is None:
        return {"file": relpath,
                "skipped": "no das_pc record on one side"}

    b_flat, c_flat = _flatten(base), _flatten(cur)
    rows = []
    for key in sorted(c_flat):
        if key not in b_flat or b_flat[key] == 0:
            continue
        if any(key.startswith(p) or p in key for p in _PC_SKIP):
            continue
        d = _PC_DIRECTIONS.get(key) or direction(key)
        if d == "neutral":
            continue
        b, c = b_flat[key], c_flat[key]
        if not isinstance(b, (int, float)) or isinstance(b, bool):
            continue
        rel = (c - b) / abs(b)
        rows.append({
            "key": key, "baseline": b, "current": c,
            "change_pct": round(rel * 100, 1), "direction": d,
            "worse": (rel > threshold if d == "lower"
                      else rel < -threshold),
            "better": (rel < -threshold if d == "lower"
                       else rel > threshold),
        })

    def frac(rec):
        lie = rec.get("lying_encoder") or {}
        n = lie.get("clients") or 0
        return (lie.get("clients_parity_fail", 0) / n) if n else None

    b_f, c_f = frac(base), frac(cur)
    detect = {"baseline": b_f, "current": c_f,
              "worse": (b_f is not None and c_f is not None
                        and c_f < b_f),
              "better": False}
    regs = [r for r in rows if r["worse"]]
    if detect["worse"]:
        regs.append({"key": "lying_encoder_parity_fail_frac", **detect})
    return {
        "file": relpath, "mode": "das_pc",
        "lying_encoder_detect": detect,
        "rows": rows,
        "regressions": regs,
        "improvements": [r for r in rows if r["better"]],
    }


def _city_record(flat_src: str):
    """The city_combined record from a WORKLOADS.json body, or None."""
    data = _load(flat_src)
    if isinstance(data, dict):
        rec = data.get("city_combined")
        if isinstance(rec, dict):
            return rec
    return None


# polarity the suffix heuristics would misread or miss: the coalesce
# factor and the normalized dispatch-call rates are the headline of the
# shared-scheduler work, and "dispatch_calls_per_1k_sigs" LOOKS like a
# "sigs_per" throughput key but is a cost
_CITY_DIRECTIONS = {
    "coalescing.coalesce_factor": "higher",
    "coalescing.dispatch_calls_per_1k_sigs_sequential": "lower",
    "coalescing.dispatch_calls_per_1k_sigs_coalesced": "lower",
    "das.withholding_detect_frac": "higher",
}
# non-measurement leaves: run geometry, raw counters that scale with
# wall time, and 1-core wall-clock samples too noisy to diff
_CITY_SKIP = ("gate.", "duration_s", "combined_wall_s", "clients",
              "max_verify_calls", "joiner.blocks", "joiner.validators",
              "joiner.seconds", "joiner.sigs_verified", "joiner.sched_",
              "coalescing.tenants", "coalescing.requests",
              "coalescing.sigs", "coalescing.sequential_dispatches",
              "coalescing.coalesced_dispatches", "wall_ms",
              "passthrough_")


def compare_city(ref: str, threshold: float,
                 relpath: str = "WORKLOADS.json") -> dict:
    """Diff of the city-scale combined workload (ISSUE 15): the four
    concurrent legs' SLO numbers plus the shared-scheduler coalescing
    measurement. The coalesce factor is first-class — it dropping is
    the regression the one-scheduler-N-tenants work exists to prevent;
    the dispatch-call rates carry explicit polarity because the suffix
    heuristics would read them as throughput."""
    cur_path = os.path.join(REPO, relpath)
    if not os.path.exists(cur_path):
        return {"file": relpath, "skipped": "no working-tree copy"}
    base_text = _git_show(ref, relpath)
    if base_text is None:
        return {"file": relpath,
                "skipped": f"no baseline at {ref} (or git unavailable)"}
    with open(cur_path) as f:
        cur = _city_record(f.read())
    base = _city_record(base_text)
    if cur is None or base is None:
        return {"file": relpath,
                "skipped": "no city_combined record on one side"}

    b_flat, c_flat = _flatten(base), _flatten(cur)
    rows = []
    for key in sorted(c_flat):
        if key not in b_flat or b_flat[key] == 0:
            continue
        if any(key.startswith(p) or p in key for p in _CITY_SKIP):
            continue
        d = _CITY_DIRECTIONS.get(key) or direction(key)
        if d == "neutral":
            continue
        b, c = b_flat[key], c_flat[key]
        rel = (c - b) / abs(b)
        rows.append({
            "key": key, "baseline": b, "current": c,
            "change_pct": round(rel * 100, 1), "direction": d,
            "worse": (rel > threshold if d == "lower"
                      else rel < -threshold),
            "better": (rel < -threshold if d == "lower"
                       else rel > threshold),
        })

    b_x = (base.get("coalescing") or {}).get("coalesce_factor")
    c_x = (cur.get("coalescing") or {}).get("coalesce_factor")
    factor = {"baseline": b_x, "current": c_x,
              "worse": (b_x is not None and c_x is not None
                        and c_x < b_x * (1 - threshold)),
              "better": (b_x is not None and c_x is not None
                         and c_x > b_x * (1 + threshold))}
    regs = [r for r in rows if r["worse"]]
    if factor["worse"]:
        regs.append({"key": "coalesce_factor", **factor})
    return {
        "file": relpath, "mode": "city_combined",
        "coalesce_factor": factor,
        "rows": rows,
        "regressions": regs,
        "improvements": [r for r in rows if r["better"]],
    }


def _replicated_record(flat_src: str):
    """The city_replicated record from a WORKLOADS.json body, or None."""
    data = _load(flat_src)
    if isinstance(data, dict):
        rec = data.get("city_replicated")
        if isinstance(rec, dict):
            return rec
    return None


# bootstrap readiness has no recognized lower-better suffix; everything
# else the heuristics get right (deliveries/samples per_sec higher,
# proof p99 lower)
_REPL_DIRECTIONS = {
    "bootstrap.ready_s": "lower",
}
# non-measurement leaves: run geometry, wall-scaled counters, and the
# correctness invariants handled first-class below (gaps/dups/mismatches
# must stay 0 — a ratio diff over a 0 baseline is meaningless)
_REPL_SKIP = ("gate.", "duration_s", "combined_wall_s", "clients",
              "blocks", "replicas", "stream_groups", "stream_lines",
              "heights_sampled", "samples_total", "clients_confident",
              "failovers", "diff_checks", "spawned_at_height",
              "snapshot_height", "applied_height", "forwarding.",
              "gaps", "dups", "diff_mismatches")


def compare_replicated(ref: str, threshold: float,
                       relpath: str = "WORKLOADS.json") -> dict:
    """Diff of the scale-out serving-plane workload (ISSUE 16): fleet
    delivery/sampling throughput, proof latency, and bootstrap wall time
    go through the directional machinery; the zero-gap/zero-mismatch
    invariants are first-class — ANY nonzero current value is a
    regression regardless of baseline, because the replication cursor
    and byte-identity contracts admit no tolerance."""
    cur_path = os.path.join(REPO, relpath)
    if not os.path.exists(cur_path):
        return {"file": relpath, "skipped": "no working-tree copy"}
    base_text = _git_show(ref, relpath)
    if base_text is None:
        return {"file": relpath,
                "skipped": f"no baseline at {ref} (or git unavailable)"}
    with open(cur_path) as f:
        cur = _replicated_record(f.read())
    base = _replicated_record(base_text)
    if cur is None or base is None:
        return {"file": relpath,
                "skipped": "no city_replicated record on one side"}

    b_flat, c_flat = _flatten(base), _flatten(cur)
    rows = []
    for key in sorted(c_flat):
        if key not in b_flat or b_flat[key] == 0:
            continue
        if any(key.startswith(p) or p in key for p in _REPL_SKIP):
            continue
        d = _REPL_DIRECTIONS.get(key) or direction(key)
        if d == "neutral":
            continue
        b, c = b_flat[key], c_flat[key]
        rel = (c - b) / abs(b)
        rows.append({
            "key": key, "baseline": b, "current": c,
            "change_pct": round(rel * 100, 1), "direction": d,
            "worse": (rel > threshold if d == "lower"
                      else rel < -threshold),
            "better": (rel < -threshold if d == "lower"
                       else rel > threshold),
        })

    def invariant(key):
        return {"key": key, "baseline": b_flat.get(key, 0.0),
                "current": c_flat.get(key, 0.0),
                "worse": c_flat.get(key, 0.0) > 0}

    invariants = [invariant(k) for k in (
        "light.gaps", "light.dups", "light.diff_mismatches",
        "das.stream_gaps", "failover.delivery_gaps")]
    regs = [r for r in rows if r["worse"]]
    regs += [i for i in invariants if i["worse"]]
    return {
        "file": relpath, "mode": "city_replicated",
        "invariants": invariants,
        "rows": rows,
        "regressions": regs,
        "improvements": [r for r in rows if r["better"]],
    }


def _certnative_record(flat_src: str):
    """The certnative record from a WORKLOADS.json body, or None."""
    data = _load(flat_src)
    if isinstance(data, dict):
        rec = data.get("certnative")
        if isinstance(rec, dict):
            return rec
    return None


# the cert-side byte footprints have no recognized lower-better suffix
# ("bytes" alone is polarity-free: sink_bytes is cost, bytes_ratio is
# win), and the feed saving percentage is higher-better; the ratios and
# sigs_per_sec/speedup keys the heuristics already read correctly
_CERT_DIRECTIONS = {
    "wire.cert_commit_bytes": "lower",
    "store.cert_bytes_per_block": "lower",
    "feed.cert_frame_bytes": "lower",
    "feed.saving_pct": "higher",
}
# non-measurement leaves: run geometry, gate metadata, the column-side
# constants (baseline-format properties, not this feature's output),
# 1-core wall-clock samples, and the invariants handled first-class
_CERT_SKIP = ("gate.", "verdicts.", "validators", "blocks",
              "replay.pairing_checks", "replay.column_s", "replay.cert_s")


def compare_certnative(ref: str, threshold: float,
                       relpath: str = "WORKLOADS.json") -> dict:
    """Diff of the certificate-native workload (ISSUE 17): wire/store/
    feed byte footprints and the one-pairing replay throughput go
    through the directional machinery; two invariants are first-class
    and zero-tolerance — the cert-vs-column verdict differential must
    show ZERO mismatches (a certificate accepting what the signature
    column rejects is a soundness hole, not a perf regression), and
    replay must stay at one pairing per block."""
    cur_path = os.path.join(REPO, relpath)
    if not os.path.exists(cur_path):
        return {"file": relpath, "skipped": "no working-tree copy"}
    base_text = _git_show(ref, relpath)
    if base_text is None:
        return {"file": relpath,
                "skipped": f"no baseline at {ref} (or git unavailable)"}
    with open(cur_path) as f:
        cur = _certnative_record(f.read())
    base = _certnative_record(base_text)
    if cur is None or base is None:
        return {"file": relpath,
                "skipped": "no certnative record on one side"}

    b_flat, c_flat = _flatten(base), _flatten(cur)
    rows = []
    for key in sorted(c_flat):
        if key not in b_flat or b_flat[key] == 0:
            continue
        if any(key.startswith(p) or p in key for p in _CERT_SKIP):
            continue
        d = _CERT_DIRECTIONS.get(key) or direction(key)
        if d == "neutral":
            continue
        b, c = b_flat[key], c_flat[key]
        rel = (c - b) / abs(b)
        rows.append({
            "key": key, "baseline": b, "current": c,
            "change_pct": round(rel * 100, 1), "direction": d,
            "worse": (rel > threshold if d == "lower"
                      else rel < -threshold),
            "better": (rel < -threshold if d == "lower"
                       else rel > threshold),
        })

    mism = {"key": "verdicts.mismatches",
            "baseline": b_flat.get("verdicts.mismatches", 0.0),
            "current": c_flat.get("verdicts.mismatches", 0.0),
            "worse": c_flat.get("verdicts.mismatches", 0.0) > 0}
    pair = {"key": "replay.pairings_per_block",
            "baseline": (b_flat.get("replay.pairing_checks", 0.0)
                         / max(b_flat.get("blocks", 1.0), 1.0)),
            "current": (c_flat.get("replay.pairing_checks", 0.0)
                        / max(c_flat.get("blocks", 1.0), 1.0)),
            "worse": (c_flat.get("replay.pairing_checks", 0.0)
                      > c_flat.get("blocks", 0.0))}
    invariants = [mism, pair]
    regs = [r for r in rows if r["worse"]]
    regs += [i for i in invariants if i["worse"]]
    return {
        "file": relpath, "mode": "certnative",
        "invariants": invariants,
        "rows": rows,
        "regressions": regs,
        "improvements": [r for r in rows if r["better"]],
    }


def _workloads_record(flat_src: str, metric: str):
    """A named record from a WORKLOADS.json body, or None."""
    data = _load(flat_src)
    if isinstance(data, dict):
        rec = data.get(metric)
        if isinstance(rec, dict):
            return rec
    return None


# run geometry and the legs handled first-class (or non-numeric)
_WT_SKIP = ("gate.", "detection.", "false_positives", "p99_budget_ms",
            "nodes", "blocks", "validators")


def compare_watchtower(ref: str, threshold: float,
                       relpath: str = "WORKLOADS.json") -> dict:
    """Diff of the watchtower audit workload (ISSUE 18): the audit
    frame rate and latency distribution go through the directional
    machinery; two invariants are first-class and independent of the
    baseline — the clean-feed FALSE-POSITIVE count must be zero (a
    baseline that also cried wolf would excuse nothing), and the
    audit-latency p99 must stay inside the record's own absolute
    budget (the auditor must remain cheap enough to run inline with a
    live feed on this machine)."""
    cur_path = os.path.join(REPO, relpath)
    if not os.path.exists(cur_path):
        return {"file": relpath, "skipped": "no working-tree copy"}
    with open(cur_path) as f:
        cur = _workloads_record(f.read(), "watchtower")
    if cur is None:
        return {"file": relpath, "skipped": "no watchtower record"}
    base_text = _git_show(ref, relpath)
    base = (_workloads_record(base_text, "watchtower")
            if base_text is not None else None)

    c_flat = _flatten(cur)
    b_flat = _flatten(base) if base is not None else {}
    rows = []
    for key in sorted(c_flat):
        if key not in b_flat or b_flat[key] == 0:
            continue
        if any(key.startswith(p) or p == key for p in _WT_SKIP):
            continue
        d = direction(key)
        if d == "neutral":
            continue
        b, c = b_flat[key], c_flat[key]
        rel = (c - b) / abs(b)
        rows.append({
            "key": key, "baseline": b, "current": c,
            "change_pct": round(rel * 100, 1), "direction": d,
            "worse": (rel > threshold if d == "lower"
                      else rel < -threshold),
            "better": (rel < -threshold if d == "lower"
                       else rel > threshold),
        })

    fp = {"key": "false_positives",
          "baseline": b_flat.get("false_positives"),
          "current": c_flat.get("false_positives", 0.0),
          "worse": c_flat.get("false_positives", 0.0) > 0}
    p99 = {"key": "audit_latency_p99_vs_budget_ms",
           "baseline": b_flat.get("audit_latency_ms.p99"),
           "current": c_flat.get("audit_latency_ms.p99", 0.0),
           "budget": c_flat.get("p99_budget_ms", 0.0),
           "worse": (c_flat.get("audit_latency_ms.p99", 0.0)
                     > c_flat.get("p99_budget_ms", float("inf")))}
    invariants = [fp, p99]
    regs = [r for r in rows if r["worse"]]
    regs += [i for i in invariants if i["worse"]]
    return {
        "file": relpath, "mode": "watchtower",
        "invariants": invariants,
        "rows": rows,
        "regressions": regs,
        "improvements": [r for r in rows if r["better"]],
    }


def _print_watchtower(rep: dict) -> None:
    if "skipped" in rep:
        print(f"watchtower: skipped ({rep['skipped']})")
        return
    broken = [i["key"] for i in rep["invariants"] if i["worse"]]
    tag = "REGRESSION" if broken else "          "
    print(f"watchtower ({rep['file']}): {tag} zero-false-positive/"
          f"p99-budget invariants "
          f"{'BROKEN: ' + ', '.join(broken) if broken else 'held'}")
    for r in rep["rows"]:
        tag = ("REGRESSION" if r["worse"]
               else "improved  " if r["better"] else "          ")
        print("  %s %-32s %12g -> %-12g (%+.1f%%, %s-better)"
              % (tag, r["key"], r["baseline"], r["current"],
                 r["change_pct"], r["direction"]))


def _print_certnative(rep: dict) -> None:
    if "skipped" in rep:
        print(f"certnative: skipped ({rep['skipped']})")
        return
    broken = [i["key"] for i in rep["invariants"] if i["worse"]]
    tag = "REGRESSION" if broken else "          "
    print(f"certnative ({rep['file']}): {tag} verdict-pin/one-pairing "
          f"invariants {'BROKEN: ' + ', '.join(broken) if broken else 'held'}")
    for r in rep["rows"]:
        tag = ("REGRESSION" if r["worse"]
               else "improved  " if r["better"] else "          ")
        print("  %s %-32s %12g -> %-12g (%+.1f%%, %s-better)"
              % (tag, r["key"], r["baseline"], r["current"],
                 r["change_pct"], r["direction"]))


def _print_replicated(rep: dict) -> None:
    if "skipped" in rep:
        print(f"city replicated: skipped ({rep['skipped']})")
        return
    broken = [i["key"] for i in rep["invariants"] if i["worse"]]
    tag = "REGRESSION" if broken else "          "
    print(f"city replicated ({rep['file']}): {tag} zero-gap/byte-identity "
          f"invariants {'BROKEN: ' + ', '.join(broken) if broken else 'held'}")
    for r in rep["rows"]:
        tag = ("REGRESSION" if r["worse"]
               else "improved  " if r["better"] else "          ")
        print("  %s %-32s %12g -> %-12g (%+.1f%%, %s-better)"
              % (tag, r["key"], r["baseline"], r["current"],
                 r["change_pct"], r["direction"]))


def _print_city(rep: dict) -> None:
    if "skipped" in rep:
        print(f"city combined: skipped ({rep['skipped']})")
        return
    x = rep["coalesce_factor"]
    tag = ("REGRESSION" if x["worse"]
           else "improved  " if x["better"] else "          ")
    print(f"city combined ({rep['file']}): {tag} coalesce factor "
          f"{x['baseline']} -> {x['current']}")
    for r in rep["rows"]:
        tag = ("REGRESSION" if r["worse"]
               else "improved  " if r["better"] else "          ")
        print("  %s %-44s %12g -> %-12g (%+.1f%%, %s-better)"
              % (tag, r["key"], r["baseline"], r["current"],
                 r["change_pct"], r["direction"]))


def _print_das(rep: dict) -> None:
    if "skipped" in rep:
        print(f"das sampling: skipped ({rep['skipped']})")
        return
    d = rep["withholding_detect"]
    tag = ("REGRESSION" if d["worse"]
           else "improved  " if d["better"] else "          ")
    b = f"{d['baseline']:.1%}" if d["baseline"] is not None else "n/a"
    c = f"{d['current']:.1%}" if d["current"] is not None else "n/a"
    print(f"das sampling ({rep['file']}): {tag} withholding detected by "
          f"{b} -> {c} of the fleet")
    for r in rep["rows"]:
        tag = ("REGRESSION" if r["worse"]
               else "improved  " if r["better"] else "          ")
        print("  %s %-32s %12g -> %-12g (%+.1f%%, %s-better)"
              % (tag, r["key"], r["baseline"], r["current"],
                 r["change_pct"], r["direction"]))


def _print_pc(rep: dict) -> None:
    if "skipped" in rep:
        print(f"das pc: skipped ({rep['skipped']})")
        return
    d = rep["lying_encoder_detect"]
    tag = "REGRESSION" if d["worse"] else "          "
    b = f"{d['baseline']:.1%}" if d["baseline"] is not None else "n/a"
    c = f"{d['current']:.1%}" if d["current"] is not None else "n/a"
    print(f"das pc ({rep['file']}): {tag} lying encoder caught for "
          f"{b} -> {c} of the fleet")
    for r in rep["rows"]:
        tag = ("REGRESSION" if r["worse"]
               else "improved  " if r["better"] else "          ")
        print("  %s %-32s %12g -> %-12g (%+.1f%%, %s-better)"
              % (tag, r["key"], r["baseline"], r["current"],
                 r["change_pct"], r["direction"]))


def _print_bls(rep: dict) -> None:
    if "skipped" in rep:
        print(f"bls crossover: skipped ({rep['skipped']})")
        return
    x = rep["crossover"]
    tag = ("REGRESSION" if x["worse"]
           else "improved  " if x["better"] else "          ")
    print(f"bls crossover ({rep['file']}): {tag} cert beats ed25519 from "
          f"{x['baseline']} -> {x['current']} validators")
    for r in rep["rows"]:
        tag = ("REGRESSION" if r["worse"]
               else "improved  " if r["better"] else "          ")
        print("  %s %-7s %-24s %10g -> %-10g (%+.1f%%, %s-better)"
              % (tag, r["point"], r["key"], r["baseline"], r["current"],
                 r["change_pct"], r["direction"]))


def _print_ingest(rep: dict) -> None:
    if "skipped" in rep:
        print(f"ingest waterfall: skipped ({rep['skipped']})")
        return
    dom = rep["dominant_stage_p99"]
    print(f"ingest waterfall ({rep['file']}): dominant p99 stage "
          f"{dom['baseline']} -> {dom['current']}")
    for h in rep["headlines"]:
        tag = ("REGRESSION" if h["worse"]
               else "improved  " if h["better"] else "          ")
        print("  %s %-24s %10g -> %-10g (%+.1f%%)"
              % (tag, h["key"], h["baseline"], h["current"],
                 h["change_pct"]))
    for r in rep["stages"]:
        tag = ("REGRESSION" if r["worse"]
               else "improved  " if r["better"] else "          ")
        print("  %s %-13s %-7s %10g -> %-10g (%+.1f%%)"
              % (tag, r["stage"], r["quantile"], r["baseline"],
                 r["current"], r["change_pct"]))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="diff fresh bench/workload JSON against the last "
                    "committed round")
    ap.add_argument("--files", nargs="+", default=list(DEFAULT_FILES))
    ap.add_argument("--ingest", action="store_true",
                    help="also diff the sustained-ingest stage waterfall "
                         "stage-by-stage (proposal_wait / commit p99 "
                         "first-class)")
    ap.add_argument("--bls", action="store_true",
                    help="also diff the ed25519-vs-BLS crossover table "
                         "point-by-point (the crossover validator count "
                         "first-class)")
    ap.add_argument("--das", action="store_true",
                    help="also diff the data-availability sampling "
                         "workload (withholding detection fraction "
                         "first-class)")
    ap.add_argument("--pc", action="store_true",
                    help="also diff the polynomial-commitment DAS "
                         "workload (lying-encoder parity-fail fraction "
                         "first-class)")
    ap.add_argument("--city", action="store_true",
                    help="also diff the city-scale combined workload "
                         "(shared-scheduler coalesce factor first-class)")
    ap.add_argument("--replicas", action="store_true",
                    help="also diff the scale-out serving-plane workload "
                         "(zero-gap and byte-identity invariants "
                         "first-class)")
    ap.add_argument("--certnative", action="store_true",
                    help="also diff the certificate-native workload "
                         "(cert-vs-column verdict pins and the one-"
                         "pairing-per-block replay invariant first-class)")
    ap.add_argument("--watchtower", action="store_true",
                    help="also diff the watchtower audit workload "
                         "(zero-false-positive and audit-latency-p99-"
                         "budget invariants first-class)")
    ap.add_argument("--ref", default="HEAD",
                    help="git ref holding the baseline (default HEAD)")
    ap.add_argument("--threshold", type=float, default=0.10,
                    help="relative change that counts as a regression "
                         "(default 0.10 = 10%%)")
    ap.add_argument("--advisory", action="store_true",
                    help="always exit 0; print the table only "
                         "(how tier-1 invokes it)")
    ap.add_argument("--json", action="store_true", dest="as_json")
    args = ap.parse_args(argv)

    reports = [compare_file(f, args.ref, args.threshold)
               for f in args.files]
    ingest_rep = (compare_ingest(args.ref, args.threshold)
                  if args.ingest else None)
    bls_rep = (compare_bls(args.ref, args.threshold)
               if args.bls else None)
    das_rep = (compare_das(args.ref, args.threshold)
               if args.das else None)
    pc_rep = (compare_pc(args.ref, args.threshold)
              if args.pc else None)
    city_rep = (compare_city(args.ref, args.threshold)
                if args.city else None)
    repl_rep = (compare_replicated(args.ref, args.threshold)
                if args.replicas else None)
    cert_rep = (compare_certnative(args.ref, args.threshold)
                if args.certnative else None)
    wt_rep = (compare_watchtower(args.ref, args.threshold)
              if args.watchtower else None)
    n_reg = sum(len(r.get("regressions", ())) for r in reports)
    for extra in (ingest_rep, bls_rep, das_rep, pc_rep, city_rep,
                  repl_rep, cert_rep, wt_rep):
        if extra is not None:
            n_reg += len(extra.get("regressions", ()))
    summary = {"ref": args.ref, "threshold": args.threshold,
               "advisory": args.advisory, "total_regressions": n_reg,
               "files": reports}
    if ingest_rep is not None:
        summary["ingest_waterfall"] = ingest_rep
    if bls_rep is not None:
        summary["bls_crossover"] = bls_rep
    if das_rep is not None:
        summary["das_sampling"] = das_rep
    if pc_rep is not None:
        summary["das_pc"] = pc_rep
    if city_rep is not None:
        summary["city_combined"] = city_rep
    if repl_rep is not None:
        summary["city_replicated"] = repl_rep
    if cert_rep is not None:
        summary["certnative"] = cert_rep
    if wt_rep is not None:
        summary["watchtower"] = wt_rep
    if args.as_json:
        print(json.dumps(summary, indent=2))
    else:
        for r in reports:
            if "skipped" in r:
                print(f"{r['file']}: skipped ({r['skipped']})")
                continue
            print(f"{r['file']}: {r['compared']} shared keys, "
                  f"{r['changed']} changed, "
                  f"{len(r['regressions'])} regression(s), "
                  f"{len(r['improvements'])} improvement(s)")
            for row in r["regressions"]:
                print("  REGRESSION %-52s %12g -> %-12g (%+.1f%%, %s-better)"
                      % (row["key"], row["baseline"], row["current"],
                         row["change_pct"], row["direction"]))
            for row in r["improvements"]:
                print("  improved   %-52s %12g -> %-12g (%+.1f%%)"
                      % (row["key"], row["baseline"], row["current"],
                         row["change_pct"]))
        if ingest_rep is not None:
            _print_ingest(ingest_rep)
        if bls_rep is not None:
            _print_bls(bls_rep)
        if das_rep is not None:
            _print_das(das_rep)
        if pc_rep is not None:
            _print_pc(pc_rep)
        if city_rep is not None:
            _print_city(city_rep)
        if repl_rep is not None:
            _print_replicated(repl_rep)
        if cert_rep is not None:
            _print_certnative(cert_rep)
        if wt_rep is not None:
            _print_watchtower(wt_rep)
        verdict = ("ADVISORY — not gating" if args.advisory
                   else ("FAIL" if n_reg else "OK"))
        print(f"bench_compare: {n_reg} regression(s) past "
              f"{args.threshold:.0%} vs {args.ref} [{verdict}]")
    if args.advisory:
        return 0
    return 1 if n_reg else 0


if __name__ == "__main__":
    raise SystemExit(main())
