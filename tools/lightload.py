#!/usr/bin/env python3
"""Concurrent light-client traffic generator (ROADMAP item #2).

Boots one in-process validator node with the light serving surface on
(`[light] serve = true`) and simulates a large light-client population
against it:

- N simulated stream subscribers (default 10000): each is a real
  server-side `StreamSubscriber` queue registered on the service — the
  exact object a /light_stream HTTP connection holds — receiving every
  committed height's header+proof payload; drain sweeps count
  deliveries and the distinct clients served.
- A handful of REAL /light_stream HTTP connections reading
  chunked-transfer JSONL off the RPC server, proving the wire path and
  verifying each received proof client-side (light.verify_ancestry).
- A worker pool issuing light_bisect + light_mmr_proof requests through
  the route table, timing per-proof latency (p50/p99) and driving the
  verified-commit cache so the per-height verify amortization is
  observable: `max_verify_calls_per_height` must be exactly 1 no matter
  how many clients asked.

A small tx producer keeps blocks committing underneath. Emits one JSON
object on stdout.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import random
import shutil
import sys
import tempfile
import threading
import time
import urllib.request

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _build_node(home: str):
    from cometbft_tpu.abci.kvstore import KVStoreApp
    from cometbft_tpu.config import Config
    from cometbft_tpu.node import Node
    from cometbft_tpu.privval import FilePV
    from cometbft_tpu.types import Timestamp
    from cometbft_tpu.types.genesis import GenesisDoc, GenesisValidator

    os.makedirs(os.path.join(home, "config"), exist_ok=True)
    os.makedirs(os.path.join(home, "data"), exist_ok=True)
    pv = FilePV.generate(None, None)
    genesis = GenesisDoc(
        chain_id="lightload-chain",
        genesis_time=Timestamp(1_700_000_000, 0),
        validators=[GenesisValidator(pv.pub_key().bytes(), 10, "v0")],
    )
    genesis.save(os.path.join(home, "config/genesis.json"))
    with open(os.path.join(home, "config/priv_validator_key.json"), "w") as f:
        json.dump({
            "address": pv.pub_key().address().hex(),
            "pub_key": pv.pub_key().bytes().hex(),
            "priv_key": pv._priv.bytes().hex(),
        }, f)

    cfg = Config()
    cfg.base.home = home
    cfg.base.moniker = "lightload"
    cfg.base.db_backend = "mem"
    cfg.base.crypto_backend = "tpu"  # self-calibrating dispatch
    cfg.p2p.laddr = "tcp://127.0.0.1:0"
    cfg.rpc.laddr = "tcp://127.0.0.1:0"  # real HTTP for /light_stream
    cfg.consensus.timeout_propose = 0.6
    cfg.consensus.timeout_propose_delta = 0.2
    cfg.consensus.timeout_prevote = 0.3
    cfg.consensus.timeout_prevote_delta = 0.1
    cfg.consensus.timeout_precommit = 0.3
    cfg.consensus.timeout_precommit_delta = 0.1
    cfg.consensus.timeout_commit = 0.05
    cfg.light.serve = True
    cfg.light.persist_mmr = False  # mem node: rebuild is free
    return Node(cfg, app=KVStoreApp())


def run(clients: int, duration_s: float, workers: int,
        http_streams: int) -> dict:
    home = tempfile.mkdtemp(prefix="lightload-")
    node = _build_node(home)
    from cometbft_tpu.light import verify_ancestry
    from cometbft_tpu.rpc.client import LocalClient

    node.start()
    srv = node.light_serve
    rpc_host, rpc_port = node.rpc_addr
    stop = threading.Event()

    # -- tx producer: keeps consensus committing non-empty blocks -------
    def producer():
        client = LocalClient(node.rpc_env)
        seq = 0
        while not stop.is_set():
            try:
                client.broadcast_tx_sync(tx=f"lk{seq}={seq}".encode().hex())
            except Exception:  # noqa: BLE001 — pool full: back off
                stop.wait(0.05)
            seq += 1
            stop.wait(0.01)

    # -- simulated subscriber population ---------------------------------
    sub_ids, subs = [], []
    for _ in range(clients):
        sid, sub = srv.subscribe()
        sub_ids.append(sid)
        subs.append(sub)

    delivered = [0] * clients  # payloads received per simulated client
    deliveries_lock = threading.Lock()
    total_delivered = 0

    def drainer():
        nonlocal total_delivered
        while not stop.is_set():
            got = 0
            for i, sub in enumerate(subs):
                n = len(sub.drain())
                if n:
                    delivered[i] += n
                    got += n
            if got:
                with deliveries_lock:
                    total_delivered += got
            stop.wait(0.05)

    # -- real HTTP /light_stream readers ---------------------------------
    http_lines = [0] * http_streams
    http_verified = [0] * http_streams
    http_errors: list[str] = []

    def http_reader(i: int):
        url = (f"http://{rpc_host}:{rpc_port}/light_stream"
               f"?timeout_s={duration_s + 5}")
        try:
            with urllib.request.urlopen(url, timeout=duration_s + 10) as resp:
                for raw in resp:
                    if stop.is_set():
                        break
                    line = raw.strip()
                    if not line:
                        continue
                    p = json.loads(line)
                    http_lines[i] += 1
                    ok = verify_ancestry(
                        bytes.fromhex(p["mmr_root"]), p["mmr_size"],
                        srv.base_height, p["height"],
                        bytes.fromhex(p["hash"]),
                        bytes.fromhex(p["mmr_proof"]),
                    )
                    if ok:
                        http_verified[i] += 1
                    else:
                        http_errors.append(
                            f"stream {i}: proof failed at {p['height']}")
        except Exception as e:  # noqa: BLE001 — stream torn down at stop
            if not stop.is_set():
                http_errors.append(f"stream {i}: {e}")

    # -- request workers: proofs + bisection through the route table -----
    proof_lat: list[float] = []
    proof_sizes: list[int] = []
    bisect_calls = [0]
    req_lock = threading.Lock()

    def requester(wid: int):
        client = LocalClient(node.rpc_env)
        rng = random.Random(wid)
        while not stop.is_set():
            size, _root = srv.mmr_snapshot()
            if size < 2 or srv.base_height is None:
                stop.wait(0.05)
                continue
            tip = srv.base_height + size - 1
            h = rng.randint(srv.base_height, tip)
            t0 = time.perf_counter()
            try:
                r = client.light_mmr_proof(height=str(h))
            except Exception:  # noqa: BLE001 — height pruned mid-race
                continue
            dt = time.perf_counter() - t0
            with req_lock:
                proof_lat.append(dt)
                proof_sizes.append(int(r["proof_bytes"]))
            if rng.random() < 0.25 and tip > srv.base_height + 1:
                try:
                    client.light_bisect(
                        trusted_height=str(srv.base_height),
                        height=str(rng.randint(srv.base_height + 1, tip)),
                    )
                    with req_lock:
                        bisect_calls[0] += 1
                except Exception:  # noqa: BLE001
                    pass
            stop.wait(0.002)

    threads = [threading.Thread(target=producer, daemon=True),
               threading.Thread(target=drainer, daemon=True)]
    threads += [threading.Thread(target=http_reader, args=(i,), daemon=True)
                for i in range(http_streams)]
    threads += [threading.Thread(target=requester, args=(i,), daemon=True)
                for i in range(workers)]
    t_start = time.perf_counter()
    start_height = node.consensus.sm_state.last_block_height
    for t in threads:
        t.start()
    stop.wait(duration_s)
    stop.set()
    for t in threads:
        t.join(timeout=5)
    t_load = time.perf_counter() - t_start
    end_height = node.consensus.sm_state.last_block_height

    # final sweep so late payloads count
    for i, sub in enumerate(subs):
        n = len(sub.drain())
        delivered[i] += n
        total_delivered += n
    stats = srv.stats()
    for sid in sub_ids:
        srv.unsubscribe(sid)
    node.stop()
    shutil.rmtree(home, ignore_errors=True)

    lat_ms = sorted(x * 1e3 for x in proof_lat)

    def pct(p: float) -> float:
        if not lat_ms:
            return float("nan")
        return lat_ms[min(len(lat_ms) - 1, int(p * len(lat_ms)))]

    heights = end_height - start_height
    mmr_size = stats["mmr_size"]
    bound = 96 * math.log2(max(mmr_size, 2))
    return {
        "metric": "light_stream_10000c",
        "clients": clients,
        "http_stream_clients": http_streams,
        "request_workers": workers,
        "duration_s": round(t_load, 2),
        "heights_committed": heights,
        "headers_per_sec": round(heights / t_load, 2),
        "deliveries": total_delivered,
        "deliveries_per_sec": round(total_delivered / t_load, 1),
        "clients_served": sum(1 for d in delivered if d > 0),
        "http_stream_lines": sum(http_lines),
        "http_stream_verified": sum(http_verified),
        "http_stream_errors": http_errors[:5],
        "proof_requests": len(proof_lat),
        "proof_p50_ms": round(pct(0.50), 3),
        "proof_p99_ms": round(pct(0.99), 3),
        "proof_bytes_max": max(proof_sizes, default=0),
        "proof_bytes_bound": round(bound, 1),
        "bisect_calls": bisect_calls[0],
        "mmr_size": mmr_size,
        "verify_cache_hits": stats["cache_hits"],
        "verify_cache_misses": stats["cache_misses"],
        "max_verify_calls_per_height": stats["max_verify_calls_per_height"],
        "stream_dropped": stats["stream_dropped"],
    }


def run_remote(endpoints: list[str], clients: int, duration_s: float,
               workers: int) -> dict:
    """Multi-endpoint mode (--endpoints): drive an EXISTING serving
    fleet — typically `cli.py replica` processes — instead of booting a
    node. Logical clients pin to an endpoint round-robin; each pin
    group shares one real /light_stream connection (a remote driver
    cannot register in-process subscriber queues, so group fan-out is
    the delivery accounting model) with a height cursor. On a
    connection error the group FAILS OVER to the next endpoint and
    reconnects with `?since=<cursor>`, so the replay window covers the
    outage: the per-group gap counter stays 0 unless heights were truly
    lost. Proof workers round-robin `light_mmr_proof` across endpoints
    and differentially compare two endpoints' answers per height."""
    from cometbft_tpu.light import verify_ancestry
    from cometbft_tpu.rpc.client import HTTPClient

    n_eps = len(endpoints)
    groups = min(clients, n_eps) or 1
    group_clients = [len(range(g, clients, groups)) for g in range(groups)]
    stop = threading.Event()

    base_height = None
    for ep in endpoints:
        try:
            st = HTTPClient(f"http://{ep}", timeout=5).light_status()
            base_height = int(st["base_height"])
            break
        except Exception:  # noqa: BLE001 — endpoint still booting
            continue

    lines = [0] * groups
    verified = [0] * groups
    gaps = [0] * groups
    dups = [0] * groups
    failovers = [0] * groups
    connects = [0] * groups
    cursors = [0] * groups
    deliveries = [0]
    dl_lock = threading.Lock()
    errors: list[str] = []

    def reader(g: int):
        order = endpoints[g % n_eps:] + endpoints[:g % n_eps]
        idx = 0
        while not stop.is_set():
            ep = order[idx % len(order)]
            url = (f"http://{ep}/light_stream"
                   f"?since={cursors[g]}&timeout_s={duration_s + 5}")
            try:
                with urllib.request.urlopen(
                        url, timeout=duration_s + 10) as resp:
                    connects[g] += 1
                    for raw in resp:
                        if stop.is_set():
                            return
                        line = raw.strip()
                        if not line:
                            continue
                        p = json.loads(line)
                        h = p["height"]
                        if h <= cursors[g]:
                            dups[g] += 1
                            continue
                        if cursors[g] and h > cursors[g] + 1:
                            gaps[g] += h - cursors[g] - 1
                        cursors[g] = h
                        lines[g] += 1
                        if base_height is not None and verify_ancestry(
                            bytes.fromhex(p["mmr_root"]), p["mmr_size"],
                            base_height, h, bytes.fromhex(p["hash"]),
                            bytes.fromhex(p["mmr_proof"]),
                        ):
                            verified[g] += 1
                        with dl_lock:
                            deliveries[0] += group_clients[g]
            except Exception as e:  # noqa: BLE001 — endpoint died: fail over
                if stop.is_set():
                    return
                idx += 1
                failovers[g] += 1
                if len(errors) < 5:
                    errors.append(f"group {g} @ {ep}: {e}")
                stop.wait(0.2)

    proof_lat: list[float] = []
    diff_checks = [0]
    diff_mismatches = [0]
    req_lock = threading.Lock()

    def requester(wid: int):
        rng = random.Random(wid)
        cls = [HTTPClient(f"http://{ep}", timeout=10) for ep in endpoints]
        while not stop.is_set():
            tip = max(cursors)
            if base_height is None or tip < base_height + 1:
                stop.wait(0.05)
                continue
            h = rng.randint(base_height, tip)
            pin = wid % n_eps
            t0 = time.perf_counter()
            try:
                r = cls[pin].light_mmr_proof(height=str(h))
            except Exception:  # noqa: BLE001 — pruned/lagging: retry
                stop.wait(0.05)
                continue
            with req_lock:
                proof_lat.append(time.perf_counter() - t0)
            if n_eps > 1 and rng.random() < 0.25:
                # serving-plane differential: two replicas at the SAME
                # accumulator state must answer byte-identically; a
                # replica mid-apply answers against a different
                # mmr_size, which is lag, not divergence — skip it
                other = (pin + 1 + rng.randrange(n_eps - 1)) % n_eps
                try:
                    r2 = cls[other].light_mmr_proof(height=str(h))
                except Exception:  # noqa: BLE001 — lagging replica
                    continue
                if r.get("mmr_size") != r2.get("mmr_size"):
                    continue
                with req_lock:
                    diff_checks[0] += 1
                    if r != r2:
                        diff_mismatches[0] += 1
            stop.wait(0.002)

    threads = [threading.Thread(target=reader, args=(g,), daemon=True)
               for g in range(groups)]
    threads += [threading.Thread(target=requester, args=(i,), daemon=True)
                for i in range(workers)]
    t_start = time.perf_counter()
    for t in threads:
        t.start()
    stop.wait(duration_s)
    stop.set()
    for t in threads:
        t.join(timeout=5)
    t_load = time.perf_counter() - t_start

    lat_ms = sorted(x * 1e3 for x in proof_lat)

    def pct(p: float) -> float:
        if not lat_ms:
            return float("nan")
        return lat_ms[min(len(lat_ms) - 1, int(p * len(lat_ms)))]

    return {
        "metric": "light_stream_remote",
        "endpoints": endpoints,
        "clients": clients,
        "stream_groups": groups,
        "duration_s": round(t_load, 2),
        "stream_lines": sum(lines),
        "stream_verified": sum(verified),
        "deliveries": deliveries[0],
        "deliveries_per_sec": round(deliveries[0] / t_load, 1),
        "gaps": sum(gaps),
        "dups": sum(dups),
        "failovers": sum(failovers),
        "connects": sum(connects),
        "max_height_seen": max(cursors, default=0),
        "proof_requests": len(proof_lat),
        "proof_p50_ms": round(pct(0.50), 3),
        "proof_p99_ms": round(pct(0.99), 3),
        "diff_checks": diff_checks[0],
        "diff_mismatches": diff_mismatches[0],
        "errors": errors,
    }


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--clients", type=int, default=10000,
                    help="simulated stream subscribers")
    ap.add_argument("--duration", type=float, default=10.0)
    ap.add_argument("--workers", type=int, default=8,
                    help="proof/bisect request workers")
    ap.add_argument("--http-streams", type=int, default=4,
                    help="real /light_stream HTTP connections")
    ap.add_argument("--endpoints", default="",
                    help="comma-separated host:port serving endpoints "
                         "(replica fleet); skips booting a node")
    args = ap.parse_args()
    if args.endpoints:
        eps = [e.strip() for e in args.endpoints.split(",") if e.strip()]
        res = run_remote(eps, args.clients, args.duration, args.workers)
    else:
        res = run(args.clients, args.duration, args.workers,
                  args.http_streams)
    print(json.dumps(res))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
