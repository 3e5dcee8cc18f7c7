#!/usr/bin/env python3
"""Sustained tx-ingress traffic generator (ROADMAP item #4).

Boots a small in-process world — one validator over the KVStore app
with fast consensus timeouts — and drives it with many concurrent
`broadcast_tx_sync` clients through the RPC route table, measuring:

- sustained throughput: committed txs/s over the load window
- commit latency: submit -> Tx event, p50/p99
- admission amortization: app CheckTx invocations (each one is a
  shared-app-mutex acquisition) per admitted tx

Two admission modes make the tentpole comparison:

  --mode batched   micro-batched pipeline (default; windows amortize
                   the app round-trip, sig verify, and mempool lock)
  --mode pertx     pipeline disabled — the seed's per-tx admission

`--signed` wraps every tx in the STX ed25519 envelope so admission
exercises the batch signature-verify stage.

Emits one JSON object on stdout.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import threading
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _build_node(home: str, mode: str, window: int, delay_ms: float,
                signed: bool, lifecycle_rate: int | None = None):
    from cometbft_tpu.abci.kvstore import KVStoreApp
    from cometbft_tpu.config import Config
    from cometbft_tpu.node import Node
    from cometbft_tpu.privval import FilePV
    from cometbft_tpu.types import Timestamp
    from cometbft_tpu.types.genesis import GenesisDoc, GenesisValidator

    class CountingKVStore(KVStoreApp):
        """KVStore with app-call accounting: every check_tx/check_txs
        is one serialized app-mutex acquisition — the quantity the
        micro-batched pipeline amortizes."""

        def __init__(self):
            super().__init__()
            self.mempool_calls = 0
            self.txs_checked = 0

        def check_tx(self, tx):
            self.mempool_calls += 1
            self.txs_checked += 1
            return super().check_tx(tx)

        def check_txs(self, txs):
            self.mempool_calls += 1
            self.txs_checked += len(txs)
            return [KVStoreApp.check_tx(self, tx) for tx in txs]

    os.makedirs(os.path.join(home, "config"), exist_ok=True)
    os.makedirs(os.path.join(home, "data"), exist_ok=True)
    pv = FilePV.generate(None, None)
    genesis = GenesisDoc(
        chain_id="txload-chain",
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
    cfg.base.moniker = "txload"
    cfg.base.db_backend = "mem"
    # "tpu" = the self-calibrating dispatch: admission windows go to the
    # native batch engine on CPU-only hosts, device paths when present
    cfg.base.crypto_backend = "tpu"
    cfg.p2p.laddr = "tcp://127.0.0.1:0"
    cfg.rpc.laddr = ""  # in-process RPC LocalClient; no HTTP server
    cfg.consensus.timeout_propose = 0.6
    cfg.consensus.timeout_propose_delta = 0.2
    cfg.consensus.timeout_prevote = 0.3
    cfg.consensus.timeout_prevote_delta = 0.1
    cfg.consensus.timeout_precommit = 0.3
    cfg.consensus.timeout_precommit_delta = 0.1
    cfg.consensus.timeout_commit = 0.05
    cfg.mempool.size = 20000
    cfg.mempool.cache_size = 200000
    if mode == "pertx":
        cfg.mempool.admission_window = 0
    else:
        cfg.mempool.admission_window = window
        cfg.mempool.admission_max_delay_ms = delay_ms
    # both modes verify STX signatures when --signed: per-tx mode does a
    # native single-verify per tx, batched mode one batch verify per
    # window: what --mode batched and --mode pertx differ by
    cfg.mempool.admission_verify_sigs = signed
    if lifecycle_rate is not None:
        # trace sink inside the tempdir home -> tx.lifecycle records land
        # where run() can feed them to latency_analyze before teardown
        cfg.instrumentation.trace_sink = "data/trace.jsonl"
        cfg.instrumentation.txlife_sample_rate = lifecycle_rate
    app = CountingKVStore()
    return Node(cfg, app=app), app


def run(mode: str, clients: int, duration_s: float, window: int,
        delay_ms: float, signed: bool,
        lifecycle_rate: int | None = None) -> dict:
    home = tempfile.mkdtemp(prefix="txload-")
    if lifecycle_rate is not None:
        from cometbft_tpu.utils import txlife as _txlife

        _txlife.reset()
    node, app = _build_node(home, mode, window, delay_ms, signed,
                            lifecycle_rate)
    from cometbft_tpu.rpc.client import LocalClient

    priv = None
    if signed:
        from cometbft_tpu.crypto.ed25519 import Ed25519PrivKey

        priv = Ed25519PrivKey.generate()
    node.start()
    submit_times: dict[bytes, float] = {}
    latencies: list[float] = []
    counts = {"submitted": 0, "accepted": 0, "rejected": 0, "committed": 0}
    lock = threading.Lock()
    stop = threading.Event()

    # one NewBlock message per block (a per-Tx subscription overflows
    # its 256-message buffer the moment a block carries a few thousand
    # txs and gets dropped as a slow consumer)
    sub = node.event_bus.subscribe("txload", "tm.event = 'NewBlock'")

    def collector():
        from cometbft_tpu.utils.pubsub import SubscriptionCancelled

        while True:
            try:
                msg = sub.next(timeout=0.5)
            except SubscriptionCancelled:
                return
            if msg is None:
                if stop.is_set() and not submit_times:
                    return
                continue
            now = time.perf_counter()
            for tx in msg.data["block"].data.txs:
                counts["committed"] += 1
                t0 = submit_times.pop(bytes(tx), None)
                if t0 is not None:
                    latencies.append(now - t0)

    def producer(cid: int):
        client = LocalClient(node.rpc_env)
        seq = 0
        while not stop.is_set():
            payload = f"c{cid}k{seq}={seq}".encode()
            if priv is not None:
                from cometbft_tpu.mempool import wrap_signed_tx

                tx = wrap_signed_tx(priv, payload)
            else:
                tx = payload
            seq += 1
            with lock:
                submit_times[tx] = time.perf_counter()
                counts["submitted"] += 1
            try:
                r = client.broadcast_tx_sync(tx=tx.hex())
                ok = int(r.get("code", 1)) == 0
            except Exception:  # noqa: BLE001 — count and continue
                ok = False
            with lock:
                if ok:
                    counts["accepted"] += 1
                else:
                    counts["rejected"] += 1
                    submit_times.pop(tx, None)
            if not ok:
                # back off when the pool is full so the generator does
                # not starve consensus of the core it needs to drain it
                stop.wait(0.01)

    coll = threading.Thread(target=collector, daemon=True)
    coll.start()
    producers = [
        threading.Thread(target=producer, args=(i,), daemon=True)
        for i in range(clients)
    ]
    t_start = time.perf_counter()
    for p in producers:
        p.start()
    stop.wait(duration_s)
    stop.set()
    for p in producers:
        p.join(timeout=5)
    t_load = time.perf_counter() - t_start
    # grace: let in-flight txs commit
    deadline = time.perf_counter() + max(3.0, duration_s * 0.5)
    while submit_times and time.perf_counter() < deadline:
        time.sleep(0.1)
    node.event_bus.unsubscribe_all("txload")
    coll.join(timeout=2)
    height = node.consensus.sm_state.last_block_height
    node.stop()
    waterfall = None
    if lifecycle_rate is not None:
        # flush + close the sink, decompose it, THEN drop the tempdir
        from cometbft_tpu.utils import trace as _trace

        _trace.disable()
        sys.path.insert(0, os.path.join(REPO, "tools"))
        import latency_analyze

        try:
            waterfall = latency_analyze.analyze(
                [os.path.join(home, "data", "trace.jsonl")])
        except Exception as e:  # noqa: BLE001 — report, don't crash load
            waterfall = {"error": str(e)}
    shutil.rmtree(home, ignore_errors=True)

    lat_ms = sorted(x * 1e3 for x in latencies)

    def pct(p: float) -> float:
        if not lat_ms:
            return float("nan")
        return lat_ms[min(len(lat_ms) - 1, int(p * len(lat_ms)))]

    committed = counts["committed"]
    res = {
        "metric": "ingest_sustained_load",
        "mode": mode,
        "clients": clients,
        "duration_s": round(t_load, 2),
        "signed": signed,
        "window": 0 if mode == "pertx" else window,
        "submitted": counts["submitted"],
        "accepted": counts["accepted"],
        "rejected": counts["rejected"],
        "committed": committed,
        "height": height,
        "txs_per_sec": round(committed / t_load, 1),
        "commit_latency_ms": {
            "p50": round(pct(0.50), 1),
            "p99": round(pct(0.99), 1),
        },
        "app_mempool_calls": app.mempool_calls,
        "txs_per_app_call": round(
            app.txs_checked / max(app.mempool_calls, 1), 2),
    }
    if waterfall is not None:
        res["lifecycle_rate"] = lifecycle_rate
        res["stage_waterfall"] = waterfall
    return res


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--mode", choices=("batched", "pertx"),
                    default="batched")
    ap.add_argument("--clients", type=int, default=8)
    ap.add_argument("--duration", type=float, default=10.0)
    ap.add_argument("--window", type=int, default=256)
    ap.add_argument("--delay-ms", type=float, default=2.0)
    ap.add_argument("--signed", action="store_true",
                    help="STX ed25519 envelopes -> batch verify stage")
    ap.add_argument("--lifecycle", action="store_true",
                    help="trace tx.lifecycle stages to a sink and attach "
                         "the latency_analyze stage waterfall")
    ap.add_argument("--lifecycle-rate", type=int, default=16,
                    help="1/N hash-prefix sampling for --lifecycle runs "
                         "(denser than the production default of 64 so "
                         "short runs still get statistics)")
    args = ap.parse_args()
    res = run(args.mode, args.clients, args.duration, args.window,
              args.delay_ms, args.signed,
              lifecycle_rate=args.lifecycle_rate if args.lifecycle else None)
    print(json.dumps(res))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
