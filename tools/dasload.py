#!/usr/bin/env python3
"""Data-availability sampling fleet driver (ROADMAP item #3, ISSUE 14).

Boots one in-process validator with DA encoding on (`[da] enabled =
true`) and drives a large sampling-client population against its
serving surface:

- a tx producer keeps non-empty blocks committing, each one
  erasure-coded (k data + m parity shards over GF(2^16)) and committed
  to in the header's da_root at proposal time;
- per committed height, N da/sampler.py clients (default 1000) draw
  seeded random chunk indices and verify each opening proof against
  the header root — the in-process `DAServe.sample` transport, i.e.
  the same object the `da_sample` RPC route calls;
- a handful of REAL HTTP `da_sample` requests prove the wire path
  (hex/b64 decode + client-side proof verification);
- an adversarial leg re-runs the fleet against a height with m+1
  chunks withheld (the minimum unrecoverable suppression): clients
  must fail samples and NOT reach confidence;
- the native GF(2^16) codec is timed against the numpy oracle on a
  proposal-sized payload (same parity, differentially checked here).

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
import urllib.error
import urllib.request

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _build_node(home: str, k: int, m: int, pc: bool = False,
                k_c: int = 4, m_c: int = 4):
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
        chain_id="dasload-chain",
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
    cfg.base.moniker = "dasload"
    cfg.base.db_backend = "mem"
    cfg.base.crypto_backend = "cpu"  # 1 validator: batching buys nothing
    cfg.p2p.laddr = "tcp://127.0.0.1:0"
    cfg.rpc.laddr = "tcp://127.0.0.1:0"  # real HTTP for da_sample
    cfg.consensus.timeout_propose = 0.6
    cfg.consensus.timeout_propose_delta = 0.2
    cfg.consensus.timeout_prevote = 0.3
    cfg.consensus.timeout_prevote_delta = 0.1
    cfg.consensus.timeout_precommit = 0.3
    cfg.consensus.timeout_precommit_delta = 0.1
    cfg.consensus.timeout_commit = 0.05
    cfg.light.serve = True  # /light_stream carries the da_* fields
    cfg.light.persist_mmr = False
    cfg.da.enabled = True
    cfg.da.data_shards = k
    cfg.da.parity_shards = m
    cfg.da.pc = pc
    cfg.da.pc_data_cols = k_c
    cfg.da.pc_parity_cols = m_c
    return Node(cfg, app=KVStoreApp())


def _http_sample(host, port, height, index, da_root):
    """One da_sample over real HTTP, proof verified client-side."""
    import base64

    from cometbft_tpu.crypto import merkle
    from cometbft_tpu.da.commit import DACommitment

    url = (f"http://{host}:{port}/da_sample"
           f"?height={height}&index={index}")
    with urllib.request.urlopen(url, timeout=10) as resp:
        r = json.loads(resp.read())["result"]
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
    ok = (com.root() == da_root
          and com.verify_sample(int(r["index"]), chunk, proof))
    return ok


def _bench_codec(k: int, m: int, payload_bytes: int) -> dict:
    """Native vs oracle encode on one proposal-sized payload; parity
    must be byte-identical (the fleet leg already trusts dispatch —
    this pins the differential in the workload record too)."""
    import numpy as np

    from cometbft_tpu.crypto import native
    from cometbft_tpu.da import rs
    from cometbft_tpu.da.commit import split_payload

    payload = np.random.default_rng(7).bytes(payload_bytes)
    data = split_payload(payload, k)
    t0 = time.perf_counter()
    oracle = rs.encode_oracle(data, m)
    t_oracle = time.perf_counter() - t0
    out = {
        "payload_bytes": payload_bytes,
        "oracle_encode_ms": round(t_oracle * 1e3, 2),
        "oracle_mb_s": round(payload_bytes / t_oracle / 1e6, 1),
        "native_available": native.rs_available(),
        "rs_threads": native.rs_threads(),
    }
    if native.rs_available():
        sl = len(data[0])
        blob = b"".join(data)
        native.rs_encode(blob, k, m, sl)  # warmup (table build)
        t0 = time.perf_counter()
        nat = native.rs_encode(blob, k, m, sl)
        t_native = time.perf_counter() - t0
        assert nat == b"".join(oracle), "native parity != oracle parity"
        out["native_encode_ms"] = round(t_native * 1e3, 2)
        out["native_mb_s"] = round(payload_bytes / t_native / 1e6, 1)
        out["native_speedup"] = round(t_oracle / t_native, 2)
    return out


def run(clients: int, duration_s: float, k: int, m: int,
        http_samples: int, codec_mb: float) -> dict:
    home = tempfile.mkdtemp(prefix="dasload-")
    node = _build_node(home, k, m)
    from cometbft_tpu.da.sampler import Sampler
    from cometbft_tpu.rpc.client import LocalClient

    node.start()
    srv = node.da_serve
    rpc_host, rpc_port = node.rpc_addr
    stop = threading.Event()

    def producer():
        client = LocalClient(node.rpc_env)
        seq = 0
        while not stop.is_set():
            try:
                client.broadcast_tx_sync(
                    tx=f"das{seq}={'x' * 64}".encode().hex())
            except Exception:  # noqa: BLE001 — pool full: back off
                stop.wait(0.05)
            seq += 1
            stop.wait(0.005)

    # one reusable fleet: seeded draws differ per (client, height, root)
    fleet = [Sampler(client_id=i, n=k + m, k=k, confidence=0.99, seed=1)
             for i in range(clients)]

    def run_fleet(height: int, da_root: bytes) -> dict:
        confident = 0
        failed_clients = 0
        samples_ok = 0
        samples_failed = 0
        proof_bytes = 0
        t0 = time.perf_counter()
        for s in fleet:
            res = s.run(height, da_root, srv.sample)
            samples_ok += res.samples_ok
            samples_failed += res.samples_failed
            proof_bytes += res.proof_bytes
            if res.confident:
                confident += 1
            if res.detected_withholding:
                failed_clients += 1
        dt = time.perf_counter() - t0
        total = samples_ok + samples_failed
        return {
            "clients": len(fleet),
            "clients_confident": confident,
            "clients_detected_withholding": failed_clients,
            "samples": total,
            "samples_ok": samples_ok,
            "samples_per_sec": round(total / dt, 1) if dt else 0.0,
            "proof_bytes_per_sample": (
                round(proof_bytes / samples_ok, 1) if samples_ok else 0.0),
            "fleet_s": round(dt, 3),
        }

    t_prod = threading.Thread(target=producer, daemon=True)
    t_start = time.perf_counter()
    start_height = node.consensus.sm_state.last_block_height
    t_prod.start()

    # honest legs: sample every freshly committed height until the
    # duration budget is spent
    honest_legs = []
    last_sampled = 0
    deadline = time.monotonic() + duration_s
    while time.monotonic() < deadline:
        st = srv.stats()
        h = st["max_height"]
        if h and h > last_sampled:
            com = srv.commitment(h)
            if com is None:  # trimmed mid-race
                continue
            leg = run_fleet(h, com.root())
            leg["height"] = h
            # per-sample wire bound: one chunk + the Merkle path
            # (leaf hash + ceil(log2 n) aunts) + the 12-byte header
            leg["chunk_bytes"] = 2 * max(1, -(-com.payload_len // (2 * k)))
            leg["proof_bytes_bound"] = (
                leg["chunk_bytes"] + 32 * (1 + (k + m - 1).bit_length()) + 12)
            honest_legs.append(leg)
            last_sampled = h
        else:
            time.sleep(0.02)

    # wire leg: a handful of REAL HTTP da_sample fetches
    http_ok = 0
    http_errors = []
    wire_h = last_sampled
    wire_root = srv.commitment(wire_h).root() if wire_h else b""
    for i in range(http_samples):
        try:
            if _http_sample(rpc_host, rpc_port, wire_h, i % (k + m),
                            wire_root):
                http_ok += 1
            else:
                http_errors.append(f"sample {i}: proof failed")
        except Exception as e:  # noqa: BLE001 — record, gate below
            http_errors.append(f"sample {i}: {e}")

    # adversarial leg: withhold m+1 chunks of the latest height — the
    # minimum suppression that makes the payload unrecoverable — and
    # re-run the fleet. Detection is probabilistic per client (each
    # sample hits a withheld chunk with prob > (m+1)/n), so the gate is
    # on the detecting FRACTION, not unanimity.
    adv_h = last_sampled
    srv.set_withholding(adv_h, range(m + 1))
    adv = run_fleet(adv_h, srv.commitment(adv_h).root())
    adv["height"] = adv_h
    adv["withheld_chunks"] = m + 1

    stop.set()
    t_prod.join(timeout=5)
    t_load = time.perf_counter() - t_start
    end_height = node.consensus.sm_state.last_block_height
    stats = srv.stats()
    header_root = node.block_store.load_block(adv_h).header.da_root
    node.stop()
    shutil.rmtree(home, ignore_errors=True)

    codec = _bench_codec(k, m, int(codec_mb * 1e6))

    heights = end_height - start_height
    agg = {
        "clients": clients,
        "heights_sampled": len(honest_legs),
        "clients_confident_min": min(
            (l["clients_confident"] for l in honest_legs), default=0),
        "samples_total": sum(l["samples"] for l in honest_legs),
        "samples_per_sec": round(
            sum(l["samples_per_sec"] for l in honest_legs)
            / max(1, len(honest_legs)), 1),
        "proof_bytes_per_sample": max(
            (l["proof_bytes_per_sample"] for l in honest_legs), default=0.0),
        "proof_bytes_bound": max(
            (l["proof_bytes_bound"] for l in honest_legs), default=0),
    }
    return {
        "metric": "das_sampling_1000c",
        "data_shards": k,
        "parity_shards": m,
        "duration_s": round(t_load, 2),
        "heights_committed": heights,
        "header_da_root": header_root.hex(),
        "honest": agg,
        "honest_legs": honest_legs[:3],
        "withholding": adv,
        "http_samples_ok": http_ok,
        "http_samples": http_samples,
        "http_errors": http_errors[:5],
        "blocks_encoded": stats["blocks_encoded"],
        "samples_served": stats["samples_served"],
        "withheld_hits": stats["withheld_hits"],
        "codec": codec,
    }


def _http_pc_sample(host, port, height, row, cols, pc_root, com) -> bool:
    """One da_pc_sample over real HTTP: commitments fetched via
    da_pc_commitments are cross-checked against the in-process ones,
    then the multiproof is verified client-side."""
    from cometbft_tpu.da import pc as pcmod

    url = f"http://{host}:{port}/da_pc_commitments?height={height}"
    with urllib.request.urlopen(url, timeout=10) as resp:
        r = json.loads(resp.read())["result"]
    wire_com = pcmod.PCCommitment(
        n_r=int(r["rows"]), k_r=int(r["data_rows"]),
        n_c=int(r["cols"]), k_c=int(r["data_cols"]),
        payload_len=int(r["payload_len"]),
        commitments=tuple(bytes.fromhex(c) for c in r["commitments"]),
    )
    if wire_com.root() != pc_root or wire_com != com:
        return False
    colarg = ",".join(str(c) for c in cols)
    url = (f"http://{host}:{port}/da_pc_sample"
           f"?height={height}&row={row}&cols={colarg}")
    with urllib.request.urlopen(url, timeout=10) as resp:
        r = json.loads(resp.read())["result"]
    ys = [int(y, 16) for y in r["ys"]]
    proof = bytes.fromhex(r["proof"])
    return pcmod.verify_sample(wire_com, pc_root, row, cols, ys, proof)


def _bench_openings(k_r: int, n_cols: int, iters: int) -> dict:
    """Multiproof opening throughput, native MSM engine vs the forced
    Python oracle on the SAME folded quotient — the pipelined-engine
    claim measured, differential equality asserted per iteration."""
    from cometbft_tpu.crypto import kzg, native

    srs = kzg.setup(k_r)
    cols = [
        [(7 * j + i * i + 3) % kzg.R for i in range(k_r)]
        for j in range(n_cols)
    ]
    coms = [kzg.commit(c, srs) for c in cols]
    z = 3
    kzg.open_multi(cols, coms, z, srs)  # warmup (SRS cache etc.)
    t0 = time.perf_counter()
    for _ in range(iters):
        ys_n, pi_n = kzg.open_multi(cols, coms, z, srs)
    t_native = (time.perf_counter() - t0) / iters
    t0 = time.perf_counter()
    ys_o, pi_o = kzg.open_multi(cols, coms, z, srs, force_oracle=True)
    t_oracle = time.perf_counter() - t0
    assert (ys_n, pi_n) == (ys_o, pi_o), "native opening != oracle"
    t0 = time.perf_counter()
    ok = kzg.verify_multi(coms, z, ys_n, pi_n, srs)
    t_verify = time.perf_counter() - t0
    assert ok, "multiproof verify failed"
    return {
        "quotient_degree": k_r - 1,
        "cols_per_opening": n_cols,
        "native_available": native.g1_msm_available(),
        "msm_threads": native.g1_msm_threads(),
        "native_open_ms": round(t_native * 1e3, 2),
        "oracle_open_ms": round(t_oracle * 1e3, 2),
        "native_openings_per_s": round(1.0 / t_native, 1),
        "oracle_openings_per_s": round(1.0 / t_oracle, 1),
        "native_speedup": round(t_oracle / t_native, 2),
        "verify_ms": round(t_verify * 1e3, 2),
    }


def run_pc(clients: int, duration_s: float, k_c: int, m_c: int,
           http_samples: int, open_iters: int) -> dict:
    """--pc fleet mode: the 2D polynomial-commitment track end-to-end.

    Boots one validator with `[da] pc = true`, keeps blocks committing,
    and per height drives N PCSampler clients: each downloads the
    commitment list once, runs the parity-linearity (lying-encoder)
    check, then verifies ONE aggregated multiproof for its s sampled
    columns. Legs: honest fleet (byte accounting INCLUDING the
    commitment download), withholding (m_c+1 columns refused),
    lying-encoder (garbage parity under honest commitments — 2D
    detects via the linearity check while a 1D fleet against the
    Merkle-committed analogue stays fully confident), real-HTTP
    multiproofs, and the native-vs-oracle opening throughput bench.
    """
    home = tempfile.mkdtemp(prefix="daspcload-")
    node = _build_node(home, 16, 16, pc=True, k_c=k_c, m_c=m_c)
    from cometbft_tpu.da.sampler import PCSampler, Sampler
    from cometbft_tpu.rpc.client import LocalClient

    node.start()
    srv = node.da_serve
    rpc_host, rpc_port = node.rpc_addr
    stop = threading.Event()

    def producer():
        client = LocalClient(node.rpc_env)
        seq = 0
        while not stop.is_set():
            try:
                client.broadcast_tx_sync(
                    tx=f"pc{seq}={'y' * 64}".encode().hex())
            except Exception:  # noqa: BLE001 — pool full: back off
                stop.wait(0.05)
            seq += 1
            stop.wait(0.005)

    n_c = k_c + m_c

    def run_pc_fleet(height: int) -> dict:
        com = srv.pc_commitments(height)
        pc_root = com.root()
        confident = 0
        detected = 0
        parity_fail = 0
        samples_ok = 0
        samples_failed = 0
        client_bytes = []
        t0 = time.perf_counter()
        for i in range(clients):
            s = PCSampler(client_id=i, n_c=n_c, k_c=k_c, n_r=com.n_r,
                          confidence=0.99, seed=1)
            res = s.run(height, pc_root, com, srv.pc_sample)
            samples_ok += res.samples_ok
            samples_failed += res.samples_failed
            if res.confident:
                confident += 1
            if res.detected_withholding:
                detected += 1
            if not res.commitments_ok:
                parity_fail += 1
            if res.samples_ok:
                client_bytes.append(
                    (res.proof_bytes + res.commitment_bytes)
                    / res.samples_ok)
        dt = time.perf_counter() - t0
        total = samples_ok + samples_failed
        return {
            "height": height,
            "clients": clients,
            "clients_confident": confident,
            "clients_detected": detected,
            "clients_parity_fail": parity_fail,
            "samples": total,
            "samples_ok": samples_ok,
            "samples_per_sec": round(total / dt, 1) if dt else 0.0,
            # worst per-client average, commitment download INCLUDED
            "bytes_per_sample": (
                round(max(client_bytes), 1) if client_bytes else 0.0),
            "fleet_s": round(dt, 3),
        }

    t_prod = threading.Thread(target=producer, daemon=True)
    t_start = time.perf_counter()
    t_prod.start()

    honest_legs = []
    last_sampled = 0
    geom = None
    deadline = time.monotonic() + duration_s
    while time.monotonic() < deadline:
        st = srv.stats()
        h = st["max_height"]
        if h and h > last_sampled and srv.pc_commitments(h) is not None:
            leg = run_pc_fleet(h)
            honest_legs.append(leg)
            last_sampled = h
            geom = srv.pc_commitments(h)
        else:
            time.sleep(0.02)

    # wire leg: REAL HTTP da_pc_commitments + da_pc_sample roundtrips
    http_ok = 0
    http_errors = []
    wire_h = last_sampled
    wire_com = srv.pc_commitments(wire_h)
    for i in range(http_samples):
        try:
            cols = [(i + t) % n_c for t in range(3)]
            if _http_pc_sample(rpc_host, rpc_port, wire_h,
                               i % wire_com.n_r, cols,
                               wire_com.root(), wire_com):
                http_ok += 1
            else:
                http_errors.append(f"pc sample {i}: proof failed")
        except Exception as e:  # noqa: BLE001 — record, gate below
            http_errors.append(f"pc sample {i}: {e}")

    # adversarial leg 1: withhold m_c+1 columns (minimum that blocks
    # column reconstruction); clients re-probe per column, so failed
    # columns are attributed
    adv_h = last_sampled
    srv.set_pc_withholding(adv_h, range(m_c + 1))
    adv = run_pc_fleet(adv_h)
    adv["withheld_cols"] = m_c + 1
    srv.set_pc_withholding(adv_h, ())

    # header binding check BEFORE the lying-encoder leg mutates this
    # height's serve-side encoding: the stored header's da_root must be
    # the combined (1D, PC) root of what the node actually serves
    from cometbft_tpu.da.commit import combined_root
    header_root = node.block_store.load_block(adv_h).header.da_root
    root_binds = header_root == combined_root(
        srv.commitment(adv_h).root(), srv.pc_commitments(adv_h).root())

    # adversarial leg 2: the lying encoder — honest commitments over
    # garbage parity columns; every OPENING verifies, only the
    # parity-linearity check catches it (detection is deterministic,
    # not probabilistic: fraction must be 1.0)
    lie_h = last_sampled
    assert srv.corrupt_pc_parity(lie_h, seed=11)
    lie = run_pc_fleet(lie_h)

    # the same world on the 1D track: garbage parity shards under an
    # HONEST Merkle root. Every opening verifies and no sample can
    # tell — the fleet stays fully confident (the blindness the 2D
    # linearity check exists to fix).
    from cometbft_tpu.da.commit import commit_shards, split_payload
    payload = bytes(range(256)) * 8
    data_1d = split_payload(payload, 16)
    garbage = [bytes((b + 1) % 256 for b in s) for s in data_1d]
    shards_1d = data_1d + garbage
    com_1d, proofs_1d = commit_shards(shards_1d, 16, len(payload))
    blind_confident = 0
    for i in range(min(clients, 200)):
        res = Sampler(client_id=i, n=32, k=16, seed=1).run(
            1, com_1d.root(),
            lambda h, idx: (shards_1d[idx], proofs_1d[idx], com_1d))
        if res.confident:
            blind_confident += 1
    oneD_blind_fraction = blind_confident / min(clients, 200)

    stop.set()
    t_prod.join(timeout=5)
    t_load = time.perf_counter() - t_start
    stats = srv.stats()
    node.stop()
    shutil.rmtree(home, ignore_errors=True)

    openings = _bench_openings(k_r=33, n_cols=samples_per_draw(n_c),
                               iters=open_iters)

    agg = {
        "clients": clients,
        "heights_sampled": len(honest_legs),
        "clients_confident_min": min(
            (l["clients_confident"] for l in honest_legs), default=0),
        "samples_total": sum(l["samples"] for l in honest_legs),
        "samples_per_sec": round(
            sum(l["samples_per_sec"] for l in honest_legs)
            / max(1, len(honest_legs)), 1),
        # worst case across legs of the worst per-client average,
        # commitment-list download included — the honest accounting
        # the <256 B gate is asserted against
        "bytes_per_sample": max(
            (l["bytes_per_sample"] for l in honest_legs), default=0.0),
    }
    return {
        "metric": "das_pc_multiproof",
        "pc_data_cols": k_c,
        "pc_parity_cols": m_c,
        "grid_rows": geom.n_r if geom else 0,
        "duration_s": round(t_load, 2),
        "header_da_root": header_root.hex(),
        "header_root_binds_pc": root_binds,
        "honest": agg,
        "honest_legs": honest_legs[:3],
        "withholding": adv,
        "lying_encoder": lie,
        "oneD_blind_confident_fraction": round(oneD_blind_fraction, 3),
        "http_samples_ok": http_ok,
        "http_samples": http_samples,
        "http_errors": http_errors[:5],
        "blocks_encoded": stats["blocks_encoded"],
        "pc_skipped_rows": stats["pc_skipped_rows"],
        "pc_samples_served": stats["pc_samples_served"],
        "openings": openings,
        # the 1D record's per-sample bound this track undercuts
        "rs_proof_bytes_bound": 256,
    }


def samples_per_draw(n_c: int) -> int:
    """Columns per client draw at the default 99% target (clamped to
    the column count like PCSampler does)."""
    from cometbft_tpu.da.sampler import samples_for_confidence

    return min(n_c, samples_for_confidence(0.99, n_c, n_c // 2))


def _http_fetch(ep: str, height: int, index: int):
    """One da_sample against `ep`, parsed into the (chunk, proof, com)
    triple a Sampler's transport returns. None = the endpoint answered
    but has no sample (unknown height / withheld index); transport
    errors propagate so the caller can fail over."""
    import base64

    from cometbft_tpu.crypto import merkle
    from cometbft_tpu.da.commit import DACommitment

    url = f"http://{ep}/da_sample?height={height}&index={index}"
    try:
        with urllib.request.urlopen(url, timeout=10) as resp:
            body = json.loads(resp.read())
    except urllib.error.HTTPError as e:
        if e.code == 400:  # RPC-level error rides a 400 JSON body
            return None
        raise
    if "error" in body:
        return None
    r = body["result"]
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
    return bytes.fromhex(r["chunk"]), proof, com


def run_remote(endpoints: list[str], clients: int, duration_s: float,
               k: int, m: int) -> dict:
    """Multi-endpoint mode (--endpoints): sample an EXISTING serving
    fleet (replica processes) over real HTTP instead of booting a node.
    One /light_stream reader per endpoint discovers committed heights +
    their da_root (reconnecting with a `since` cursor on failure, gap-
    accounted); sampling clients pin to an endpoint round-robin and
    fail over to the next endpoint when the pinned one dies, counting
    per-client failovers."""
    from cometbft_tpu.da.sampler import Sampler

    n = k + m
    n_eps = len(endpoints)
    stop = threading.Event()
    cursors = [0] * n_eps
    gaps = [0] * n_eps
    dups = [0] * n_eps
    failovers = [0] * n_eps
    connects = [0] * n_eps
    roots: dict[int, bytes] = {}
    roots_lock = threading.Lock()
    errors: list[str] = []

    def reader(g: int):
        order = endpoints[g:] + endpoints[:g]
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
                        if "da_root" in p:
                            with roots_lock:
                                roots[h] = bytes.fromhex(p["da_root"])
            except Exception as e:  # noqa: BLE001 — endpoint died
                if stop.is_set():
                    return
                idx += 1
                failovers[g] += 1
                if len(errors) < 5:
                    errors.append(f"reader {g} @ {ep}: {e}")
                stop.wait(0.2)

    fleet = [Sampler(client_id=i, n=n, k=k, confidence=0.99, seed=1)
             for i in range(clients)]
    client_failovers = [0] * clients

    def make_fetch(i: int):
        def fetch(height: int, index: int):
            for attempt in range(n_eps):
                ep = endpoints[(i + attempt) % n_eps]
                try:
                    return _http_fetch(ep, height, index)
                except Exception:  # noqa: BLE001 — fail over
                    if attempt == 0:
                        client_failovers[i] += 1
                    continue
            return None
        return fetch

    fetchers = [make_fetch(i) for i in range(clients)]
    readers = [threading.Thread(target=reader, args=(g,), daemon=True)
               for g in range(n_eps)]
    t_start = time.perf_counter()
    for t in readers:
        t.start()

    legs = []
    last_sampled = 0
    deadline = time.monotonic() + duration_s
    while time.monotonic() < deadline:
        with roots_lock:
            fresh = max(roots, default=0)
            root = roots.get(fresh)
        if not fresh or fresh <= last_sampled:
            time.sleep(0.02)
            continue
        confident = 0
        samples_ok = samples_failed = 0
        t0 = time.perf_counter()
        for i, s in enumerate(fleet):
            res = s.run(fresh, root, fetchers[i])
            samples_ok += res.samples_ok
            samples_failed += res.samples_failed
            if res.confident:
                confident += 1
        dt = time.perf_counter() - t0
        total = samples_ok + samples_failed
        legs.append({
            "height": fresh,
            "clients_confident": confident,
            "samples": total,
            "samples_ok": samples_ok,
            "samples_per_sec": round(total / dt, 1) if dt else 0.0,
        })
        last_sampled = fresh

    stop.set()
    for t in readers:
        t.join(timeout=5)
    t_load = time.perf_counter() - t_start

    return {
        "metric": "das_sampling_remote",
        "endpoints": endpoints,
        "clients": clients,
        "data_shards": k,
        "parity_shards": m,
        "duration_s": round(t_load, 2),
        "heights_sampled": len(legs),
        "clients_confident_min": min(
            (leg["clients_confident"] for leg in legs), default=0),
        "samples_total": sum(leg["samples"] for leg in legs),
        "samples_ok": sum(leg["samples_ok"] for leg in legs),
        "legs": legs[:3],
        "stream_gaps": sum(gaps),
        "stream_dups": sum(dups),
        "stream_failovers": sum(failovers),
        "stream_connects": sum(connects),
        "client_failovers": sum(client_failovers),
        "errors": errors,
    }


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--clients", type=int, default=1000,
                    help="sampling clients per committed block")
    ap.add_argument("--duration", type=float, default=8.0)
    ap.add_argument("--data-shards", type=int, default=16)
    ap.add_argument("--parity-shards", type=int, default=16)
    ap.add_argument("--http-samples", type=int, default=8,
                    help="real HTTP da_sample fetches")
    ap.add_argument("--codec-mb", type=float, default=4.0,
                    help="payload MB for the native-vs-oracle encode leg")
    ap.add_argument("--endpoints", default="",
                    help="comma-separated host:port serving endpoints "
                         "(replica fleet); skips booting a node")
    ap.add_argument("--pc", action="store_true",
                    help="2D polynomial-commitment track: KZG "
                         "multiproof fleet instead of the 1D RS one")
    ap.add_argument("--pc-data-cols", type=int, default=4)
    ap.add_argument("--pc-parity-cols", type=int, default=4)
    ap.add_argument("--open-iters", type=int, default=10,
                    help="iterations for the native opening bench")
    args = ap.parse_args()
    if args.pc:
        res = run_pc(args.clients, args.duration, args.pc_data_cols,
                     args.pc_parity_cols, args.http_samples,
                     args.open_iters)
    elif args.endpoints:
        eps = [e.strip() for e in args.endpoints.split(",") if e.strip()]
        res = run_remote(eps, args.clients, args.duration,
                         args.data_shards, args.parity_shards)
    else:
        res = run(args.clients, args.duration, args.data_shards,
                  args.parity_shards, args.http_samples, args.codec_mb)
    print(json.dumps(res))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
