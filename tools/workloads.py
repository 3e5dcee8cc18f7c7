"""Consensus-path workload benchmarks -> WORKLOADS.json.

Three production shapes (SURVEY §3.3 / BASELINE configs):
  1. verify_commit_p50_150v — one Cosmos-Hub-sized commit through
     types.validation.verify_commit with the default backend dispatch
     (commit-sized batches route to the native C++ RLC engine).
  2. light_stream_1000h_150v — light-client verify_stream over 1000
     contiguous headers (one signature mega-batch).
  3. replay_500b_100v — block-store replay of 500 blocks through the
     batched ReplayEngine (blocksync's consumption shape).

Run: python tools/workloads.py [--quick]
Each metric prints one JSON line; all are written to WORKLOADS.json.

Separate flags run the heavier subsystem workloads on their own:
--ingest, --light (10k-subscriber /light_stream fan-out), --bls
(aggregate-signature certificate track), --das (data-availability
sampling fleet + withholding leg), --das --pc (the 2D
polynomial-commitment DAS track: KZG multiproof fleet, lying-encoder
and 1D-blindness legs, native MSM opening bench), --certnative
(certificate-native
wire/store/feed byte gates + one-pairing replay vs the
fold-after-the-fact column baseline), --city (four concurrent legs),
--city --replicas N (the scale-out serving plane: N stateless replica
processes carry the fleets, with snapshot-bootstrap and
kill-one-replica failover legs), --multichip, --two-backend.
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

QUICK = "--quick" in sys.argv


def _best_of(timed_fn, reps=3):
    """(min_seconds, stat_label) over `reps` runs of timed_fn (1 when
    --quick). timed_fn returns the duration of exactly the region it
    measured — setup and assertions stay outside the clock, keeping the
    measurement boundary identical to earlier rounds.

    Single samples of a device round trip on a shared host swing
    (unmeasured on today's machine); the minimum is the stable
    estimator of steady-state capability. Every record carries the returned "stat" label so
    cross-round comparisons know what they are comparing.
    """
    n = reps if not QUICK else 1
    best = None
    for _ in range(n):
        d = timed_fn()
        best = d if best is None else min(best, d)
    return best, f"best_of_{n}"


def _signed_chain(n_blocks, n_vals):
    from cometbft_tpu.utils import factories as fx

    return fx.make_chain(
        n_blocks, n_validators=n_vals, chain_id="bench-chain", backend="cpu"
    )


def bench_verify_commit(n_vals=150, reps=31):
    from cometbft_tpu.types.block import block_id_for
    from cometbft_tpu.types.validation import verify_commit

    store, state, genesis, _ = _signed_chain(3, n_vals)
    blk = store.load_block(3)
    commit = store.load_block_commit(3) or store.load_seen_commit(3)
    vals = state.validators
    block_id = commit.block_id
    chain_id = state.chain_id
    times = []
    for _ in range(3):  # warmup (library load, table init)
        verify_commit(chain_id, vals, block_id, 3, commit)
    for _ in range(reps if not QUICK else 5):
        t0 = time.perf_counter()
        verify_commit(chain_id, vals, block_id, 3, commit)
        times.append(time.perf_counter() - t0)
    times.sort()
    p50 = times[len(times) // 2]
    return {
        "metric": f"verify_commit_p50_{n_vals}v",
        "value": round(p50 * 1e3, 3),
        "unit": "ms",
        "stat": f"p50_of_{len(times)}",
        "sigs_per_sec": round(n_vals / p50, 1),
    }


def bench_light_stream(n_headers=1000, n_vals=150):
    from cometbft_tpu.light.client import StoreProvider
    from cometbft_tpu.light.verifier import verify_stream
    from cometbft_tpu.state.types import encode_validator_set
    from cometbft_tpu.storage import MemKV, StateStore
    from cometbft_tpu.types import Timestamp

    if QUICK:
        n_headers = 100
    store, state, genesis, _ = _signed_chain(n_headers + 1, n_vals)
    ss = StateStore(MemKV())
    for h in range(1, n_headers + 2):
        ss._db.set(
            b"SV:" + h.to_bytes(8, "big"),
            encode_validator_set(state.validators),
        )
    p = StoreProvider(state.chain_id, store, ss)
    trusted = p.light_block(1)
    stream = [p.light_block(h) for h in range(2, n_headers + 2)]
    now = Timestamp.from_unix_ns(1_700_009_000 * 10**9)
    # steady-state measurement: a long-running light client traces +
    # compiles each kernel bucket once per process, not per stream
    verify_stream(state.chain_id, trusted, stream, 10**9, now)

    def timed():
        t0 = time.perf_counter()
        verify_stream(state.chain_id, trusted, stream, 10**9, now)
        return time.perf_counter() - t0

    dt, stat = _best_of(timed)
    sigs = len(stream) * n_vals
    return {
        "metric": f"light_stream_{n_headers}h_{n_vals}v",
        "value": round(dt, 3),
        "unit": "s",
        "stat": stat,
        "headers_per_sec": round(len(stream) / dt, 1),
        "sigs_per_sec": round(sigs / dt, 1),
    }


def bench_replay(n_blocks=500, n_vals=100):
    from cometbft_tpu.abci.client import AppConns
    from cometbft_tpu.abci.kvstore import KVStoreApp
    from cometbft_tpu.blocksync import ReplayEngine
    from cometbft_tpu.state.execution import BlockExecutor

    if QUICK:
        n_blocks = 50
    store, final_state, genesis, _ = _signed_chain(n_blocks, n_vals)
    # steady-state: trace/compile the replay window's kernel bucket once
    # (a syncing node replays far more than one 500-block span)
    warm = ReplayEngine(
        store, BlockExecutor(AppConns(KVStoreApp())),
        verify_mode="batched", window=128,
    )
    warm.run(genesis.copy())
    results = {}

    def one_run():
        executor = BlockExecutor(AppConns(KVStoreApp()))
        engine = ReplayEngine(store, executor, verify_mode="batched", window=128)
        start = genesis.copy()
        t0 = time.perf_counter()
        state, stats = engine.run(start)
        d = time.perf_counter() - t0
        assert state.last_block_height == n_blocks
        assert state.app_hash == final_state.app_hash
        results["stats"] = stats
        return d

    dt, stat = _best_of(one_run)
    stats = results["stats"]
    return {
        "metric": f"replay_{n_blocks}b_{n_vals}v",
        "value": round(dt, 3),
        "unit": "s",
        "stat": stat,
        "blocks_per_sec": round(n_blocks / dt, 1),
        "sigs_per_sec": round(stats.sigs_verified / dt, 1),
    }


def bench_replay_northstar(n_blocks=50_000, n_vals=1000, chunk=500,
                           store_dir="/tmp/ns_chain"):
    """BASELINE config #4: block-sync replay of 50k blocks @ 1000
    validators. The chain generates ONCE into an on-disk sqlite store
    (chunked, bounded memory, ~75 min — generation is NOT part of the
    measurement and a populated store is reused on rerun); the measured
    region is a single ReplayEngine pass over the full store — 50M
    signatures and real store-growth read patterns."""
    from cometbft_tpu.abci.client import AppConns
    from cometbft_tpu.abci.kvstore import KVStoreApp
    from cometbft_tpu.blocksync import ReplayEngine
    from cometbft_tpu.state.execution import BlockExecutor, make_genesis_state
    from cometbft_tpu.storage import BlockStore, open_kv
    from cometbft_tpu.utils import factories as fx

    if QUICK:
        n_blocks, chunk = 2000, 500
    os.makedirs(store_dir, exist_ok=True)
    db_path = os.path.join(store_dir, f"blockstore_{n_blocks}b_{n_vals}v.db")
    store = BlockStore(open_kv(db_path))
    signers = fx.make_signers(n_vals)
    vals = fx.make_validator_set(signers)
    genesis = make_genesis_state("ns-chain", vals)
    if store.height() < n_blocks:
        app = KVStoreApp()
        pool = fx.RPool(n_vals, blocks_per_fill=32)
        state, last_commit = None, None
        if store.height():
            # resume is not supported mid-chain (app state not
            # persisted); start fresh
            raise SystemExit(
                f"partial store at {store.height()}; delete {db_path}"
            )
        t0 = time.perf_counter()
        h = 1
        while h <= n_blocks:
            n = min(chunk, n_blocks - h + 1)
            _, state, _, _ = fx.make_chain(
                n, n_validators=n_vals, chain_id="ns-chain", app=app,
                block_store=store, verify_last_commit=False, r_pool=pool,
                start_state=state, start_commit=last_commit, start_height=h,
            )
            h += n
            last_commit = store.load_seen_commit(h - 1)
            el = time.perf_counter() - t0
            print(f"  generated {h-1}/{n_blocks} blocks "
                  f"({(h-1)/el:.1f} blk/s)", file=sys.stderr)
        # persist the expected final app hash for verification on reruns
        with open(db_path + ".apphash", "w") as f:
            f.write(state.app_hash.hex())
    with open(db_path + ".apphash") as f:
        want_app_hash = bytes.fromhex(f.read().strip())

    executor = BlockExecutor(AppConns(KVStoreApp()))
    engine = ReplayEngine(store, executor, verify_mode="batched", window=128)
    t0 = time.perf_counter()
    state, stats = engine.run(genesis.copy())
    dt = time.perf_counter() - t0
    assert state.last_block_height == n_blocks
    assert state.app_hash == want_app_hash, "replay must reproduce app hash"
    return {
        "metric": f"replay_{n_blocks}b_{n_vals}v",
        "value": round(dt, 1),
        "unit": "s",
        "stat": "single_run",
        "blocks_per_sec": round(n_blocks / dt, 1),
        "sigs_per_sec": round(stats.sigs_verified / dt, 1),
        "sigs_verified": stats.sigs_verified,
    }


def bench_megacommit_mixed(n_vals=10_000, n_sr=1000, n_secp=500, reps=5):
    """BASELINE config #5: one 10k-validator mega-commit with mixed key
    types (ed25519 majority + sr25519 + secp256k1) through verify_commit
    — the multi-curve partition dispatch at full scale."""
    from cometbft_tpu.crypto.secp256k1 import Secp256k1PrivKey
    from cometbft_tpu.crypto.sr25519 import Sr25519PrivKey
    from cometbft_tpu.types import (
        BlockID, BlockIDFlag, Commit, CommitSig, PartSetHeader, Timestamp,
    )
    from cometbft_tpu.types.validation import verify_commit
    from cometbft_tpu.types.validator_set import Validator, ValidatorSet
    from cometbft_tpu.types.vote import SignedMsgType, Vote
    from cometbft_tpu.utils import factories as fx

    if QUICK:
        n_vals, n_sr, n_secp = 1000, 100, 50
    n_ed = n_vals - n_sr - n_secp
    ed_signers = fx.make_signers(n_ed)
    sr_privs = [Sr25519PrivKey(bytes([1 + (i % 250)]) * 31 + bytes([i // 250]))
                for i in range(n_sr)]
    secp_privs = [Secp256k1PrivKey.from_secret(b"megacommit-%d" % i)
                  for i in range(n_secp)]

    vals_list = [Validator.from_pub_key(s.pub_key(), 10) for s in ed_signers]
    vals_list += [Validator.from_pub_key(p.pub_key(), 10)
                  for p in sr_privs + secp_privs]
    vals = ValidatorSet(vals_list)
    bid = BlockID(b"\xaa" * 32, PartSetHeader(1, b"\xbb" * 32))
    chain_id = "mega-mixed"
    height = 9

    ed_by_addr = {s.address(): s for s in ed_signers}
    other_by_addr = {p.pub_key().address(): p for p in sr_privs + secp_privs}
    commit = Commit(height=height, round=0, block_id=bid, signatures=[])
    ts = Timestamp(1_700_000_000, 0)
    for val in vals.validators:
        commit.signatures.append(
            CommitSig(BlockIDFlag.COMMIT, val.address, ts, b""))
    ed_idx, ed_msgs = [], []
    for idx, val in enumerate(vals.validators):
        sb = commit.vote_sign_bytes(chain_id, idx)
        if val.address in ed_by_addr:
            ed_idx.append(idx)
            ed_msgs.append(sb)
        else:
            commit.signatures[idx].signature = \
                other_by_addr[val.address].sign(sb)
    ed_sigs = fx.batch_sign(
        [ed_by_addr[vals.validators[i].address] for i in ed_idx], ed_msgs)
    for i, sig in zip(ed_idx, ed_sigs):
        commit.signatures[i].signature = sig
    commit.invalidate_memos()

    from cometbft_tpu.utils.metrics import crypto_metrics

    def _curve_sums():
        # verify_seconds carries ("path", "curve") labels; fold paths
        return_by_curve: dict[str, float] = {}
        for key, agg in crypto_metrics().verify_seconds.snapshot().items():
            curve = key[1] if len(key) > 1 else "unknown"
            return_by_curve[curve] = return_by_curve.get(curve, 0.0) + agg["sum"]
        return return_by_curve

    verify_commit(chain_id, vals, bid, height, commit)  # warmup/compile
    times = []
    shares = []
    for _ in range(reps if not QUICK else 2):
        before = _curve_sums()
        t0 = time.perf_counter()
        verify_commit(chain_id, vals, bid, height, commit)
        times.append(time.perf_counter() - t0)
        after = _curve_sums()
        shares.append({c: after.get(c, 0.0) - before.get(c, 0.0)
                       for c in after})
    best = min(range(len(times)), key=times.__getitem__)
    dt = times[best]
    rec = {
        "metric": f"megacommit_mixed_{n_vals}v",
        "value": round(dt * 1e3, 1),
        "unit": "ms",
        "stat": f"best_of_{len(times)}",
        "curves": {"ed25519": n_ed, "sr25519": n_sr, "secp256k1": n_secp},
        "curve_shares_ms": {c: round(s * 1e3, 1)
                            for c, s in sorted(shares[best].items())},
        "sigs_per_sec": round(n_vals / dt, 1),
    }
    if not QUICK:
        # the round-7 bars (PROFILE.md): total <= 2.2 s, and neither
        # non-ed curve above 100 ms — machine-checked so a regression
        # fails the bench instead of silently rewriting the record
        assert dt <= 2.2, f"megacommit regression: {dt*1e3:.0f} ms > 2200 ms"
        for c in ("sr25519", "secp256k1"):
            share = shares[best].get(c, 0.0)
            assert share <= 0.100, \
                f"{c} share regression: {share*1e3:.0f} ms > 100 ms"
    return rec


def bench_megacommit_bls(sizes=(150, 1500, 10_000)):
    """ISSUE 13 / ROADMAP item #2: the honest ed25519-vs-BLS crossover
    (arXiv:2302.00418 reproduced on this codebase). For each validator
    count the SAME uniform-timestamp commit shape is verified twice —
    once with ed25519 keys (native batch verify), once with BLS keys
    (partition dispatch collapses the whole signature column into ONE
    product-of-pairings check) — and the byte story rides along: the
    ed25519 wire commit vs the BLS wire commit (96 B sigs: BIGGER) vs
    the folded AggregateCommit certificate (one 96 B sig + bitmap).

    The per-slot-signature BLS commit is G2-DECODE-bound (~0.5 ms per
    96 B signature for decompress + subgroup), so it never crosses
    native ed25519; the crossover and the latency gate are therefore
    defined on the certificate path (constant one-pairing cost after
    the commit is folded once at aggregation time), which is what a
    BLS chain actually gossips — exactly the arXiv:2302.00418 framing.

    Latency gates follow the skipped-with-reason convention: on a
    starved host the two legs time-share one core with the harness, so
    pass/fail would gate on scheduler interleaving. The byte ratios and
    the one-pairing-check invariant are deterministic and assert
    everywhere."""
    from cometbft_tpu.crypto import bls
    from cometbft_tpu.types import (
        BlockID, BlockIDFlag, Commit, CommitSig, PartSetHeader, Timestamp,
    )
    from cometbft_tpu.types.agg_commit import AggregateCommit
    from cometbft_tpu.types.validation import verify_commit
    from cometbft_tpu.types.validator_set import Validator, ValidatorSet
    from cometbft_tpu.types.vote import SignedMsgType, canonical_vote_bytes
    from cometbft_tpu.utils import factories as fx

    if QUICK:
        sizes = (50, 150, 500)
    bid = BlockID(b"\xcc" * 32, PartSetHeader(1, b"\xdd" * 32))
    chain_id = "mega-bls"
    height = 11
    ts = Timestamp(1_700_000_000, 0)
    msg = canonical_vote_bytes(
        SignedMsgType.PRECOMMIT, height, 0, bid, ts, chain_id)

    def build_commit(vals, sign_fn):
        commit = Commit(height=height, round=0, block_id=bid, signatures=[])
        for val in vals.validators:
            commit.signatures.append(
                CommitSig(BlockIDFlag.COMMIT, val.address, ts,
                          sign_fn(val.address)))
        commit.invalidate_memos()
        return commit

    def timed_verify(vals, commit, reps):
        verify_commit(chain_id, vals, bid, height, commit)  # warmup/caches
        times = []
        for _ in range(reps):
            t0 = time.perf_counter()
            verify_commit(chain_id, vals, bid, height, commit)
            times.append(time.perf_counter() - t0)
        return min(times)

    points = {}
    for n in sizes:
        reps = 3 if n >= 5000 else (2 if QUICK else 5)
        # --- ed25519 leg: the wire-bound incumbent -----------------
        ed_signers = fx.make_signers(n)
        ed_vals = ValidatorSet(
            [Validator.from_pub_key(s.pub_key(), 10) for s in ed_signers])
        ed_by_addr = {s.address(): s for s in ed_signers}
        ed_sigs = fx.batch_sign(ed_signers, [msg] * n)
        ed_sig_by_addr = dict(zip(ed_by_addr.keys(), ed_sigs))
        ed_commit = build_commit(ed_vals, ed_sig_by_addr.__getitem__)
        ed_ms = timed_verify(ed_vals, ed_commit, reps) * 1e3
        # --- BLS leg: one pairing check --------------------------------
        bls_privs = [bls.BlsPrivKey.from_secret(b"mega-bls-%d" % i)
                     for i in range(n)]
        bls_vals = ValidatorSet(
            [Validator.from_pub_key(k.pub_key(), 10) for k in bls_privs])
        bls_sig_by_addr = {k.pub_key().address(): k.sign(msg)
                           for k in bls_privs}
        bls_commit = build_commit(bls_vals, bls_sig_by_addr.__getitem__)
        pc0 = bls.pairing_checks()
        bls_ms = timed_verify(bls_vals, bls_commit, reps) * 1e3
        per_call = (bls.pairing_checks() - pc0) // (reps + 1)
        assert per_call == 1, (
            f"all-BLS {n}v commit took {per_call} pairing checks, want 1")
        # --- the folded certificate ------------------------------------
        cert = AggregateCommit.from_commit(bls_commit)
        cert.verify(chain_id, bls_vals)  # warmup
        cts = []
        for _ in range(reps):
            t0 = time.perf_counter()
            cert.verify(chain_id, bls_vals)
            cts.append(time.perf_counter() - t0)
        points[str(n)] = {
            "ed25519_verify_ms": round(ed_ms, 2),
            "bls_verify_ms": round(bls_ms, 2),
            "bls_cert_verify_ms": round(min(cts) * 1e3, 2),
            "bls_speedup": round(ed_ms / bls_ms, 2),
            "cert_speedup": round(ed_ms / (min(cts) * 1e3), 2),
            "ed25519_commit_bytes": len(ed_commit.encode()),
            "bls_commit_bytes": len(bls_commit.encode()),
            "bls_cert_bytes": cert.wire_size(),
            "pairing_checks_per_verify": per_call,
        }
        p = points[str(n)]
        p["cert_bytes_ratio"] = round(
            p["ed25519_commit_bytes"] / p["bls_cert_bytes"], 1)
        print(f"  {n}v: ed25519 {p['ed25519_verify_ms']} ms / "
              f"{p['ed25519_commit_bytes']} B  vs  BLS "
              f"{p['bls_verify_ms']} ms (cert {p['bls_cert_verify_ms']} ms"
              f" / {p['bls_cert_bytes']} B, {p['cert_bytes_ratio']}x "
              f"smaller)", file=sys.stderr)
    # crossover: smallest measured size where the folded certificate
    # beats the ed25519 batch engine
    crossover = next(
        (int(n) for n, p in sorted(points.items(), key=lambda kv: int(kv[0]))
         if p["bls_cert_verify_ms"] < p["ed25519_verify_ms"]), None)
    largest = points[str(max(sizes))]
    gate = {
        "pairing_checks_per_verify": 1,
        "min_cert_bytes_ratio": 20.0,
        "cert_wins_at_largest": True,
    }
    # deterministic byte gate: asserts everywhere
    for n, p in points.items():
        assert p["cert_bytes_ratio"] >= gate["min_cert_bytes_ratio"], (
            f"{n}v certificate only {p['cert_bytes_ratio']}x smaller than "
            f"the ed25519 commit (< {gate['min_cert_bytes_ratio']}x)")
    cores = os.cpu_count() or 1
    if cores < 2:
        gate["asserted"] = False
        gate["reason"] = (
            f"starved host: {cores} core(s) — the pooled pubkey "
            "aggregation and the ed25519 batch engine time-share the "
            "core, so the latency crossover would gate on scheduler "
            "interleaving; byte ratios and the one-pairing-check "
            "invariant asserted anyway. Re-run `python tools/workloads.py "
            "--bls` on a >=2-core host"
        )
    else:
        gate["asserted"] = True
        assert largest["bls_cert_verify_ms"] < largest["ed25519_verify_ms"], (
            f"BLS certificate verify {largest['bls_cert_verify_ms']} ms did "
            f"not beat ed25519 {largest['ed25519_verify_ms']} ms at "
            f"{max(sizes)}v")
    return {
        "metric": f"megacommit_bls_{max(sizes)}v",
        "value": largest["bls_cert_verify_ms"],
        "unit": "ms",
        "stat": "best_of_3" if max(sizes) >= 5000 else "best_of_5",
        "points": points,
        "crossover_validators": crossover,
        "gate": gate,
    }


def _bls_chain(n_blocks, n_vals, cert_native, privs, chain_id):
    """A fully-signed all-BLS chain through the real executor. With
    cert_native the embedded/stored LastCommit is the folded CertCommit
    (what a cert-native net produces, ISSUE 17); without it the full
    signature column rides the blocks — the fold-after-the-fact
    baseline the replay delta is measured against. Precommit timestamps
    are uniform per height in BOTH chains (the cert-native nets' PBTS
    behavior), so the byte and verify deltas isolate the commit format.
    """
    from cometbft_tpu.abci.client import AppConns
    from cometbft_tpu.abci.kvstore import KVStoreApp
    from cometbft_tpu.state.execution import BlockExecutor, make_genesis_state
    from cometbft_tpu.storage import BlockStore, MemKV
    from cometbft_tpu.types import BlockIDFlag, Commit, CommitSig, Timestamp
    from cometbft_tpu.types.agg_commit import fold_commit
    from cometbft_tpu.types.block import block_id_for
    from cometbft_tpu.types.validator_set import Validator, ValidatorSet
    from cometbft_tpu.types.vote import SignedMsgType, canonical_vote_bytes

    vals = ValidatorSet(
        [Validator.from_pub_key(k.pub_key(), 10) for k in privs])
    by_addr = {k.pub_key().address(): k for k in privs}
    db = MemKV()
    store = BlockStore(db)
    executor = BlockExecutor(AppConns(KVStoreApp()))
    genesis = make_genesis_state(chain_id, vals)
    state = genesis.copy()
    last_commit = Commit()
    for h in range(1, n_blocks + 1):
        txs = [b"k%d-%d=v%d" % (h, i, i) for i in range(2)]
        proposer = state.validators.get_proposer()
        block = executor.create_proposal_block(
            h, state, last_commit, proposer.address, txs,
            block_time=state.last_block_time,
        )
        bid = block_id_for(block)
        vals_h = state.validators
        state = executor.apply_block(
            state, bid, block, last_commit_preverified=True)
        ts = Timestamp.from_unix_ns(
            state.last_block_time.unix_ns() + 1_000_000_000)
        msg = canonical_vote_bytes(
            SignedMsgType.PRECOMMIT, h, 0, bid, ts, chain_id)
        commit = Commit(height=h, round=0, block_id=bid, signatures=[])
        for val in vals_h.validators:
            commit.signatures.append(
                CommitSig(BlockIDFlag.COMMIT, val.address, ts,
                          by_addr[val.address].sign(msg)))
        commit.invalidate_memos()
        if cert_native:
            commit = fold_commit(commit, vals_h)
            assert getattr(commit, "cert", None) is not None, (
                "uniform-timestamp all-BLS commit failed to fold")
        store.save_block(block, commit)
        last_commit = commit
    return store, db, state, genesis, vals


def bench_certnative(n_vals=10_000, n_blocks=4):
    """ISSUE 17: certificate-native consensus, measured end to end on
    the same chain twice — once with the full BLS signature column as
    the commit (fold-after-the-fact baseline: every replayed block
    G2-decodes N signatures before the one pairing), once with the
    folded CertCommit as the canonical commit everywhere (wire, block
    store, replication feed; one 96 B aggregate + bitmap per height).

    Deterministic gates assert on EVERY machine: the wire and store
    byte ratios (>= 50x at every measured size), the cert-vs-column
    verdict pins (accept AND both reject classes must agree), and the
    one-pairing-per-certificate replay invariant. The replay throughput
    delta follows the skipped-with-reason convention on a starved host.
    """
    from cometbft_tpu.abci.client import AppConns
    from cometbft_tpu.abci.kvstore import KVStoreApp
    from cometbft_tpu.blocksync import ReplayEngine
    from cometbft_tpu.crypto import bls
    from cometbft_tpu.replication.feed import ReplicationFeed
    from cometbft_tpu.state.execution import BlockExecutor
    from cometbft_tpu.types import (
        BlockID, BlockIDFlag, Commit, CommitSig, PartSetHeader, Timestamp,
    )
    from cometbft_tpu.types.agg_commit import AggregateCommit, CertCommit
    from cometbft_tpu.types.validation import verify_commit
    from cometbft_tpu.types.validator_set import Validator, ValidatorSet
    from cometbft_tpu.types.vote import SignedMsgType, canonical_vote_bytes

    if QUICK:
        n_vals, n_blocks = 300, 3
    chain_id = "certnative-chain"
    privs = [bls.BlsPrivKey.from_secret(b"certnative-%d" % i)
             for i in range(n_vals)]
    print(f"  generating {n_blocks}-block column + cert chains at "
          f"{n_vals}v ...", file=sys.stderr)
    col_store, col_db, col_state, genesis, vals = _bls_chain(
        n_blocks, n_vals, False, privs, chain_id)
    cert_store, cert_db, cert_state, _, _ = _bls_chain(
        n_blocks, n_vals, True, privs, chain_id)

    # --- wire bytes per commit (the block-embedded LastCommit) ---------
    col_commit = col_store.load_block(n_blocks).last_commit
    cert_commit = cert_store.load_block(n_blocks).last_commit
    wire = {
        "column_commit_bytes": len(col_commit.encode()),
        "cert_commit_bytes": len(cert_commit.encode()),
    }
    wire["bytes_ratio"] = round(
        wire["column_commit_bytes"] / wire["cert_commit_bytes"], 1)

    # --- store bytes per block (total KV footprint / heights) ----------
    def kv_bytes(db):
        return sum(len(k) + len(v) for k, v in db.iterate_prefix(b""))

    stor = {
        "column_bytes_per_block": kv_bytes(col_db) // n_blocks,
        "cert_bytes_per_block": kv_bytes(cert_db) // n_blocks,
    }
    stor["bytes_ratio"] = round(
        stor["column_bytes_per_block"] / stor["cert_bytes_per_block"], 1)

    # --- replication feed bytes per height -----------------------------
    class _Vals:
        def load_validators(self, h):
            return vals

    feed = {}
    for label, store in (("column", col_store), ("cert", cert_store)):
        f = ReplicationFeed(chain_id, store, _Vals())
        feed[f"{label}_frame_bytes"] = len(
            f._build_frame(store.load_block(n_blocks)))
    feed["saving_pct"] = round(
        100.0 * (1 - feed["cert_frame_bytes"] / feed["column_frame_bytes"]),
        1)
    # the frame also carries the valset (dominates at scale), so the
    # gate here is direction, not a ratio: cert frames must be smaller
    assert feed["cert_frame_bytes"] < feed["column_frame_bytes"], feed

    # --- replay: fold-after-the-fact column vs certificate path --------
    replay = {}
    for label, store, want in (("column", col_store, col_state),
                               ("cert", cert_store, cert_state)):
        engine = ReplayEngine(
            store, BlockExecutor(AppConns(KVStoreApp())),
            verify_mode="batched", window=64)
        pc0 = bls.pairing_checks()
        t0 = time.perf_counter()
        state, stats = engine.run(genesis.copy())
        dt = time.perf_counter() - t0
        assert state.last_block_height == n_blocks
        assert state.app_hash == want.app_hash
        replay[f"{label}_s"] = round(dt, 3)
        replay[f"{label}_sigs_per_sec"] = round(stats.sigs_verified / dt, 1)
        if label == "cert":
            # one pairing per replayed certificate, nothing else: a
            # commit per height (blocks 2..n carry 1..n-1, the tip's
            # seen commit covers height n)
            replay["pairing_checks"] = bls.pairing_checks() - pc0
            assert replay["pairing_checks"] == n_blocks, (
                f"cert replay took {replay['pairing_checks']} pairing "
                f"checks for {n_blocks} certificates")
    replay["speedup"] = round(replay["column_s"] / replay["cert_s"], 2)
    # both replays committed identical app state: the formats are
    # different encodings of the same chain, not different chains
    assert col_state.app_hash == cert_state.app_hash

    # --- differential verdict pins: cert and column must agree ---------
    nv = min(n_vals, 100)
    bid = BlockID(b"\xaa" * 32, PartSetHeader(1, b"\xbb" * 32))
    ts = Timestamp(1_700_000_000, 0)
    height = 7
    vvals = ValidatorSet(
        [Validator.from_pub_key(k.pub_key(), 10) for k in privs[:nv]])
    by_addr = {k.pub_key().address(): k for k in privs[:nv]}
    # commit slots follow the set's canonical validator order
    vprivs = [by_addr[v.address] for v in vvals.validators]
    msg = canonical_vote_bytes(
        SignedMsgType.PRECOMMIT, height, 0, bid, ts, chain_id)

    def column_of(absent=(), corrupt=None):
        c = Commit(height=height, round=0, block_id=bid, signatures=[])
        for i, k in enumerate(vprivs):
            if i in absent:
                c.signatures.append(CommitSig.absent())
                continue
            sig = k.sign(msg)
            if i == corrupt:
                sig = bytes([sig[0] ^ 0xFF]) + sig[1:]
            c.signatures.append(
                CommitSig(BlockIDFlag.COMMIT, k.pub_key().address(), ts, sig))
        c.invalidate_memos()
        return c

    def verdict(commit):
        try:
            verify_commit(chain_id, vvals, bid, height, commit)
            return "accept"
        except Exception as e:  # noqa: BLE001 — the class IS the verdict
            return type(e).__name__

    full = column_of()
    # 2/3 of slots signing is exactly AT threshold — one vote short
    short = column_of(absent=range(2 * nv // 3, nv))
    folded = CertCommit.from_commit(full)
    c = folded.cert
    bad_cert = CertCommit(
        AggregateCommit(c.height, c.round, c.block_id, c.timestamp,
                        c.bitmap,
                        bytes([c.agg_sig[0] ^ 0xFF]) + c.agg_sig[1:]),
        folded.size_)
    verdicts = {
        "accept": [verdict(full), verdict(folded)],
        "power": [verdict(short), verdict(CertCommit.from_commit(short))],
        "badsig": [verdict(column_of(corrupt=3)), verdict(bad_cert)],
    }
    verdicts["mismatches"] = sum(
        1 for pair in (verdicts["accept"], verdicts["power"],
                       verdicts["badsig"]) if pair[0] != pair[1])

    gate = {
        "min_wire_bytes_ratio": 50.0,
        "min_store_bytes_ratio": 50.0,
        "verdict_mismatches": 0,
        "pairing_checks_per_cert": 1,
    }
    # machine-independent gates: assert everywhere, no skip path
    assert wire["bytes_ratio"] >= gate["min_wire_bytes_ratio"], (
        f"wire commit only {wire['bytes_ratio']}x smaller "
        f"(< {gate['min_wire_bytes_ratio']}x) at {n_vals}v")
    assert stor["bytes_ratio"] >= gate["min_store_bytes_ratio"], (
        f"store only {stor['bytes_ratio']}x smaller per block "
        f"(< {gate['min_store_bytes_ratio']}x) at {n_vals}v")
    assert verdicts["accept"] == ["accept", "accept"], verdicts
    assert verdicts["mismatches"] == 0, (
        f"cert and column verdicts diverge: {verdicts}")
    cores = os.cpu_count() or 1
    if cores < 2:
        gate["asserted"] = False
        gate["reason"] = (
            f"starved host: {cores} core(s) — the two replay legs "
            "time-share one core with the harness, so the throughput "
            "delta would gate on scheduler interleaving; byte ratios, "
            "verdict pins and the one-pairing invariant asserted "
            "anyway. Re-run `python tools/workloads.py --certnative` "
            "on a >=2-core host"
        )
    else:
        gate["asserted"] = True
        assert replay["cert_s"] < replay["column_s"], (
            f"certificate replay {replay['cert_s']}s did not beat the "
            f"fold-after-the-fact column {replay['column_s']}s")
    print(f"  wire {wire['bytes_ratio']}x / store {stor['bytes_ratio']}x "
          f"smaller; replay {replay['column_s']}s -> {replay['cert_s']}s "
          f"({replay['speedup']}x)", file=sys.stderr)
    return {
        "metric": "certnative",
        "value": replay["cert_sigs_per_sec"],
        "unit": "sigs_per_sec",
        "stat": "single_run",
        "validators": n_vals,
        "blocks": n_blocks,
        "wire": wire,
        "store": stor,
        "feed": feed,
        "replay": replay,
        "verdicts": verdicts,
        "gate": gate,
    }


def bench_watchtower(n_nodes=3, n_blocks=12, n_vals=4):
    """ISSUE 18: the streaming safety auditor, measured offline on
    synthetic feeds. One factory chain is served as N identical node
    feeds through the auditor's ingest path; the clean leg records the
    audit frame rate, the audit-latency distribution, and — the
    first-class number — the false-positive count, which must be ZERO
    (an auditor that cries wolf on a healthy net is worse than none).
    The detection leg then forks one node's frame at the tip and
    asserts the fork verdict names every double-signing validator and
    the cross-column equivocation scan yields verified evidence — so a
    zero in the clean leg means "nothing to find", not "not looking".
    """
    from cometbft_tpu.replication.feed import ReplicationFeed
    from cometbft_tpu.utils import factories as fx
    from cometbft_tpu.utils.metrics import reset_bundles
    from cometbft_tpu.watchtower import Watchtower

    if QUICK:
        n_blocks = 6
    chain_id = "watchtower-chain"
    store, state, _genesis, signers = fx.make_chain(
        n_blocks, n_vals, chain_id=chain_id)
    vals = fx.make_validator_set(signers)
    by_addr = {s.address(): s for s in signers}

    class _Vals:
        def load_validators(self, h):
            return vals

    feed = ReplicationFeed(chain_id, store, _Vals())
    frames = [json.loads(feed._build_frame(store.load_block(h)))
              for h in range(1, n_blocks + 1)]

    # --- clean leg: N identical feeds, zero verdicts expected ----------
    reset_bundles()
    names = [f"node{i}" for i in range(n_nodes)]
    wt = Watchtower({n: "" for n in names}, chain_id=chain_id,
                    submit_evidence=False)
    lats = []
    t0 = time.perf_counter()
    for frame in frames:
        for name in names:
            t1 = time.perf_counter()
            wt.ingest_frame(name, frame)
            lats.append(time.perf_counter() - t1)
    clean_s = time.perf_counter() - t0
    lats.sort()
    false_positives = len(wt.verdicts)

    def pct(p):
        return round(lats[min(int(p * len(lats)), len(lats) - 1)] * 1e3, 3)

    # --- detection leg: fork node1's tip frame -------------------------
    wt2 = Watchtower({n: "" for n in names}, chain_id=chain_id,
                     submit_evidence=False)
    for frame in frames[:-1]:
        for name in names:
            wt2.ingest_frame(name, frame)
    tip_frame = frames[-1]
    wt2.ingest_frame("node0", tip_frame)
    forked_commit = fx.make_commit(
        chain_id, n_blocks, 0, fx.make_block_id(b"watchtower-fork"),
        vals, by_addr)
    forked = dict(tip_frame)
    forked["seen"] = forked_commit.encode().hex()
    wt2.ingest_frame("node1", forked)
    det = {
        "fork": sum(1 for v in wt2.verdicts if v["check"] == "fork"),
        "equivocation": sum(
            1 for v in wt2.verdicts if v["check"] == "equivocation"),
        "culprits": max(
            (len(v.get("culprits", ())) for v in wt2.verdicts
             if v["check"] == "fork"), default=0),
    }
    gate = {"zero_false_positives": True, "asserted": True}
    assert false_positives == 0, (
        f"clean synthetic feeds raised {false_positives} verdict(s): "
        f"{wt.verdicts[:3]}")
    assert det["fork"] >= 1, "forked tip frame not detected"
    assert det["culprits"] == n_vals, (
        f"fork culprits {det['culprits']} != every signer {n_vals}")
    assert det["equivocation"] >= 1, (
        "cross-column equivocation scan produced no verified evidence")
    frames_per_s = round(len(lats) / clean_s, 1)
    print(f"  watchtower: {frames_per_s} frames/s audited, p99 "
          f"{pct(0.99)} ms, 0 false positives, fork+equivocation "
          f"detected", file=sys.stderr)
    return {
        "metric": "watchtower",
        "value": frames_per_s,
        "unit": "frames_per_sec",
        "stat": "single_run",
        "nodes": n_nodes,
        "blocks": n_blocks,
        "validators": n_vals,
        "false_positives": false_positives,
        "audit_latency_ms": {"p50": pct(0.50), "p99": pct(0.99)},
        # absolute per-machine budget the compare leg gates on: audit
        # must stay cheap enough to run inline with a feed (this is a
        # 1-core-CI-safe bound, not a perf target)
        "p99_budget_ms": 250.0,
        "detection": det,
        "gate": gate,
    }


def _emit(rec):
    print(json.dumps(rec))
    sys.stdout.flush()


def _spawn_child(args, env_extra, timeout=3600):
    """Run this script as a child with a controlled jax environment and
    return its last JSON stdout line. Subprocesses are mandatory here:
    XLA's device count is fixed at process start, so each n_devices
    point needs its own interpreter."""
    import subprocess

    env = dict(os.environ)
    env.update(env_extra)
    p = subprocess.run(
        [sys.executable, os.path.abspath(__file__)] + args,
        capture_output=True, text=True, env=env, timeout=timeout,
    )
    if p.returncode != 0:
        raise RuntimeError(
            f"child {args} rc={p.returncode}\n"
            f"stderr: {p.stderr[-2000:]}\nstdout: {p.stdout[-2000:]}"
        )
    for ln in reversed(p.stdout.strip().splitlines()):
        try:
            return json.loads(ln)
        except json.JSONDecodeError:
            continue
    raise RuntimeError(f"child {args} produced no JSON: {p.stdout[-500:]}")


def _accel_devices() -> int:
    """Real accelerator device count (0 on CPU-only jax), asked of a
    short-lived child. A chip belongs to one process at a time: the
    parents that call this go on to start children that need the chip,
    so they must never initialise a jax backend themselves."""
    import subprocess

    p = subprocess.run(
        [sys.executable, "-c",
         "import jax; print(0 if jax.default_backend() == 'cpu' "
         "else len(jax.devices()))"],
        capture_output=True, text=True, timeout=600,
    )
    if p.returncode != 0:
        raise RuntimeError(f"device probe failed: {p.stderr[-2000:]}")
    return int(p.stdout.strip().splitlines()[-1])


def multichip_child(n_devices: int, batch: int = 1024):
    """One sharded-verify measurement at a fixed device count: build a
    signed batch through the production packing (Ed25519BatchVerifier
    rsk pack), shard it over the mesh, and time submit→fetch."""
    import jax
    import numpy as np

    from cometbft_tpu.crypto import ed25519 as E
    from cometbft_tpu.crypto import ed25519_ref as ref
    from cometbft_tpu.parallel.mesh import MeshVerifyEngine, pad_to_shards

    devs = jax.devices()[:n_devices]
    assert len(devs) == n_devices, f"need {n_devices} devices, have {len(devs)}"
    eng = MeshVerifyEngine(devs)
    seeds = [bytes([i + 1]) * 32 for i in range(4)]
    pubs = [ref.pubkey_from_seed(s) for s in seeds]
    msgs = [b"multichip-%d" % i for i in range(4)]
    sigs = [ref.sign(seeds[i], msgs[i]) for i in range(4)]
    bv = E.Ed25519BatchVerifier()
    for i in range(batch):
        j = i % 4
        bv.add(E.Ed25519PubKey(pubs[j]), msgs[j], sigs[j])
    n = bv.count()
    b = pad_to_shards(n, eng.n_devices, bucket=E._bucket(n))
    rsk, live, pub_blob = bv._pack_rsk_live(n, b)
    # warmup: compiles both sharded programs and stages the column's pair
    all_ok, _ = eng.submit(pub_blob, rsk, live)
    assert bool(np.asarray(all_ok)), "warmup batch must verify"

    def timed():
        t0 = time.perf_counter()
        ok, _bits = eng.submit(pub_blob, rsk, live)
        ok = bool(np.asarray(ok))
        d = time.perf_counter() - t0
        assert ok
        return d

    dt, stat = _best_of(timed)
    return {
        "n_devices": n_devices,
        "batch": n,
        "padded": b,
        "shard_lanes": b // n_devices,
        "ms": round(dt * 1e3, 2),
        "stat": stat,
        "sigs_per_sec": round(n / dt, 1),
        "put_fixed_us": round(
            eng.dispatch_terms()["put_fixed_s"] * 1e6, 2),
    }


def bench_multichip(points=(1, 2, 4, 8), batch=1024):
    """Real sharded multichip record -> MULTICHIP_r06.json: aggregate
    sigs/s per device count plus scaling efficiency. On a host without
    a real multi-device accelerator the mesh is XLA's virtual CPU
    devices — every "chip" shares this host's physical cores, so the
    speedup gate is recorded as skipped (asserting near-linear scaling
    on a time-sliced mesh would gate on scheduler noise, not on the
    sharded path); on a real pod the gate asserts >=1.7x at 2 chips."""
    real = _accel_devices()
    emulated = real < 2
    per = {}
    for nd in points:
        env = {}
        if emulated:
            env["JAX_PLATFORMS"] = "cpu"
            env["XLA_FLAGS"] = (
                f"--xla_force_host_platform_device_count={nd}"
            )
        elif nd > real:
            break
        per[str(nd)] = _spawn_child(
            ["--multichip-child", str(nd), str(batch)], env)
        print(f"  multichip n_devices={nd}: "
              f"{per[str(nd)]['sigs_per_sec']} sigs/s", file=sys.stderr)
    base = per["1"]["sigs_per_sec"]
    eff = {
        nd: round(r["sigs_per_sec"] / (int(nd) * base), 3)
        for nd, r in per.items()
    }
    gate = {"min_speedup_2dev": 1.7}
    if emulated:
        gate["asserted"] = False
        gate["reason"] = (
            "emulated mesh: XLA virtual CPU devices time-share this "
            "host's cores, so aggregate throughput cannot scale with "
            "device count; the gate needs >=2 real accelerator devices"
        )
    else:
        gate["asserted"] = True
        speedup = per["2"]["sigs_per_sec"] / base
        gate["speedup_2dev"] = round(speedup, 3)
        assert speedup >= 1.7, (
            f"sharded verify speedup at 2 devices {speedup:.2f}x < 1.7x"
        )
    rec = {
        "mode": "sharded_verify_rsk",
        "batch": batch,
        "emulated_cpu_mesh": emulated,
        "per_n_devices": per,
        "scaling_efficiency": eff,
        "gate": gate,
    }
    path = os.path.join(
        os.path.dirname(__file__), "..", "MULTICHIP_r06.json")
    with open(path, "w") as f:
        json.dump(rec, f, indent=2)
        f.write("\n")
    return rec


def two_backend_child(to_height: int = 16, window: int = 4):
    """Device/mesh leg of the two-backend replay: same chain, same
    ReplayEngine, but dispatch FORCED onto the sharded mesh path
    (NATIVE_MAX=0 + always-mesh) so the measurement is the device
    pipeline, not whatever dispatch would honestly pick here."""
    import numpy as np  # noqa: F401  (jax warmup ordering)

    from cometbft_tpu.abci.client import AppConns
    from cometbft_tpu.abci.kvstore import KVStoreApp
    from cometbft_tpu.blocksync import ReplayEngine
    from cometbft_tpu.crypto import ed25519 as E
    from cometbft_tpu.state.execution import BlockExecutor, make_genesis_state
    from cometbft_tpu.storage import BlockStore, open_kv
    from cometbft_tpu.utils import factories as fx

    E.NATIVE_MAX = 0
    E.MESH_MIN = 0
    E._mesh_beats_single = lambda n, b: True
    db_path = os.path.join("/tmp/ns_chain", "blockstore_2000b_1000v.db")
    store = BlockStore(open_kv(db_path))
    assert store.height() >= to_height, "run the CPU leg first (generates)"
    signers = fx.make_signers(1000)
    vals = fx.make_validator_set(signers)
    genesis = make_genesis_state("ns-chain", vals)

    def one_run():
        executor = BlockExecutor(AppConns(KVStoreApp()))
        engine = ReplayEngine(
            store, executor, verify_mode="batched", window=window)
        t0 = time.perf_counter()
        state, stats = engine.run(genesis.copy(), to_height=to_height)
        d = time.perf_counter() - t0
        assert state.last_block_height == to_height
        return d, stats

    one_run()  # warmup: compile the shard-shape kernels
    dt, stats = one_run()
    return {
        "to_height": to_height,
        "window": window,
        "seconds": round(dt, 2),
        "sigs_verified": stats.sigs_verified,
        "sigs_per_sec": round(stats.sigs_verified / dt, 1),
        "forced_mesh_dispatch": True,
    }


def two_backend_cpu_child():
    """Host leg of the two-backend replay (and the chain's generator on
    first run), in a child pinned to JAX_PLATFORMS=cpu: dispatch then
    keeps every batch on the native IFMA engine, and the chip stays
    free for the mesh leg's child. The chain is whatever prefix exists in
    the store (generation at 1000 validators runs ~160 blocks/hour on
    a 1-core box — signing, not verification, is the wall — so the
    bench replays the available prefix rather than demanding the full
    2000-block QUICK shape; a 24-block floor is generated on first
    run)."""
    from cometbft_tpu.abci.client import AppConns
    from cometbft_tpu.abci.kvstore import KVStoreApp
    from cometbft_tpu.blocksync import ReplayEngine
    from cometbft_tpu.state.execution import BlockExecutor, \
        make_genesis_state
    from cometbft_tpu.storage import BlockStore, open_kv
    from cometbft_tpu.utils import factories as fx

    os.makedirs("/tmp/ns_chain", exist_ok=True)
    db_path = os.path.join("/tmp/ns_chain", "blockstore_2000b_1000v.db")
    store = BlockStore(open_kv(db_path))
    n_vals = 1000
    signers = fx.make_signers(n_vals)
    vals = fx.make_validator_set(signers)
    genesis = make_genesis_state("ns-chain", vals)
    if store.height() < 25:
        if store.height():
            raise SystemExit(f"store too short ({store.height()}); "
                             f"delete {db_path}")
        app = KVStoreApp()
        pool = fx.RPool(n_vals, blocks_per_fill=32)
        fx.make_chain(
            25, n_validators=n_vals, chain_id="ns-chain", app=app,
            block_store=store, verify_last_commit=False, r_pool=pool)
    # the tip block's own commit only lands with the NEXT block's
    # LastCommit, so a partially generated store replays to height-1
    to_height = store.height() - 1
    window = 4

    def cpu_leg():
        executor = BlockExecutor(AppConns(KVStoreApp()))
        engine = ReplayEngine(
            store, executor, verify_mode="batched", window=window)
        t0 = time.perf_counter()
        state, stats = engine.run(genesis.copy(), to_height=to_height)
        dt = time.perf_counter() - t0
        assert state.last_block_height == to_height
        return dt, stats

    cpu_leg()  # warmup: page the store, prime native tables
    dt, stats = cpu_leg()
    return {
        "metric": "replay_two_backend_cpu_leg_1000v",
        "backend": "native-cpu",
        "to_height": to_height,
        "window": window,
        "seconds": round(dt, 2),
        "sigs_verified": stats.sigs_verified,
        "sigs_per_sec": round(stats.sigs_verified / dt, 1),
        "blocks_per_sec": round(to_height / dt, 1),
    }


def bench_two_backend():
    """VERDICT Next #2: the two-backend replay comparison, recorded
    even where it is unflattering. Both legs replay THE SAME stored
    1000-validator chain prefix through the same ReplayEngine harness;
    only the verify backend differs. Leg A (two_backend_cpu_child) is
    the native IFMA CPU engine. Leg B (two_backend_child) forces the
    sharded mesh path — on a host without a real accelerator that
    means XLA *emulating* the mesh on CPU, so the record carries the
    flag. Each leg is a child, one after the other, and this parent
    never touches jax: a chip belongs to one process at a time. The
    stored r05 real-TPU 50k-block record rides along as the cross-box
    yardstick."""
    cpu_rec = _spawn_child(
        ["--two-backend-cpu-child"], {"JAX_PLATFORMS": "cpu"}, timeout=7200)
    real = _accel_devices()
    emulated = real < 2
    env = {"COMETBFT_TPU_MESH": "on"}
    if emulated:
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    mesh_rec = _spawn_child(["--two-backend-child"], env, timeout=3600)
    mesh_rec["emulated_cpu_mesh"] = emulated
    rec = {
        "metric": "replay_two_backend_1000v",
        "cpu_native": {
            k: cpu_rec[k]
            for k in ("to_height", "seconds", "sigs_per_sec",
                      "blocks_per_sec")
        },
        "mesh_device": mesh_rec,
        "ratio_cpu_over_mesh": round(
            cpu_rec["sigs_per_sec"] / mesh_rec["sigs_per_sec"], 2),
    }
    # fold in the stored real-chip record for the cross-box ratio
    path = os.path.join(os.path.dirname(__file__), "..", "WORKLOADS.json")
    if os.path.exists(path):
        with open(path) as f:
            for ln in f:
                if not ln.strip():
                    continue
                old = json.loads(ln)
                if old.get("metric") == "replay_50000b_1000v":
                    rec["r05_tpu_50000b_sigs_per_sec"] = old["sigs_per_sec"]
                    rec["ratio_r05_tpu_over_cpu"] = round(
                        old["sigs_per_sec"] / cpu_rec["sigs_per_sec"], 2)
    return [cpu_rec, rec]


def bench_ingest_sustained_load(clients=32, duration_s=8.0, window=256):
    """Sustained tx-ingress workload (ROADMAP item #4): tools/txload.py
    drives `clients` concurrent signed broadcast_tx_sync producers
    against an in-process validator, once with per-tx admission (the
    seed's path) and once with the micro-batched pipeline. The record
    carries both runs; headline numbers are the batched mode's.

    Machine gates (absolute txs/s + p99 commit latency, and the
    batched-beats-pertx comparison) are asserted only on hosts with >=2
    cores: on a 1-core box the producers, the admission drainer, and
    consensus time-share one core, so a pass/fail would gate on
    scheduler interleaving, not the ingest path — same pattern as the
    multichip gate."""
    import subprocess

    dur = 3.0 if QUICK else duration_s

    def one(mode, extra_args=(), env_extra=None):
        script = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                              "txload.py")
        p = subprocess.run(
            [sys.executable, script, "--mode", mode, "--signed",
             "--clients", str(clients), "--duration", str(dur),
             "--window", str(window), *extra_args],
            capture_output=True, text=True, timeout=600,
            env={**os.environ, "JAX_PLATFORMS": "cpu", **(env_extra or {})},
        )
        if p.returncode != 0:
            raise RuntimeError(
                f"txload --mode {mode} rc={p.returncode}\n"
                f"stderr: {p.stderr[-2000:]}")
        for ln in reversed(p.stdout.strip().splitlines()):
            try:
                return json.loads(ln)
            except json.JSONDecodeError:
                continue
        raise RuntimeError(f"txload produced no JSON: {p.stdout[-500:]}")

    # best-of-2 per mode: single samples on a time-shared host swing
    # with scheduler interleaving (same reasoning as _best_of)
    reps = 1 if QUICK else 2

    def best(mode):
        runs = [one(mode) for _ in range(reps)]
        r = max(runs, key=lambda x: x["txs_per_sec"])
        r["stat"] = f"best_of_{reps}"
        print(f"  {mode}: {r['txs_per_sec']} txs/s  "
              f"p99 {r['commit_latency_ms']['p99']} ms", file=sys.stderr)
        return r

    pertx = best("pertx")
    batched = best("batched")

    # --- tx lifecycle observatory (PROFILE round 11) -------------------
    # (a) stage-attributed commit latency: one batched run with the
    # hash-prefix lifecycle sampler tracing to a sink, decomposed by
    # tools/latency_analyze.py into the 7-stage waterfall
    life = one("batched", extra_args=("--lifecycle",))
    waterfall = life.get("stage_waterfall") or {}
    rec_check = waterfall.get("reconciliation") or {}
    if waterfall.get("dominant_stage_p99"):
        print(f"  lifecycle: {waterfall['txs_complete']} chains, "
              f"dominant stage {waterfall['dominant_stage_p99']}, "
              f"reconciliation off by "
              f"{rec_check.get('relative_error', 0) * 100:.1f}%",
              file=sys.stderr)

    # (b) sampling overhead: block rate with lifecycle sampling OFF vs
    # the production default 1/64 (env wins over config in the child) —
    # the observatory must cost <5% block rate to stay always-on
    def block_rate(env):
        runs = [one("batched", env_extra=env) for _ in range(reps)]
        return max(r["height"] / max(r["duration_s"], 1e-9) for r in runs)

    base_bps = block_rate({"COMETBFT_TPU_TXLIFE": "0"})
    samp_bps = block_rate({"COMETBFT_TPU_TXLIFE": "64"})
    overhead_pct = round(max(0.0, (base_bps - samp_bps)
                             / max(base_bps, 1e-9) * 100), 2)
    print(f"  lifecycle overhead: {base_bps:.2f} -> {samp_bps:.2f} "
          f"blocks/s ({overhead_pct}%)", file=sys.stderr)

    gate = {
        "min_txs_per_sec": 1500.0,
        "max_p99_commit_ms": 1500.0,
        "batched_beats_pertx": True,
        "waterfall_reconciles": True,
        "max_lifecycle_overhead_pct": 5.0,
    }
    cores = os.cpu_count() or 1
    starved = cores < 2
    if starved:
        gate["asserted"] = False
        gate["reason"] = (
            f"starved host: {cores} core(s) — producers, admission "
            "drainer, and consensus time-share the core, so thresholds "
            "would gate on scheduler interleaving; re-run "
            "`python tools/workloads.py --ingest` on a >=2-core host"
        )
    else:
        gate["asserted"] = True
        assert batched["txs_per_sec"] >= gate["min_txs_per_sec"], (
            f"sustained ingest {batched['txs_per_sec']} txs/s < "
            f"{gate['min_txs_per_sec']}"
        )
        assert (batched["commit_latency_ms"]["p99"]
                <= gate["max_p99_commit_ms"]), (
            f"p99 commit latency {batched['commit_latency_ms']['p99']} ms "
            f"> {gate['max_p99_commit_ms']} ms"
        )
        assert batched["txs_per_sec"] > pertx["txs_per_sec"], (
            "micro-batched admission did not beat per-tx throughput"
        )
        assert (batched["commit_latency_ms"]["p99"]
                < pertx["commit_latency_ms"]["p99"]), (
            "micro-batched admission did not beat per-tx p99 latency"
        )
        assert rec_check.get("within_tolerance"), (
            f"stage waterfall does not reconcile with measured e2e p50: "
            f"{rec_check}"
        )
        assert overhead_pct <= gate["max_lifecycle_overhead_pct"], (
            f"lifecycle sampling costs {overhead_pct}% block rate > "
            f"{gate['max_lifecycle_overhead_pct']}% budget"
        )
    return {
        "metric": "ingest_sustained_load",
        "clients": clients,
        "duration_s": dur,
        "signed": True,
        "window": window,
        "txs_per_sec": batched["txs_per_sec"],
        "commit_latency_ms": batched["commit_latency_ms"],
        "txs_per_app_call": batched["txs_per_app_call"],
        "pertx_txs_per_sec": pertx["txs_per_sec"],
        "pertx_commit_latency_ms": pertx["commit_latency_ms"],
        "pertx_txs_per_app_call": pertx["txs_per_app_call"],
        "speedup_txs_per_sec": round(
            batched["txs_per_sec"] / max(pertx["txs_per_sec"], 1e-9), 2),
        "p99_improvement": round(
            pertx["commit_latency_ms"]["p99"]
            / max(batched["commit_latency_ms"]["p99"], 1e-9), 2),
        "lifecycle_rate": life.get("lifecycle_rate"),
        "stage_waterfall": waterfall,
        "lifecycle_overhead": {
            "baseline_blocks_per_sec": round(base_bps, 2),
            "sampled_blocks_per_sec": round(samp_bps, 2),
            "sample_rate": 64,
            "overhead_pct": overhead_pct,
            "budget_pct": gate["max_lifecycle_overhead_pct"],
        },
        "gate": gate,
    }


def bench_light_stream_fanout(clients=10000, duration_s=10.0, workers=8,
                              http_streams=4):
    """Light-client streaming-service workload (ROADMAP item #2):
    tools/lightload.py boots one serving validator and simulates
    `clients` concurrent /light_stream subscribers plus a proof/bisect
    request pool against it.

    Two gate classes:

    - asserted EVERYWHERE (they measure correctness of the serving
      surface, not host speed): per-height commit verification count
      == 1 under the whole fan-out (cache amortization), every
      simulated client served, MMR proof bytes within the O(log n)
      bound, and every proof received over real HTTP verifying
      client-side;
    - machine-gated on >=2 cores (throughput/latency would gate on
      scheduler interleaving when 10k queues, consensus, and the
      drainers time-share one core): headers/s, deliveries/s, p99
      proof latency.
    """
    import subprocess

    n_clients = 500 if QUICK else clients
    dur = 4.0 if QUICK else duration_s
    script = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "lightload.py")
    p = subprocess.run(
        [sys.executable, script, "--clients", str(n_clients),
         "--duration", str(dur), "--workers", str(workers),
         "--http-streams", str(http_streams)],
        capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    if p.returncode != 0:
        raise RuntimeError(
            f"lightload rc={p.returncode}\nstderr: {p.stderr[-2000:]}")
    rec = None
    for ln in reversed(p.stdout.strip().splitlines()):
        try:
            rec = json.loads(ln)
            break
        except json.JSONDecodeError:
            continue
    if rec is None:
        raise RuntimeError(f"lightload produced no JSON: {p.stdout[-500:]}")
    print(f"  light fan-out: {rec['clients_served']}/{rec['clients']} "
          f"clients, {rec['headers_per_sec']} headers/s, "
          f"{rec['deliveries_per_sec']} deliveries/s, proof p99 "
          f"{rec['proof_p99_ms']} ms, verify/height "
          f"{rec['max_verify_calls_per_height']}", file=sys.stderr)

    # --- correctness gates: asserted unconditionally -------------------
    assert rec["max_verify_calls_per_height"] == 1, (
        f"cache amortization broken: a height was commit-verified "
        f"{rec['max_verify_calls_per_height']} times under fan-out"
    )
    assert rec["clients_served"] == rec["clients"], (
        f"only {rec['clients_served']}/{rec['clients']} subscribers "
        "received payloads"
    )
    assert rec["proof_bytes_max"] <= rec["proof_bytes_bound"], (
        f"MMR proof {rec['proof_bytes_max']} B exceeds the O(log n) "
        f"bound {rec['proof_bytes_bound']} B at n={rec['mmr_size']}"
    )
    assert rec["http_stream_lines"] > 0 and not rec["http_stream_errors"], (
        f"/light_stream HTTP path failed: {rec['http_stream_errors']}"
    )
    assert rec["http_stream_verified"] == rec["http_stream_lines"], (
        "a streamed proof failed client-side ancestry verification"
    )

    # --- throughput gates: machine-gated -------------------------------
    gate = {
        "verify_calls_per_height": 1,
        "all_clients_served": True,
        "proof_bytes_within_log_bound": True,
        "http_stream_proofs_verified": True,
        "min_headers_per_sec": 2.0,
        "min_deliveries_per_sec": float(n_clients),
        "max_proof_p99_ms": 50.0,
    }
    cores = os.cpu_count() or 1
    if cores < 2:
        gate["asserted"] = False
        gate["reason"] = (
            f"starved host: {cores} core(s) — consensus, 10k subscriber "
            "queues, drain sweeps, and the request pool time-share the "
            "core, so throughput thresholds would gate on scheduler "
            "interleaving; correctness gates above asserted anyway. "
            "Re-run `python tools/workloads.py --light` on a >=2-core "
            "host"
        )
    else:
        gate["asserted"] = True
        assert rec["headers_per_sec"] >= gate["min_headers_per_sec"], (
            f"served {rec['headers_per_sec']} headers/s < "
            f"{gate['min_headers_per_sec']}"
        )
        assert rec["deliveries_per_sec"] >= gate["min_deliveries_per_sec"], (
            f"{rec['deliveries_per_sec']} deliveries/s < "
            f"{gate['min_deliveries_per_sec']}"
        )
        assert rec["proof_p99_ms"] <= gate["max_proof_p99_ms"], (
            f"proof p99 {rec['proof_p99_ms']} ms > "
            f"{gate['max_proof_p99_ms']} ms"
        )
    rec["gate"] = gate
    return rec


def bench_das_fleet(clients=1000, duration_s=8.0, k=16, m=16,
                    http_samples=8):
    """Data-availability sampling workload (ROADMAP item #3, ISSUE 14):
    tools/dasload.py boots one DA-encoding validator and drives
    `clients` sampling clients per committed block against its serving
    surface, plus an adversarial withholding leg and a native-vs-oracle
    GF(2^16) encode comparison.

    Two gate classes:

    - asserted EVERYWHERE (protocol correctness, not host speed): every
      client of every honest leg reaches 99% confidence, each sample's
      wire cost stays within chunk + Merkle-path bound, the HTTP
      da_sample path verifies client-side, the header carries a 32-byte
      da_root, and with m+1 chunks withheld >= 95% of clients detect it
      (each client misses with prob < 0.5%);
    - machine-gated on >=2 cores: fleet sample-verify throughput and
      the native codec's speedup over the numpy oracle (both time-share
      the core with consensus on a starved host).
    """
    import subprocess

    n_clients = 200 if QUICK else clients
    dur = 4.0 if QUICK else duration_s
    script = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "dasload.py")
    p = subprocess.run(
        [sys.executable, script, "--clients", str(n_clients),
         "--duration", str(dur), "--data-shards", str(k),
         "--parity-shards", str(m), "--http-samples", str(http_samples)],
        capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    if p.returncode != 0:
        raise RuntimeError(
            f"dasload rc={p.returncode}\nstderr: {p.stderr[-2000:]}")
    rec = None
    for ln in reversed(p.stdout.strip().splitlines()):
        try:
            rec = json.loads(ln)
            break
        except json.JSONDecodeError:
            continue
    if rec is None:
        raise RuntimeError(f"dasload produced no JSON: {p.stdout[-500:]}")
    hon, adv, codec = rec["honest"], rec["withholding"], rec["codec"]
    print(f"  das fleet: {hon['clients']} clients x "
          f"{hon['heights_sampled']} heights, "
          f"{hon['samples_per_sec']} samples/s, "
          f"{hon['proof_bytes_per_sample']} B/sample, withholding "
          f"detected by {adv['clients_detected_withholding']}"
          f"/{adv['clients']}, native codec "
          f"{codec.get('native_speedup', 'n/a')}x oracle", file=sys.stderr)

    # --- correctness gates: asserted unconditionally -------------------
    assert rec["heights_committed"] > 0 and hon["heights_sampled"] > 0, (
        "no blocks committed/sampled under the DA fleet")
    assert hon["clients_confident_min"] == hon["clients"], (
        f"only {hon['clients_confident_min']}/{hon['clients']} clients "
        "reached 99% confidence on a fully-available block"
    )
    assert hon["proof_bytes_per_sample"] <= hon["proof_bytes_bound"], (
        f"per-sample wire cost {hon['proof_bytes_per_sample']} B exceeds "
        f"the chunk+path bound {hon['proof_bytes_bound']} B"
    )
    assert (rec["http_samples_ok"] == rec["http_samples"]
            and not rec["http_errors"]), (
        f"HTTP da_sample path failed: {rec['http_errors']}")
    assert len(rec["header_da_root"]) == 64, (
        f"committed header carries no 32-byte da_root: "
        f"{rec['header_da_root']!r}")
    detect_frac = adv["clients_detected_withholding"] / adv["clients"]
    assert detect_frac >= 0.95, (
        f"only {detect_frac:.1%} of clients detected {adv['withheld_chunks']}"
        f"/{k + m} chunks withheld (expected >= 95%)"
    )
    assert codec["native_available"], "native GF(2^16) codec not built"

    # --- throughput gates: machine-gated -------------------------------
    gate = {
        "all_clients_confident": True,
        "proof_bytes_within_bound": True,
        "http_samples_verified": True,
        "min_withholding_detect_frac": 0.95,
        "min_samples_per_sec": 2000.0,
        "min_native_codec_speedup": 1.5,
    }
    cores = os.cpu_count() or 1
    if cores < 2:
        gate["asserted"] = False
        gate["reason"] = (
            f"starved host: {cores} core(s) — the sampling fleet, the "
            "RS worker pool, and consensus time-share the core, so "
            "throughput/speedup thresholds would gate on scheduler "
            "interleaving; correctness gates above asserted anyway. "
            "Re-run `python tools/workloads.py --das` on a >=2-core host"
        )
    else:
        gate["asserted"] = True
        assert hon["samples_per_sec"] >= gate["min_samples_per_sec"], (
            f"{hon['samples_per_sec']} samples/s < "
            f"{gate['min_samples_per_sec']}"
        )
        assert codec["native_speedup"] >= gate["min_native_codec_speedup"], (
            f"native codec only {codec['native_speedup']}x oracle < "
            f"{gate['min_native_codec_speedup']}x"
        )
    rec["gate"] = gate
    return rec


def bench_das_pc(clients=1000, duration_s=6.0, k_c=4, m_c=4,
                 http_samples=4):
    """Polynomial-commitment DAS workload (ROADMAP items #1/#4, ISSUE
    19): tools/dasload.py --pc boots one validator with the 2D KZG
    track enabled and drives `clients` sampling clients per committed
    block, then runs three adversarial legs (column withholding, a
    lying encoder with honestly-committed garbage parity, and the same
    lying encoder on the 1D Merkle track) plus a native-vs-oracle
    multiproof opening comparison on the Pippenger MSM engine.

    Two gate classes:

    - asserted EVERYWHERE (protocol correctness + wire cost, not host
      speed): every honest client reaches 99% confidence, multiproof
      bytes/sample (INCLUDING the amortized commitment download) beat
      the 1D track's 256 B chunk+path bound, every client detects
      m_c+1 withheld columns (deterministic: more columns are withheld
      than remain), the parity-linearity check catches the lying
      encoder for EVERY client while the 1D fleet stays fully
      confident over the same corruption (the pinned blindness pair),
      the committed header's da_root binds the PC commitment via the
      combined root, the HTTP multiproof path verifies client-side,
      and the native MSM opening path is available and faster than the
      pure-Python oracle (same-host A/B, robust to starvation);
    - machine-gated on >=2 cores: absolute fleet sample throughput and
      native openings/s (the fleet, the MSM worker pool, and consensus
      time-share the core on a starved host).
    """
    import subprocess

    n_clients = 200 if QUICK else clients
    dur = 3.0 if QUICK else duration_s
    script = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "dasload.py")
    p = subprocess.run(
        [sys.executable, script, "--pc", "--clients", str(n_clients),
         "--duration", str(dur), "--pc-data-cols", str(k_c),
         "--pc-parity-cols", str(m_c),
         "--http-samples", str(http_samples)],
        capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    if p.returncode != 0:
        raise RuntimeError(
            f"dasload --pc rc={p.returncode}\n"
            f"stderr: {p.stderr[-2000:]}")
    rec = None
    for ln in reversed(p.stdout.strip().splitlines()):
        try:
            rec = json.loads(ln)
            break
        except json.JSONDecodeError:
            continue
    if rec is None:
        raise RuntimeError(
            f"dasload --pc produced no JSON: {p.stdout[-500:]}")
    hon, adv, lie = rec["honest"], rec["withholding"], rec["lying_encoder"]
    opens = rec["openings"]
    print(f"  das pc: {hon['clients']} clients x "
          f"{hon['heights_sampled']} heights, "
          f"{hon['samples_per_sec']} samples/s, "
          f"{hon['bytes_per_sample']} B/sample vs {rec['rs_proof_bytes_bound']} B "
          f"1D bound, lying encoder caught {lie['clients_parity_fail']}"
          f"/{lie['clients']}, native open "
          f"{opens.get('native_speedup', 'n/a')}x oracle",
          file=sys.stderr)

    # --- correctness gates: asserted unconditionally -------------------
    assert hon["heights_sampled"] > 0 and rec["blocks_encoded"] > 0, (
        "no blocks PC-encoded/sampled under the fleet")
    assert hon["clients_confident_min"] == hon["clients"], (
        f"only {hon['clients_confident_min']}/{hon['clients']} clients "
        "reached 99% confidence on a fully-available block")
    assert hon["bytes_per_sample"] < rec["rs_proof_bytes_bound"], (
        f"multiproof wire cost {hon['bytes_per_sample']} B/sample "
        f"(incl. commitments) does not beat the 1D "
        f"{rec['rs_proof_bytes_bound']} B bound")
    assert adv["clients_detected"] == adv["clients"], (
        f"only {adv['clients_detected']}/{adv['clients']} clients "
        f"detected {adv['withheld_cols']} withheld columns")
    assert (lie["clients_parity_fail"] == lie["clients"]
            and lie["clients_confident"] == 0), (
        f"lying encoder survived: {lie['clients_parity_fail']}"
        f"/{lie['clients']} parity failures, "
        f"{lie['clients_confident']} clients confident")
    assert lie["samples_ok"] == lie["samples"], (
        "lying-encoder openings should all VERIFY (the whole point: "
        f"only the linearity check catches it) — "
        f"{lie['samples_ok']}/{lie['samples']} ok")
    assert rec["oneD_blind_confident_fraction"] == 1.0, (
        "the 1D track detected honest-root garbage parity it is "
        "supposed to be blind to — blindness demo broken: "
        f"{rec['oneD_blind_confident_fraction']}")
    assert rec["header_root_binds_pc"], (
        "committed header da_root does not bind the PC commitment root")
    assert (rec["http_samples_ok"] == rec["http_samples"]
            and not rec["http_errors"]), (
        f"HTTP da_pc_sample path failed: {rec['http_errors']}")
    assert opens["native_available"], "native G1 MSM engine not built"
    assert opens["native_speedup"] > 1.0, (
        f"native multiproof opening only {opens['native_speedup']}x "
        "the pure-Python oracle (expected > 1x on any host)")

    # --- throughput gates: machine-gated -------------------------------
    gate = {
        "all_clients_confident": True,
        "bytes_per_sample_beats_1d_bound": True,
        "withholding_detected_by_all": True,
        "lying_encoder_caught_by_all": True,
        "oneD_track_blind": True,
        "header_root_binds_pc": True,
        "http_samples_verified": True,
        "native_open_faster_than_oracle": True,
        "min_samples_per_sec": 500.0,
        "min_native_openings_per_sec": 50.0,
    }
    cores = os.cpu_count() or 1
    if cores < 2:
        gate["asserted"] = False
        gate["reason"] = (
            f"starved host: {cores} core(s) — the sampling fleet, the "
            "MSM worker pool, and consensus time-share the core, so "
            "absolute throughput thresholds would gate on scheduler "
            "interleaving; correctness and wire-cost gates above "
            "asserted anyway. "
            "Re-run `python tools/workloads.py --das --pc` on a "
            ">=2-core host"
        )
    else:
        gate["asserted"] = True
        assert hon["samples_per_sec"] >= gate["min_samples_per_sec"], (
            f"{hon['samples_per_sec']} samples/s < "
            f"{gate['min_samples_per_sec']}")
        assert (opens["native_openings_per_s"]
                >= gate["min_native_openings_per_sec"]), (
            f"{opens['native_openings_per_s']} native openings/s < "
            f"{gate['min_native_openings_per_sec']}")
    rec["gate"] = gate
    return rec


def _city_coalescing_leg(heights=4):
    """Deterministic half of the city coalescing measurement: the same
    mixed 3-tenant x 4-source request stream dispatched (a) one verify
    call per request — what per-source dispatch did before the shared
    scheduler — and (b) through a manual-mode VerifyScheduler pumped
    with drain_once(). Dispatch counts are exact (no thread timing), so
    the >=3x cut in dispatch calls per 1k sigs and the bit-exact verdict
    differential assert on EVERY host; only the wall-clock comparison is
    machine-gated by the caller."""
    from cometbft_tpu.crypto.ed25519 import (
        Ed25519BatchVerifier, Ed25519PrivKey,
    )
    from cometbft_tpu.crypto.sched import VerifyScheduler

    privs = [Ed25519PrivKey.generate() for _ in range(32)]

    def sign_items(n, tag):
        out = []
        for i in range(n):
            p = privs[i % len(privs)]
            msg = b"city-%s-%d" % (tag, i)
            out.append((p.pub_key(), msg, p.sign(msg)))
        return out

    def fill(items):
        bv = Ed25519BatchVerifier(backend="cpu")
        for pub, msg, sig in items:
            bv.add(pub, msg, sig)
        return bv

    # the city mix: three co-hosted chains, each producing the four
    # verify shapes of the live node (commit ~100 sigs, blocksync
    # window ~128, light-serve miss ~100, admission window ~32)
    shapes = []
    for tenant in ("metro-a", "metro-b", "metro-c"):
        for h in range(heights):
            for source, n in (("consensus", 100), ("blocksync", 128),
                              ("light", 100), ("admission", 32)):
                shapes.append(
                    (tenant, source, sign_items(
                        n, b"%s-%s-%d" % (tenant.encode(),
                                          source.encode(), h))))
    total_sigs = sum(len(items) for _, _, items in shapes)

    # (a) per-source dispatch: one verify call per request
    t0 = time.perf_counter()
    seq_verdicts = [fill(items).verify() for _, _, items in shapes]
    seq_wall = time.perf_counter() - t0
    seq_dispatches = len(shapes)

    # (b) shared scheduler, same stream
    sched = VerifyScheduler(backend="cpu", manual=True,
                            max_coalesce_sigs=2048, quantum_sigs=512)
    handles = [sched.submit(fill(items), tenant=tenant, source=source)
               for tenant, source, items in shapes]
    t0 = time.perf_counter()
    while sched.drain_once():
        pass
    coal_wall = time.perf_counter() - t0
    coal_dispatches = sched.stats["dispatches"]
    sched_verdicts = [h.result(timeout=30) for h in handles]
    assert sched_verdicts == seq_verdicts, (
        "coalesced verdicts diverged from per-source dispatch")
    assert all(ok for ok, _ in sched_verdicts)

    per_1k_seq = seq_dispatches / total_sigs * 1000
    per_1k_coal = coal_dispatches / total_sigs * 1000
    factor = seq_dispatches / max(coal_dispatches, 1)
    assert factor >= 3.0, (
        f"coalescing only cut dispatch calls {factor:.1f}x "
        f"({seq_dispatches} -> {coal_dispatches}) under the city mix, "
        "need >= 3x")
    print(f"  coalescing: {seq_dispatches} -> {coal_dispatches} "
          f"dispatches over {total_sigs} sigs ({factor:.1f}x), wall "
          f"{seq_wall * 1e3:.0f} -> {coal_wall * 1e3:.0f} ms",
          file=sys.stderr)

    # single-waiter pass-through floor: a lone request through a LIVE
    # scheduler vs the same verifier dispatched directly
    live = VerifyScheduler(backend="cpu", max_coalesce_delay_ms=2.0)
    items = sign_items(100, b"solo")
    direct_ms, sched_ms = [], []
    for _ in range(11):
        bv = fill(items)
        t0 = time.perf_counter()
        ok, _bits = bv.verify()
        direct_ms.append((time.perf_counter() - t0) * 1e3)
        assert ok
        bv = fill(items)
        t0 = time.perf_counter()
        ok, _bits = live.submit(bv, tenant="solo",
                                source="consensus").result(30)
        sched_ms.append((time.perf_counter() - t0) * 1e3)
        assert ok
    assert live.stats["passthrough"] == live.stats["dispatches"], (
        "a lone request was coalesced instead of passed through")
    live.close()
    direct_ms.sort()
    sched_ms.sort()
    p50_direct = direct_ms[len(direct_ms) // 2]
    p50_sched = sched_ms[len(sched_ms) // 2]
    return {
        "tenants": 3,
        "requests": seq_dispatches,
        "sigs": total_sigs,
        "sequential_dispatches": seq_dispatches,
        "coalesced_dispatches": coal_dispatches,
        "dispatch_calls_per_1k_sigs_sequential": round(per_1k_seq, 2),
        "dispatch_calls_per_1k_sigs_coalesced": round(per_1k_coal, 2),
        "coalesce_factor": round(factor, 1),
        "verdicts_bit_exact": True,
        "sequential_wall_ms": round(seq_wall * 1e3, 1),
        "coalesced_wall_ms": round(coal_wall * 1e3, 1),
        "passthrough_direct_p50_ms": round(p50_direct, 3),
        "passthrough_sched_p50_ms": round(p50_sched, 3),
        "passthrough_added_ms": round(p50_sched - p50_direct, 3),
    }


def _city_joiner(n_blocks=40, n_vals=20):
    """Blocksync joiner leg: replay a freshly generated chain through
    the batched ReplayEngine with its window mega-batches routed
    through a live shared scheduler at blocksync priority — the node
    that joins the city mid-run."""
    from cometbft_tpu.abci.client import AppConns
    from cometbft_tpu.abci.kvstore import KVStoreApp
    from cometbft_tpu.blocksync import ReplayEngine
    from cometbft_tpu.crypto.sched import VerifyScheduler
    from cometbft_tpu.state.execution import BlockExecutor

    store, final_state, genesis, _ = _signed_chain(n_blocks, n_vals)
    sched = VerifyScheduler(backend="cpu", max_coalesce_delay_ms=1.0)
    try:
        executor = BlockExecutor(AppConns(KVStoreApp()))
        engine = ReplayEngine(store, executor, verify_mode="batched",
                              window=16, sched=sched, tenant="joiner")
        t0 = time.perf_counter()
        state, stats = engine.run(genesis.copy())
        dt = time.perf_counter() - t0
        assert state.last_block_height == n_blocks
        assert state.app_hash == final_state.app_hash
        routed = sched.tenant_stats().get("joiner", 0)
        assert routed > 0, "joiner windows did not route via the scheduler"
        assert sched.stats["dispatches"] <= sched.stats["requests"]
        return {
            "blocks": n_blocks,
            "validators": n_vals,
            "seconds": round(dt, 2),
            "blocks_per_sec": round(n_blocks / dt, 1),
            "sigs_verified": stats.sigs_verified,
            "sched_requests": sched.stats["requests"],
            "sched_dispatches": sched.stats["dispatches"],
            "sched_sigs_routed": routed,
        }
    finally:
        sched.close()


def bench_city():
    """ROADMAP #4 city-scale combined workload (ISSUE 15): sustained
    signed tx ingest + the 10k-subscriber /light_stream fan-out + the
    DA sampling fleet + a blocksync joiner, all RUNNING AT ONCE, plus
    the shared-scheduler coalescing measurement — folded into ONE
    WORKLOADS.json record whose gate asserts every SLO simultaneously:
    txs/s, commit p99, delivery p99, and sample confidence.

    Gate classes follow the house convention: protocol/scheduler
    correctness (verdict bit-exactness, the >=3x dispatch-call cut,
    cache amortization, sampling confidence, withholding detection, the
    joiner's app hash) asserts everywhere; absolute throughput/latency
    thresholds are machine-gated on >=2 cores, since four concurrent
    workloads time-sharing one core gate on scheduler interleaving, not
    on the code."""
    import subprocess
    import threading

    dur = 4.0 if QUICK else 10.0
    tools_dir = os.path.dirname(os.path.abspath(__file__))

    def child(script, args):
        p = subprocess.run(
            [sys.executable, os.path.join(tools_dir, script), *args],
            capture_output=True, text=True, timeout=900,
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
        )
        if p.returncode != 0:
            raise RuntimeError(
                f"{script} rc={p.returncode}\nstderr: {p.stderr[-2000:]}")
        for ln in reversed(p.stdout.strip().splitlines()):
            try:
                return json.loads(ln)
            except json.JSONDecodeError:
                continue
        raise RuntimeError(f"{script} produced no JSON: {p.stdout[-500:]}")

    legs = {
        "ingest": lambda: child("txload.py", [
            "--mode", "batched", "--signed", "--clients", "32",
            "--duration", str(dur), "--window", "256"]),
        "light": lambda: child("lightload.py", [
            "--clients", "500" if QUICK else "10000",
            "--duration", str(dur), "--workers", "8",
            "--http-streams", "4"]),
        "das": lambda: child("dasload.py", [
            "--clients", "200" if QUICK else "1000",
            "--duration", str(dur), "--data-shards", "16",
            "--parity-shards", "16", "--http-samples", "8"]),
        "joiner": lambda: _city_joiner(
            n_blocks=12 if QUICK else 40, n_vals=20),
    }
    results: dict = {}
    errors: dict = {}

    def run(name, fn):
        try:
            results[name] = fn()
        except Exception as e:  # noqa: BLE001 — surface below
            errors[name] = repr(e)

    t0 = time.perf_counter()
    threads = [threading.Thread(target=run, args=(n, fn))
               for n, fn in legs.items()]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    combined_wall = time.perf_counter() - t0
    assert not errors, f"city legs failed: {errors}"
    ingest, light, das, joiner = (results["ingest"], results["light"],
                                  results["das"], results["joiner"])
    print(f"  city: 4 concurrent legs in {combined_wall:.1f} s — "
          f"{ingest['txs_per_sec']} txs/s, "
          f"{light['deliveries_per_sec']} deliveries/s, "
          f"{das['honest']['samples_per_sec']} samples/s, joiner "
          f"{joiner['blocks_per_sec']} blk/s", file=sys.stderr)

    coalescing = _city_coalescing_leg(heights=2 if QUICK else 4)

    # --- correctness gates: asserted unconditionally -------------------
    assert light["max_verify_calls_per_height"] == 1, (
        "cache amortization broke under the combined load")
    assert light["clients_served"] == light["clients"], (
        f"only {light['clients_served']}/{light['clients']} light "
        "subscribers served under the combined load")
    assert light["http_stream_verified"] == light["http_stream_lines"], (
        "a streamed proof failed client-side verification")
    hon, adv = das["honest"], das["withholding"]
    assert hon["clients_confident_min"] == hon["clients"], (
        f"only {hon['clients_confident_min']}/{hon['clients']} sampling "
        "clients reached confidence under the combined load")
    assert len(das["header_da_root"]) == 64, "header lost its da_root"
    detect_frac = adv["clients_detected_withholding"] / adv["clients"]
    assert detect_frac >= 0.95, (
        f"withholding detection dropped to {detect_frac:.1%}")

    gate = {
        "min_txs_per_sec": 1500.0,
        "max_p99_commit_ms": 1500.0,
        "max_delivery_p99_ms": 50.0,
        "min_samples_per_sec": 2000.0,
        "sample_confidence": True,
        "min_coalesce_factor": 3.0,
        "verdicts_bit_exact": True,
        "max_passthrough_added_ms": 1.0,
    }
    cores = os.cpu_count() or 1
    if cores < 2:
        gate["asserted"] = False
        gate["reason"] = (
            f"starved host: {cores} core(s) — four concurrent workloads "
            "time-share the core, so throughput/latency thresholds and "
            "the pass-through timing would gate on scheduler "
            "interleaving; correctness gates (verdict bit-exactness, "
            f"{coalescing['coalesce_factor']}x dispatch-call cut, cache "
            "amortization, sample confidence, withholding detection, "
            "joiner app hash) asserted anyway. Re-run "
            "`python tools/workloads.py --city` on a >=2-core host"
        )
    else:
        gate["asserted"] = True
        assert ingest["txs_per_sec"] >= gate["min_txs_per_sec"], (
            f"city ingest {ingest['txs_per_sec']} txs/s < "
            f"{gate['min_txs_per_sec']}")
        assert (ingest["commit_latency_ms"]["p99"]
                <= gate["max_p99_commit_ms"]), (
            f"city commit p99 {ingest['commit_latency_ms']['p99']} ms > "
            f"{gate['max_p99_commit_ms']} ms")
        assert light["proof_p99_ms"] <= gate["max_delivery_p99_ms"], (
            f"city delivery p99 {light['proof_p99_ms']} ms > "
            f"{gate['max_delivery_p99_ms']} ms")
        assert hon["samples_per_sec"] >= gate["min_samples_per_sec"], (
            f"city sampling {hon['samples_per_sec']} samples/s < "
            f"{gate['min_samples_per_sec']}")
        assert (coalescing["passthrough_added_ms"]
                <= gate["max_passthrough_added_ms"]), (
            f"pass-through added {coalescing['passthrough_added_ms']} ms "
            "latency over direct dispatch")
        assert (coalescing["coalesced_wall_ms"]
                <= coalescing["sequential_wall_ms"] * 1.25), (
            "coalesced dispatch was slower than per-source dispatch")

    return {
        "metric": "city_combined",
        "duration_s": dur,
        "combined_wall_s": round(combined_wall, 1),
        "concurrent_legs": ["ingest", "light", "das", "joiner"],
        "ingest": {
            "clients": ingest["clients"],
            "txs_per_sec": ingest["txs_per_sec"],
            "commit_p50_ms": ingest["commit_latency_ms"]["p50"],
            "commit_p99_ms": ingest["commit_latency_ms"]["p99"],
            "txs_per_app_call": ingest["txs_per_app_call"],
        },
        "light": {
            "clients": light["clients"],
            "clients_served": light["clients_served"],
            "deliveries_per_sec": light["deliveries_per_sec"],
            "delivery_p99_ms": light["proof_p99_ms"],
            "max_verify_calls_per_height":
                light["max_verify_calls_per_height"],
        },
        "das": {
            "clients": hon["clients"],
            "samples_per_sec": hon["samples_per_sec"],
            "clients_confident": hon["clients_confident_min"],
            "withholding_detect_frac": round(detect_frac, 3),
        },
        "joiner": joiner,
        "coalescing": coalescing,
        "gate": gate,
    }


def bench_city_replicated(n_replicas=2):
    """ISSUE 16 scale-out serving plane: one core node publishing the
    replication feed, N stateless `cli.py replica` processes carrying
    the /light_stream + DA sampling fleets over real HTTP, one extra
    replica snapshot-bootstrapping MID-RUN, and a kill-one-replica
    failover leg whose stream clients must see ZERO delivery gaps
    (reconnect-with-cursor covers the outage window).

    Gate classes follow the house convention: serving-plane correctness
    (zero gaps/dups through failover, replica/core byte-identity on
    proofs + DA openings + accumulator roots, snapshot bootstrap
    catch-up, forwarded admission landing in the core mempool) asserts
    everywhere; absolute throughput/latency thresholds are machine-gated
    on >=2 cores — N+3 processes time-sharing one core gate on the OS
    scheduler, not on the code."""
    import signal
    import subprocess
    import tempfile
    import threading
    import urllib.error
    import urllib.request

    from cometbft_tpu.config import DAConfig
    from cometbft_tpu.crypto.ed25519 import Ed25519PrivKey
    from cometbft_tpu.crypto.keys import tmhash
    from cometbft_tpu.da.serve import DAServe
    from cometbft_tpu.light import LightServe
    from cometbft_tpu.mempool.admission import wrap_signed_tx
    from cometbft_tpu.mempool.mempool import ErrTxInCache
    from cometbft_tpu.replication import ReplicationFeed
    from cometbft_tpu.rpc.client import HTTPClient
    from cometbft_tpu.rpc.routes import Env
    from cometbft_tpu.rpc.server import RPCServer
    from cometbft_tpu.state.types import encode_validator_set
    from cometbft_tpu.storage import MemKV, StateStore

    dur = 8.0 if QUICK else 16.0
    n_blocks = 16 if QUICK else 40
    warm = 4  # heights committed before the fleet boots (snapshot seed)
    tools_dir = os.path.dirname(os.path.abspath(__file__))
    repo_root = os.path.dirname(tools_dir)

    # --- core serving plane: real stores, DA, light, feed, RPC --------
    store, state, _genesis, _ = _signed_chain(n_blocks, 4)
    ss = StateStore(MemKV())
    for h in range(1, n_blocks + 2):
        ss._db.set(b"SV:" + h.to_bytes(8, "big"),
                   encode_validator_set(state.validators))

    class _Mem:
        """check_tx-shaped recorder: where forwarded txs land."""

        def __init__(self):
            self.txs = []
            self._seen = set()

        def check_tx(self, tx, from_peer=""):
            key = tmhash(tx)
            if key in self._seen:
                raise ErrTxInCache("tx already in core cache")
            self._seen.add(key)
            self.txs.append(tx)

    da = DAServe(DAConfig(enabled=True, data_shards=4, parity_shards=4,
                          retain_heights=max(64, n_blocks)))
    light = LightServe("bench-chain", store, ss, backend="cpu",
                       tenant="core")
    light.da_serve = da
    feed = ReplicationFeed("bench-chain", store, ss, light_serve=light,
                           da_serve=da, retain_frames=max(64, n_blocks))
    mem = _Mem()
    env = Env(mempool=mem, light_serve=light, da_serve=da,
              replication_feed=feed)
    srv = RPCServer(env, "127.0.0.1", 0)
    srv.start()
    core_url = f"http://{srv.addr[0]}:{srv.addr[1]}"

    def commit(h):
        blk = store.load_block(h)
        da.on_commit(blk)
        light.on_commit(blk)
        feed.on_commit(blk)

    # --- replica process management -----------------------------------
    procs: list = []
    home = tempfile.mkdtemp(prefix="city-repl-home-")

    def start_replica(name):
        log = tempfile.NamedTemporaryFile(
            mode="w+", prefix=f"replica-{name}-", suffix=".log",
            delete=False)
        p = subprocess.Popen(
            [sys.executable, "-m", "cometbft_tpu.cli", "--home", home,
             "replica", "--core-url", core_url,
             "--laddr", "tcp://127.0.0.1:0",
             "--metrics-laddr", "127.0.0.1:0", "--name", name],
            stdout=subprocess.PIPE, stderr=log, text=True, cwd=repo_root,
            env={**os.environ, "JAX_PLATFORMS": "cpu",
                 "PYTHONPATH": repo_root},
        )
        procs.append(p)
        return {"name": name, "proc": p, "log": log.name,
                "spawned_at": time.monotonic()}

    def finish_replica(box, timeout=180.0):
        """Read the one-line JSON address report off the replica's
        stdout (in a thread: jax import dominates startup on a cold
        interpreter, so readline can block for a while)."""
        def read():
            ln = box["proc"].stdout.readline()
            try:
                box.update(json.loads(ln))
            except (json.JSONDecodeError, TypeError):
                box["boot_error"] = ln
        t = threading.Thread(target=read, daemon=True)
        t.start()
        t.join(timeout=timeout)
        if "rpc" not in box:
            tail = ""
            try:
                with open(box["log"]) as f:
                    tail = f.read()[-2000:]
            except OSError:
                pass
            raise RuntimeError(
                f"replica {box['name']} reported no address "
                f"({box.get('boot_error')!r}); log tail: {tail}")
        box["url"] = f"http://{box['rpc'][0]}:{box['rpc'][1]}"
        box["ep"] = f"{box['rpc'][0]}:{box['rpc'][1]}"
        return box

    def wait_ready(box, timeout=120.0):
        """Poll the replica's /healthz until the readiness probe flips
        to 200 (bootstrapped AND feed lag within bounds). Returns the
        spawn-to-ready wall time — interpreter + jax import + snapshot
        restore + feed catch-up, the number an operator scaling the
        fleet actually waits on."""
        mhost, mport = box["metrics"]
        url = f"http://{mhost}:{mport}/healthz"
        deadline = time.monotonic() + timeout
        last = ""
        while time.monotonic() < deadline:
            try:
                with urllib.request.urlopen(url, timeout=2) as r:
                    if r.status == 200:
                        return time.monotonic() - box["spawned_at"]
            except urllib.error.HTTPError as e:
                last = f"HTTP {e.code}"  # 503 = still bootstrapping
            except Exception as e:  # noqa: BLE001 — server not up yet
                last = repr(e)
            time.sleep(0.1)
        raise RuntimeError(f"replica {box['name']} never ready: {last}")

    def wait_applied(url, height, timeout=120.0):
        c = HTTPClient(url, timeout=5)
        deadline = time.monotonic() + timeout
        st: dict = {}
        while time.monotonic() < deadline:
            try:
                st = c.replication_status()
                if int(st.get("applied_height", 0)) >= height:
                    return st
            except Exception:  # noqa: BLE001 — transient under load
                pass
            time.sleep(0.1)
        raise RuntimeError(f"replica at {url} stuck below {height}: {st}")

    def child(script, args):
        p = subprocess.run(
            [sys.executable, os.path.join(tools_dir, script), *args],
            capture_output=True, text=True, timeout=900,
            env={**os.environ, "JAX_PLATFORMS": "cpu",
                 "PYTHONPATH": repo_root},
        )
        if p.returncode != 0:
            raise RuntimeError(
                f"{script} rc={p.returncode}\nstderr: {p.stderr[-2000:]}")
        for ln in reversed(p.stdout.strip().splitlines()):
            try:
                return json.loads(ln)
            except json.JSONDecodeError:
                continue
        raise RuntimeError(f"{script} produced no JSON: {p.stdout[-500:]}")

    try:
        commit_range_done = [warm]
        for h in range(1, warm + 1):
            commit(h)

        # boot the initial fleet in parallel, wait until every replica's
        # readiness probe reports 200 before aiming load at it
        fleet = [start_replica(f"rep-{i}") for i in range(n_replicas)]
        for box in fleet:
            finish_replica(box)
        for box in fleet:
            box["ready_s"] = wait_ready(box)
        endpoints = ",".join(box["ep"] for box in fleet)
        print(f"  city-replicated: {n_replicas} replicas ready on "
              f"[{endpoints}], core at {core_url}", file=sys.stderr)

        # producer: pace the remaining heights across the load window
        stop_prod = threading.Event()
        prod_errors: list = []

        def producer():
            interval = (dur * 0.85) / max(1, n_blocks - warm)
            try:
                for h in range(warm + 1, n_blocks + 1):
                    commit(h)
                    commit_range_done[0] = h
                    if stop_prod.wait(interval):
                        break
                # drain any heights left if the window closed early
                for h in range(commit_range_done[0] + 1, n_blocks + 1):
                    commit(h)
                    commit_range_done[0] = h
            except Exception as e:  # noqa: BLE001 — surfaced below
                prod_errors.append(repr(e))

        n_light = 500 if QUICK else 10000
        n_das = 100 if QUICK else 1000
        legs = {
            "light": lambda: child("lightload.py", [
                "--endpoints", endpoints, "--clients", str(n_light),
                "--duration", str(dur), "--workers", "4"]),
            "das": lambda: child("dasload.py", [
                "--endpoints", endpoints, "--clients", str(n_das),
                "--duration", str(dur), "--data-shards", "4",
                "--parity-shards", "4"]),
        }
        results: dict = {}
        errors: dict = {}

        def run(name, fn):
            try:
                results[name] = fn()
            except Exception as e:  # noqa: BLE001 — surfaced below
                errors[name] = repr(e)

        prod_t = threading.Thread(target=producer, daemon=True)
        threads = [threading.Thread(target=run, args=(n, fn))
                   for n, fn in legs.items()]
        t0 = time.perf_counter()
        prod_t.start()
        for t in threads:
            t.start()

        # conductor: the load children take ~dur once their interpreter
        # is up; run the two disruption legs against wall-clock offsets
        # from load start
        time.sleep(dur * 0.30)
        boot = start_replica("rep-boot")  # mid-run snapshot bootstrap
        boot_spawned_at = commit_range_done[0]

        time.sleep(dur * 0.25)
        killed = fleet[0]
        killed["proc"].send_signal(signal.SIGTERM)  # failover leg
        killed["proc"].wait(timeout=60)

        # forwarded admission: signed txs into the surviving replicas'
        # own pipelines, landing in the CORE mempool
        survivors = fleet[1:]
        fwd_sent = 16
        fwd_accepted = 0
        priv = Ed25519PrivKey.generate()
        fwd_clients = [HTTPClient(box["url"], timeout=10)
                       for box in survivors]
        for i in range(fwd_sent):
            tx = wrap_signed_tx(priv, b"city-replicated tx %d" % i)
            r = fwd_clients[i % len(fwd_clients)].broadcast_tx_sync(
                tx=tx.hex())
            if int(r.get("code", 1)) == 0:
                fwd_accepted += 1

        for t in threads:
            t.join()
        stop_prod.set()
        prod_t.join(timeout=60)
        combined_wall = time.perf_counter() - t0
        assert not errors, f"city-replicated legs failed: {errors}"
        assert not prod_errors, f"producer failed: {prod_errors}"
        light_res, das_res = results["light"], results["das"]

        # the mid-run joiner: address report + readiness can land after
        # the load window on a starved host — what matters is that it
        # bootstrapped from a snapshot taken mid-run and caught up
        finish_replica(boot)
        boot["ready_s"] = wait_ready(boot)
        boot_st = wait_applied(boot["url"], n_blocks)
        serving = survivors + [boot]
        for box in serving:
            box["status"] = wait_applied(box["url"], n_blocks)

        # --- correctness gates: asserted unconditionally ---------------
        assert light_res["stream_lines"] > 0, "no stream deliveries"
        assert (light_res["stream_verified"]
                == light_res["stream_lines"]), (
            "a replica-served stream line failed client verification")
        assert light_res["gaps"] == 0 and das_res["stream_gaps"] == 0, (
            f"delivery gaps through failover: light={light_res['gaps']} "
            f"das={das_res['stream_gaps']}")
        assert light_res["dups"] == 0 and das_res["stream_dups"] == 0, (
            "cursor resume replayed duplicate heights")
        total_failovers = (light_res["failovers"]
                           + das_res["stream_failovers"])
        assert total_failovers >= 1, (
            "the kill-one-replica leg never forced a failover")
        assert light_res["diff_mismatches"] == 0, (
            f"{light_res['diff_mismatches']} cross-replica proof "
            "mismatches")
        assert killed["proc"].returncode is not None, (
            "killed replica did not exit")
        assert das_res["heights_sampled"] >= 1, "DA fleet sampled nothing"
        assert das_res["samples_ok"] > 0, "no DA sample verified"
        assert int(boot_st["snapshot_height"]) > warm, (
            f"joiner snapshot at {boot_st['snapshot_height']} — not a "
            "mid-run bootstrap")
        assert int(boot_st["gaps"]) == 0, boot_st
        assert fwd_accepted == fwd_sent, (
            f"only {fwd_accepted}/{fwd_sent} forwarded txs accepted")
        assert len(mem.txs) == fwd_sent, (
            f"core mempool got {len(mem.txs)}/{fwd_sent} forwarded txs")

        # replica/core byte-identity differential on the survivors
        hc = HTTPClient(core_url, timeout=10)
        diff_checks = 0
        diff_heights = sorted({1, warm, n_blocks // 2, n_blocks})
        for box in serving:
            rc = HTTPClient(box["url"], timeout=10)
            for h in diff_heights:
                assert (hc.light_mmr_proof(height=str(h))
                        == rc.light_mmr_proof(height=str(h))), (
                    box["name"], h)
                diff_checks += 1
            for h, i in ((warm, 0), (n_blocks, 3)):
                assert (hc.da_sample(height=str(h), index=str(i))
                        == rc.da_sample(height=str(h), index=str(i))), (
                    box["name"], h, i)
                diff_checks += 1
            assert (hc.light_status()["mmr_root"]
                    == rc.light_status()["mmr_root"]), box["name"]
            diff_checks += 1

        samples_per_sec = round(
            das_res["samples_total"] / max(das_res["duration_s"], 1e-9),
            1)
        gate = {
            "zero_delivery_gaps": True,
            "byte_identical_serving": True,
            "bootstrap_replica_caught_up": True,
            "forwarded_admission": True,
            "min_deliveries_per_sec": 2000.0,
            "max_proof_p99_ms": 50.0,
            "min_samples_per_sec": 500.0,
            "all_clients_confident": True,
            "max_bootstrap_ready_s": 60.0,
        }
        cores = os.cpu_count() or 1
        if cores < 2:
            gate["asserted"] = False
            gate["reason"] = (
                f"starved host: {cores} core(s) — the core, "
                f"{n_replicas + 1} replica processes and two load "
                "children time-share the core, so throughput/latency "
                "thresholds, sampling confidence and bootstrap wall "
                "time gate on OS scheduling, not on the code; "
                "correctness gates (zero delivery gaps across the "
                "kill-one-replica leg, cursor resume without dups, "
                f"{diff_checks} replica/core byte-identity checks, "
                "mid-run snapshot bootstrap catch-up, forwarded "
                "admission) asserted anyway. Re-run `python "
                "tools/workloads.py --city --replicas "
                f"{n_replicas}` on a >=2-core host")
        else:
            gate["asserted"] = True
            assert (light_res["deliveries_per_sec"]
                    >= gate["min_deliveries_per_sec"]), (
                f"{light_res['deliveries_per_sec']} deliveries/s < "
                f"{gate['min_deliveries_per_sec']}")
            assert (light_res["proof_p99_ms"]
                    <= gate["max_proof_p99_ms"]), (
                f"proof p99 {light_res['proof_p99_ms']} ms > "
                f"{gate['max_proof_p99_ms']} ms")
            assert samples_per_sec >= gate["min_samples_per_sec"], (
                f"{samples_per_sec} samples/s < "
                f"{gate['min_samples_per_sec']}")
            assert (das_res["clients_confident_min"]
                    == das_res["clients"]), (
                f"only {das_res['clients_confident_min']}/"
                f"{das_res['clients']} sampling clients confident")
            assert boot["ready_s"] <= gate["max_bootstrap_ready_s"], (
                f"joiner took {boot['ready_s']:.1f} s to readiness > "
                f"{gate['max_bootstrap_ready_s']} s")

        print(f"  city-replicated: {combined_wall:.1f} s wall — "
              f"{light_res['deliveries_per_sec']} deliveries/s over "
              f"{n_replicas} replicas, {total_failovers} failovers with "
              f"0 gaps, joiner ready in {boot['ready_s']:.1f} s, "
              f"{diff_checks} byte-identity checks", file=sys.stderr)

        return {
            "metric": "city_replicated",
            "replicas": n_replicas,
            "duration_s": dur,
            "combined_wall_s": round(combined_wall, 1),
            "blocks": n_blocks,
            "light": {
                "clients": light_res["clients"],
                "stream_groups": light_res["stream_groups"],
                "stream_lines": light_res["stream_lines"],
                "deliveries_per_sec": light_res["deliveries_per_sec"],
                "proof_p99_ms": light_res["proof_p99_ms"],
                "gaps": light_res["gaps"],
                "dups": light_res["dups"],
                "failovers": light_res["failovers"],
                "diff_checks": light_res["diff_checks"],
                "diff_mismatches": light_res["diff_mismatches"],
            },
            "das": {
                "clients": das_res["clients"],
                "heights_sampled": das_res["heights_sampled"],
                "samples_total": das_res["samples_total"],
                "samples_per_sec": samples_per_sec,
                "clients_confident_min":
                    das_res["clients_confident_min"],
                "stream_gaps": das_res["stream_gaps"],
                "stream_failovers": das_res["stream_failovers"],
                "client_failovers": das_res["client_failovers"],
            },
            "failover": {
                "killed": killed["name"],
                "total_failovers": total_failovers,
                "delivery_gaps": 0,
            },
            "bootstrap": {
                "name": boot["name"],
                "spawned_at_height": boot_spawned_at,
                "snapshot_height": int(boot_st["snapshot_height"]),
                "applied_height": int(boot_st["applied_height"]),
                "ready_s": round(boot["ready_s"], 1),
            },
            "forwarding": {
                "sent": fwd_sent,
                "accepted": fwd_accepted,
                "core_received": len(mem.txs),
            },
            "diff_checks": diff_checks,
            "gate": gate,
        }
    finally:
        for p in procs:
            if p.poll() is None:
                p.send_signal(signal.SIGTERM)
        for p in procs:
            try:
                p.wait(timeout=30)
            except subprocess.TimeoutExpired:
                p.kill()
        srv.stop()
        feed.stop()
        light.stop()
        da.stop()


def main():
    if "--multichip-child" in sys.argv:
        i = sys.argv.index("--multichip-child")
        _emit(multichip_child(int(sys.argv[i + 1]), int(sys.argv[i + 2])))
        return
    if "--two-backend-child" in sys.argv:
        _emit(two_backend_child())
        return
    if "--two-backend-cpu-child" in sys.argv:
        _emit(two_backend_cpu_child())
        return
    if "--multichip" in sys.argv:
        rec = bench_multichip()
        _emit(rec)
        return
    if "--two-backend" in sys.argv:
        out = bench_two_backend()
        for rec in out:
            _emit(rec)
        _merge_workloads(out)
        return
    if "--ingest" in sys.argv:
        rec = bench_ingest_sustained_load()
        _emit(rec)
        _merge_workloads([rec])
        return
    if "--light" in sys.argv:
        rec = bench_light_stream_fanout()
        _emit(rec)
        _merge_workloads([rec])
        return
    if "--bls" in sys.argv:
        rec = bench_megacommit_bls()
        _emit(rec)
        _merge_workloads([rec])
        return
    if "--das" in sys.argv:
        rec = bench_das_pc() if "--pc" in sys.argv else bench_das_fleet()
        _emit(rec)
        _merge_workloads([rec])
        return
    if "--certnative" in sys.argv:
        rec = bench_certnative()
        _emit(rec)
        _merge_workloads([rec])
        return
    if "--watchtower" in sys.argv:
        rec = bench_watchtower()
        _emit(rec)
        _merge_workloads([rec])
        return
    if "--city" in sys.argv:
        if "--replicas" in sys.argv:
            i = sys.argv.index("--replicas")
            rec = bench_city_replicated(int(sys.argv[i + 1]))
        else:
            rec = bench_city()
        _emit(rec)
        _merge_workloads([rec])
        return
    northstar = "--northstar" in sys.argv
    benches = (
        (bench_replay_northstar, bench_megacommit_mixed)
        if northstar
        else (bench_verify_commit, bench_light_stream, bench_replay)
    )
    out = []
    for fn in benches:
        rec = fn()
        print(json.dumps(rec))
        out.append(rec)
    _merge_workloads(out)


def _merge_workloads(out):
    path = os.path.join(os.path.dirname(__file__), "..", "WORKLOADS.json")
    existing = []
    if os.path.exists(path):
        with open(path) as f:
            existing = [json.loads(ln) for ln in f if ln.strip()]
    merged = {r["metric"]: r for r in existing}
    for rec in out:
        merged[rec["metric"]] = rec
    with open(path, "w") as f:
        for rec in merged.values():
            f.write(json.dumps(rec) + "\n")


if __name__ == "__main__":
    main()
