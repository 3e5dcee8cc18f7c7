"""Ed25519 batch-verify throughput on one TPU chip.

Prints ONE JSON line: {"metric", "value", "unit", "device", ...}. The
number is a device number, so the run refuses to start (exit 1, nothing
printed on stdout) when jax's platform is not "tpu"; the line names the
device it ran on. Reach the chip through the builder's chip tool; the
quickest proof that the data plane starts there is chip_smoke.py.

Workload mirrors BASELINE.json config #5's scale: a sustained stream of
10_000-signature commits (10k-validator mega-commits) with distinct
(pubkey, msg, sig) triples and ~100-byte canonical-vote-sized messages.
Methodology matches the replay pipeline (SURVEY §3.3): all commits'
batches are submitted back-to-back (the runtime queues them; host
packing of batch i+1 overlaps device execution of batch i) and resolved
with ONE device→host transfer of the per-batch all-ok scalars — the
bitmap never transfers on the happy path. The wire format is chosen by
the measured-time dispatch (crypto/ed25519.py): on this link, R||S||k
at 96 B/lane with challenge scalars hashed natively on the host (8-way
AVX-512 multi-buffer SHA-512) beats the 73 B/lane on-device-hash path;
validator-set points live decompressed on device either way (replay
verifies the same set every height). This is exactly how
block-sync replay consumes the verifier; the number is sustained
pipeline throughput, not single-shot latency. One warm-up pass at full
pipeline depth (compiles, checks correctness) and one timed pass: how
many passes a claim needs, and what spread they show, is the
benchmark's business (ROADMAP S0), not this script's.

Baseline derivation (pinned, round 5). The reference's CPU batch
verifier is curve25519-voi's Pippenger batch path (reference
crypto/ed25519/bench_test.go:30 BenchmarkVerifyBatch, go.mod pins
oasisprotocol/curve25519-voi v0.0.0-20220708). The Go toolchain is not
in this image and egress is zero, so the voi harness cannot be re-run
or its published output fetched; the baseline is instead derived from
a MEASURED quantity plus one explicit assumption, both reported in the
JSON so the ratio is traceable:

  * measured: this host's single-core batch-verify rate through the
    repo's AVX-512 IFMA engine (radix-2^52 vpmadd52, Pippenger c=7 —
    the same algorithm class as voi's AVX2 backend with a wider
    vector unit, i.e. a generous stand-in for one voi core), sampled
    fresh every bench run (`local_cpu_sigs_per_sec`, typically
    ~115-125k sigs/s on this Icelake-server-class core at 1024-sig
    batches = ~8.4 us/sig);
  * assumed: the reference deployment verifies on BASELINE_CORES = 8
    physical cores (a mainstream server allocation; voi's batch
    verifier parallelizes across cores in the reference's usage).

  CPU_BASELINE_SIGS_PER_SEC = 1.0e6 ~= 8 cores x 125k sigs/s/core is
  kept as the fixed headline denominator for round-over-round
  comparability (it is the FAST end: 1.0 us/sig amortized). The JSON
  additionally emits `vs_local_cpu` (chip vs one measured core) and
  `vs_local_cpu_x8` (chip vs 8 measured cores — the fully-measured
  version of the headline ratio, no constants involved).
"""

import json
import sys
import time

CPU_BASELINE_SIGS_PER_SEC = 1.0e6  # = BASELINE_CORES x ~125k measured sigs/s/core (docstring)
BASELINE_CORES = 8
N_SIGS = 10_000
N_COMMITS = 32  # pipeline depth (amortizes the fixed D2H round trip; measured +5% over 16)


def main():
    import jax

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    if dev.platform != "tpu":
        print(f"bench.py measures a TPU; jax found {device}",
              file=sys.stderr)
        return 1

    from cometbft_tpu.crypto.ed25519 import (
        Ed25519BatchVerifier,
        Ed25519PubKey,
        collect_pending,
    )
    from cometbft_tpu.crypto.testgen import generate_signed_batch

    # Distinct keys + messages for every lane, generated with the device
    # fixed-base ladder (host signing would dominate setup time). Two
    # distinct commits alternated so consecutive batches never share
    # data. Messages are canonical-vote shaped (shared prefix/suffix,
    # per-vote timestamp bytes) — the shape replay actually verifies —
    # so the wire dispatch sees the same structure production does.
    commits = [
        generate_signed_batch(N_SIGS, seed=s, msg_len=100, vote_shaped=True)
        for s in (0, 1)
    ]

    # Verifiers are built once: commit contents are packed per submit()
    # (vectorized numpy), matching how replay reuses a verifier per
    # commit without reconstructing per-item state.
    verifiers = []
    for i in range(N_COMMITS):
        bv = Ed25519BatchVerifier(backend="tpu")
        for pub, msg, sig in commits[i % 2]:
            bv.add(Ed25519PubKey(pub), msg, sig)
        verifiers.append(bv)

    # Warmup: compile the bucket kernel + the summary stack, and verify
    # correctness once at full pipeline depth.
    res = collect_pending([verifiers[i].submit() for i in range(N_COMMITS)])
    assert all(ok for ok, _ in res), "bench warmup must verify"

    t0 = time.perf_counter()
    pending = [verifiers[i].submit() for i in range(N_COMMITS)]
    results = collect_pending(pending)
    dt = time.perf_counter() - t0
    assert all(ok for ok, _ in results), "all bench batches must verify"
    best = N_COMMITS * N_SIGS / dt

    from cometbft_tpu.crypto import ed25519 as _e
    from cometbft_tpu.crypto import native as _native

    # pin the local CPU baseline: this host's own best native batch
    # rate, measured like the TPU number (warmup, then best of 3) so
    # the vs_local_cpu ratio compares best against best
    local_cpu = 0.0
    if _native.available():
        sample = commits[0][:4096]
        if _native.batch_verify(sample):  # warmup: tables, caches, pages
            best_cpu = None
            for _ in range(3):
                t0 = time.perf_counter()
                _native.batch_verify(sample)
                dt = time.perf_counter() - t0
                best_cpu = dt if best_cpu is None else min(best_cpu, dt)
            local_cpu = len(sample) / best_cpu

    # North-star ceiling accounting (VERDICT Next #4): the modeled
    # per-stage floors behind the dispatch, plus what each path could
    # deliver if its binding stage were the only cost — and the 8-chip
    # extrapolation where the device term scales but this host's wire
    # and pack stages are shared and do not.
    model = _e.dispatch_model(N_SIGS, _e._bucket(N_SIGS))

    def _cap(stages, chips=1):
        bound = max(stages["wire"], stages["host"], stages["device"] / chips)
        return round(N_SIGS / bound, 1)

    ceiling = {
        "link_mbps": round(model["link_mbps"], 1),
        "device_us_per_sig": {
            "ladder": _e._DEV_LADDER_US, "rlc": _e._DEV_RLC_US,
        },
        "host_us_per_sig": {
            "ladder": round(model["host_terms"]["ladder_us"], 3),
            "rlc": round(model["host_terms"]["rlc_us"], 3),
            "rlc_threads": model["host_terms"]["rlc_threads"],
            "calibrated": model["host_terms"]["calibrated"],
        },
        "wire_bytes_per_lane": {
            "ladder": _e._WIRE_LADDER_B, "rlc": _e._WIRE_RLC_B,
        },
        "sigs_per_sec_cap": {
            "ladder": _cap(model["ladder"]),
            "rlc": _cap(model["rlc"]),
            "selected": "rlc" if model["t_rlc"] < model["t_ladder"]
            else "ladder",
        },
        "sigs_per_sec_cap_8chip": {
            "ladder": _cap(model["ladder"], chips=8),
            "rlc": _cap(model["rlc"], chips=8),
        },
    }
    if "mesh" in model:
        # live mesh term (parallel/mesh engine active): unlike the
        # 8-chip extrapolation above, this uses the CALIBRATED shard
        # H2D + collective costs, so the cap reflects what dispatch
        # actually compares against the single-chip paths
        ceiling["sigs_per_sec_cap_mesh"] = {
            "mesh": _cap(model["mesh"]),
            "n_devices": model["n_devices"],
        }

    # snapshot of the run's crypto instrumentation: which dispatch paths
    # fired, the observed batch-size distribution, and per-path verify
    # latency — the same series a live node exports on /metrics
    from cometbft_tpu.utils.metrics import crypto_metrics

    cm = crypto_metrics()
    metrics_snapshot = {
        "path_selected_total": {
            (k[0] if k else ""): v
            for k, v in cm.path_selected_total.values().items()
        },
        "batch_size": {
            (",".join(k) if k else ""): v
            for k, v in cm.batch_size.snapshot().items()
        },
        "verify_seconds": {
            (k[0] if k else ""): {
                "count": v["count"], "sum_s": round(v["sum"], 4)
            }
            for k, v in cm.verify_seconds.snapshot().items()
        },
    }

    print(
        json.dumps(
            {
                "metric": "ed25519_batch_verify_throughput_10k",
                "value": round(best, 1),
                "unit": "sigs/sec/chip",
                "device": device,
                "vs_baseline": round(best / CPU_BASELINE_SIGS_PER_SEC, 4),
                "baseline_derivation": (
                    f"{BASELINE_CORES} cores x ~125k sigs/s/core measured "
                    "locally (AVX-512 IFMA, 1024-sig Pippenger batches); "
                    "see bench.py docstring"
                ),
                "wire_bytes_per_lane": _e._LAST_WIRE_B_PER_LANE,
                "local_cpu_sigs_per_sec": round(local_cpu, 1),
                "vs_local_cpu": (
                    round(best / local_cpu, 3) if local_cpu else None
                ),
                "vs_local_cpu_x8": (
                    round(best / (local_cpu * BASELINE_CORES), 4)
                    if local_cpu else None
                ),
                "local_cpu_engine": _native.engine(),
                "ceiling": ceiling,
                "crypto_metrics": metrics_snapshot,
            }
        )
    )


if __name__ == "__main__":
    sys.exit(main())
