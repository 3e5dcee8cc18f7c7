"""Batch-size-aware backend dispatch: native C++ RLC for commit-sized
batches, the per-lane TPU kernel for mega-batches and for blame, the TPU
RLC/MSM engine a candidate that the v5e's measured terms never pick
(reference types/validation.go:26-53 + crypto/batch dispatch; sizing
policy is ours — the reference has one CPU backend, we have three
engines behind one seam)."""

import numpy as np
import pytest

from cometbft_tpu.crypto import ed25519_ref as ref
from cometbft_tpu.crypto import native
from cometbft_tpu.crypto.ed25519 import (
    DonePending,
    Ed25519BatchVerifier,
    Ed25519PubKey,
)

rng = np.random.default_rng(11)


def _signed(n, msg_len=80):
    out = []
    for _ in range(n):
        seed = bytes(rng.bytes(32))
        msg = bytes(rng.bytes(msg_len))
        out.append((ref.pubkey_from_seed(seed), msg, ref.sign(seed, msg)))
    return out


needs_native = pytest.mark.skipif(
    not native.available(), reason="no C++ toolchain"
)


@needs_native
def test_small_batch_routes_to_native():
    items = _signed(16)
    bv = Ed25519BatchVerifier(backend="tpu")
    for p, m, s in items:
        bv.add(Ed25519PubKey(p), m, s)
    pending = bv.submit()
    assert isinstance(pending, DonePending), "small batch must use native"
    ok, bits = pending.result()
    assert ok and all(bits) and len(bits) == 16


@needs_native
def test_native_batch_blames_individual_failures():
    items = _signed(12)
    bv = Ed25519BatchVerifier(backend="tpu")
    bad = {2, 9}
    for i, (p, m, s) in enumerate(items):
        if i in bad:
            s = bytes([s[0] ^ 1]) + s[1:]
        bv.add(Ed25519PubKey(p), m, s)
    ok, bits = bv.submit().result()
    assert not ok
    assert [not b for b in bits] == [i in bad for i in range(12)]


@needs_native
def test_native_batch_rejects_noncanonical_s():
    (pub, msg, sig), = _signed(1)
    s = int.from_bytes(sig[32:], "little")
    mal = sig[:32] + (s + ref.L).to_bytes(32, "little")
    bv = Ed25519BatchVerifier(backend="tpu")
    bv.add(Ed25519PubKey(pub), msg, mal)
    for p, m, sg in _signed(3):
        bv.add(Ed25519PubKey(p), m, sg)
    ok, bits = bv.submit().result()
    assert not ok and bits == [False, True, True, True]


@needs_native
def test_native_batch_verify_direct():
    items = _signed(50, msg_len=200)
    assert native.batch_verify(items)
    p, m, s = items[7]
    items[7] = (p, m, bytes([s[0] ^ 1]) + s[1:])
    assert not native.batch_verify(items)


def test_native_limit_tracks_accelerator_presence(monkeypatch):
    """With a real accelerator, NATIVE_MAX caps the native engine and
    mega-batches earn the device round trip; on CPU-only jax the
    "device" is this same host emulating the graph, so every size
    stays native. NATIVE_MAX = 0 force-disables native either way (the
    seam the device-path tests use)."""
    from cometbft_tpu.crypto import ed25519 as e

    monkeypatch.setattr(e, "_ACCEL_BACKED", True)
    assert e._native_limit(5000) == e.NATIVE_MAX
    assert e._native_limit(100) == e.NATIVE_MAX
    monkeypatch.setattr(e, "_ACCEL_BACKED", False)
    assert e._native_limit(5000) == 5001
    monkeypatch.setattr(e, "NATIVE_MAX", 0)
    assert e._native_limit(5000) == 0
    monkeypatch.setattr(e, "_ACCEL_BACKED", True)
    assert e._native_limit(5000) == 0


@needs_native
def test_no_accel_keeps_mega_batches_native(monkeypatch):
    """A batch past NATIVE_MAX must still route to the native engine
    when no accelerator backs jax — the emulated device paths lose by
    orders of magnitude and their mega-shape XLA compiles take
    minutes."""
    from cometbft_tpu.crypto import ed25519 as e

    monkeypatch.setattr(e, "_ACCEL_BACKED", False)
    n = e.NATIVE_MAX + 40
    items = _signed(n, msg_len=40)
    bv = Ed25519BatchVerifier(backend="tpu")
    for p, m, s in items:
        bv.add(Ed25519PubKey(p), m, s)
    pending = bv.submit()
    assert isinstance(pending, DonePending), "mega batch must stay native"
    ok, bits = pending.result()
    assert ok and all(bits) and len(bits) == n


def test_expand_stream_device_matches_host():
    """The on-device stream expansion must reproduce the host reference
    expansion exactly (cheap jit; the full MSM e2e below is TPU-only
    because the 19968-lane graph takes minutes to compile on CPU)."""
    import jax

    from cometbft_tpu.crypto import rlc
    from cometbft_tpu.ops.msm import expand_stream

    items = _signed(7)
    prep = rlc.prepare(items, np.zeros(7, bool), 64)
    s_pad = -(-prep["s_rounds"] // 8) * 8
    want_idx, want_neg = rlc.expand_stream_host(prep, s_pad)
    got_idx, got_neg = jax.jit(expand_stream, static_argnames="s_rounds")(
        prep["stream"], prep["stream_neg"], prep["counts"], s_rounds=s_pad
    )
    assert (np.asarray(got_idx) == want_idx).all()
    assert (np.asarray(got_neg) == want_neg).all()


@pytest.mark.skipif(
    "COMETBFT_RLC_E2E" not in __import__("os").environ,
    reason="multi-minute XLA compile on CPU; run with COMETBFT_RLC_E2E=1 "
    "(validated on the real TPU, where the pallas path compiles fast)",
)
def test_rlc_device_path_end_to_end(monkeypatch):
    """Force the dispatch through the device RLC/MSM engine (compact
    stream wire format + on-device gather-table expansion) and check
    both the all-valid verdict and the bad-lane fallback blame."""
    from cometbft_tpu.crypto import ed25519 as e

    monkeypatch.setattr(e, "NATIVE_MAX", 0)
    monkeypatch.setattr(e, "RLC_MIN", 1)
    monkeypatch.setattr(e, "_rlc_beats_ladder", lambda n, b: True)
    items = _signed(20, msg_len=48)
    bv = e.Ed25519BatchVerifier(backend="tpu")
    for p, m, s in items:
        bv.add(e.Ed25519PubKey(p), m, s)
    pending = bv.submit()
    assert isinstance(pending, e.PendingRLC), "dispatch must pick RLC"
    ok, bits = pending.result()
    assert ok and all(bits) and len(bits) == 20

    bv2 = e.Ed25519BatchVerifier(backend="tpu")
    for i, (p, m, s) in enumerate(items):
        if i == 3:
            s = bytes([s[0] ^ 1]) + s[1:]
        bv2.add(e.Ed25519PubKey(p), m, s)
    ok2, bits2 = bv2.submit().result()
    assert not ok2
    assert [not b for b in bits2] == [i == 3 for i in range(20)]


def test_rlc_host_layout_roundtrip():
    """The host bucket layout must place every nonzero digit exactly
    once with the pre-negated sign (pure-numpy check, no device)."""
    from cometbft_tpu.crypto import rlc

    items = _signed(5)
    prep = rlc.prepare(items, np.zeros(5, bool), 64)
    assert prep is not None
    idx, neg = rlc.expand_stream_host(prep)  # (S, WK)
    assert idx.shape == (prep["s_rounds"], rlc.WK)
    assert prep["s_rounds"] <= rlc.slot_depth(64)
    sentinel = 2 * 64
    # each real point index appears <= total windows times
    used = idx[idx != sentinel]
    assert used.size > 0
    assert ((0 <= used) & (used < sentinel)).all()
    # R points (idx < 64) live only in z regions: lane = region*K + b
    z_regions = {rlc.region_of_z(w) for w in range(rlc.Z_WINDOWS)}
    lanes = np.nonzero((idx != sentinel) & (idx < 64))[1]
    assert set(np.unique(lanes // rlc.K_BUCKETS)) <= z_regions
    # sentinel slots carry no sign flips
    assert not neg[idx == sentinel].any()


def test_rlc_host_layout_skips_precheck_failures():
    from cometbft_tpu.crypto import rlc

    items = _signed(4)
    skip = np.array([False, True, False, False])
    prep = rlc.prepare(items, skip, 64)
    idx, _ = rlc.expand_stream_host(prep)
    used = idx[idx != 128]
    # lane 1's R (idx 1) and A (idx 64+1) never contribute
    assert not np.isin(used, [1, 65]).any()


def test_rlc_layout_msm_semantics():
    """Exact-integer emulation of the device MSM over the host layout:
    gather tables + weight table + c digits must reproduce
    [c]B + sum [z_i](-R_i) + sum [m_i](-A_i) == identity for valid
    signatures (the oracle's point arithmetic stands in for the TPU)."""
    from cometbft_tpu.crypto import rlc

    items = _signed(9, msg_len=64)
    bucket = 64
    prep = rlc.prepare(items, np.zeros(len(items), bool), bucket)
    assert prep is not None
    idx, negf = rlc.expand_stream_host(prep)  # (S, WK)
    wt = prep["weights"]          # (W, K)

    # point table: R_i at 0..n-1, A_i at bucket..bucket+n-1 — the gather
    # digits are PRE-negated host-side, so the raw points go in as-is
    ident = (0, 1, 1, 0)
    table = {}
    for i, (p, m, s) in enumerate(items):
        table[i] = ref._to_ext(ref._decode_point(s[:32], zip215=True))
        table[bucket + i] = ref._to_ext(ref._decode_point(p, zip215=True))
    sentinel = 2 * bucket

    # lane accumulation
    acc = [ident] * rlc.WK
    for s_i in range(idx.shape[0]):
        for lane in range(rlc.WK):
            j = idx[s_i, lane]
            if j == sentinel:
                continue
            pt = table[int(j)]
            if negf[s_i, lane]:
                pt = ref._ext_neg(pt)
            acc[lane] = ref._ext_add(acc[lane], pt)

    # weighted region reduction + Horner over regions: region r's weight
    # power comes from its window (region_of_m / region_of_z inverse)
    window_of = {}
    for w in range(rlc.N_WINDOWS):
        window_of[rlc.region_of_m(w)] = w
    for w in range(rlc.Z_WINDOWS):
        window_of[rlc.region_of_z(w)] = w
    total = ident
    for r in range(rlc.N_REGIONS):
        win = ident
        for k in range(rlc.K_BUCKETS):
            wgt = int(wt[r, k])
            if wgt:
                win = ref._ext_add(
                    win, ref._ext_scalar_mul(wgt, acc[r * rlc.K_BUCKETS + k])
                )
        total = ref._ext_add(
            total, ref._ext_scalar_mul(1 << (10 * window_of[r]), win)
        )

    # add [c]B: recover c from digits
    c = 0
    for i, d in enumerate(prep["c_digits"][:, 0]):
        c += int(d) << (4 * i)
    c %= ref.L
    gx = 15112221349535400772501151409588531511454012693041857206046113283949847762202
    gy = 46316835694926478169428394003475163141307993866256225615783033603165251855960
    Bpt = ref._to_ext((gx, gy))
    total = ref._ext_add(total, ref._ext_scalar_mul(c, Bpt))
    total = ref._ext_scalar_mul(8, total)
    assert ref._ext_is_identity(total), "layout must satisfy the RLC equation"


def test_delta_wire_path_end_to_end(monkeypatch):
    """Structured messages (shared prefix/suffix, per-lane mid) route
    through the delta wire path: R||S + ~8 delta bytes per lane, message
    rebuilt + hashed on device. Verify both verdicts and blame."""
    from cometbft_tpu.crypto import ed25519 as e

    monkeypatch.setattr(e, "NATIVE_MAX", 0)
    monkeypatch.setattr(e, "DELTA_MIN", 1)
    # pin the wire-format choice: this test exercises the delta path
    # itself, not the measured-time dispatch between delta/prehashed
    monkeypatch.setattr(e, "_delta_beats_prehashed", lambda n, b: True)
    pfx = b"\x08\x02\x11" + bytes(range(60))  # vote-ish shared prefix
    sfx = b"2\x0bbench-chain"
    items = []
    for i in range(24):
        seed = bytes(rng.bytes(32))
        msg = pfx + i.to_bytes(6, "big") + sfx  # 6-byte per-lane mid
        items.append((ref.pubkey_from_seed(seed), msg, None, seed))
    items = [
        (p, m, __import__("cometbft_tpu.crypto.ed25519_ref", fromlist=["x"]).sign(s, m))
        for (p, m, _, s) in items
    ]
    bv = e.Ed25519BatchVerifier(backend="tpu")
    for p, m, s in items:
        bv.add(e.Ed25519PubKey(p), m, s)
    pending = bv.submit()
    ok, bits = pending.result()
    assert ok and all(bits) and len(bits) == 24
    assert e._LAST_WIRE_B_PER_LANE < 80, e._LAST_WIRE_B_PER_LANE

    # detection result is memoized; a bad signature still gets blamed
    bv2 = e.Ed25519BatchVerifier(backend="tpu")
    for i, (p, m, s) in enumerate(items):
        if i == 5:
            s = bytes([s[0] ^ 1]) + s[1:]
        bv2.add(e.Ed25519PubKey(p), m, s)
    ok2, bits2 = bv2.submit().result()
    assert not ok2 and [not b for b in bits2] == [i == 5 for i in range(24)]


def test_delta_detection_rejects_random_messages():
    from cometbft_tpu.crypto.ed25519 import _detect_delta

    items = _signed(8, msg_len=100)
    assert _detect_delta(items) is None  # no shared structure


def test_delta_detection_ragged_lengths(monkeypatch):
    """Variable-length mids (varint timestamps) still verify through the
    delta path."""
    from cometbft_tpu.crypto import ed25519 as e

    monkeypatch.setattr(e, "NATIVE_MAX", 0)
    monkeypatch.setattr(e, "DELTA_MIN", 1)
    monkeypatch.setattr(e, "_delta_beats_prehashed", lambda n, b: True)
    pfx = bytes(rng.bytes(70))
    sfx = bytes(rng.bytes(14))
    items = []
    for i in range(12):
        seed = bytes(rng.bytes(32))
        mid = bytes(rng.bytes(5 + (i % 4)))  # 5..8 byte mids
        msg = pfx + mid + sfx
        items.append((ref.pubkey_from_seed(seed), msg, ref.sign(seed, msg)))
    bv = e.Ed25519BatchVerifier(backend="tpu")
    for p, m, s in items:
        bv.add(e.Ed25519PubKey(p), m, s)
    ok, bits = bv.submit().result()
    assert ok and all(bits)


def _pin_model(monkeypatch, link_mbps, rlc_us, ladder_us=1.6):
    from cometbft_tpu.crypto import ed25519 as e

    monkeypatch.setattr(e, "_LINK_MBPS", float(link_mbps))
    monkeypatch.setattr(e, "_HOST_TERMS", {
        "ladder_us": float(ladder_us), "rlc_us": float(rlc_us),
        "rlc_threads": 1, "rlc_native": True, "calibrated": True,
    })
    return e


def _binding(stages: dict) -> str:
    return max(stages, key=stages.get)


@pytest.mark.parametrize(
    "link_mbps, rlc_us, n, b, winner, ladder_binds, rlc_binds",
    [
        # the round-6 'Done' case (native packer 1.1 us/sig, fast link):
        # neither host nor wire binds, and the device terms the v5e
        # measures give the batch to the ladder
        pytest.param(1000.0, 1.1, 10000, 10240, "ladder", "device",
                     "device", id="fast_link_native_packer"),
        # numpy packer (20 us/sig): RLC is host-bound at 200 ms, further
        # behind still
        pytest.param(1000.0, 20.0, 10000, 10240, "ladder", "device", "host",
                     id="numpy_host_still_loses"),
        # a 30 MB/s link: the ladder's 96 B/lane wire (32.8 ms) binds it;
        # RLC's 116 B/lane (39.6 ms) is still under its device time
        pytest.param(30.0, 1.1, 10000, 10240, "ladder", "wire", "device",
                     id="30mbps_wire_still_loses"),
        # REAL link probe and REAL first-use calibration on this host (the
        # CPU loopback): whatever they read, wire does not bind and the
        # measured device terms decide
        pytest.param(None, None, 10000, 10240, "ladder", None, None,
                     marks=needs_native,
                     id="loopback_with_real_calibration"),
        # the two batches the benchmark's cells send, at the link the
        # chip's machine probes (1.0-1.9 GB/s)
        pytest.param(1000.0, 1.65, 10000, 10240, "ladder", "device",
                     "device", id="megacommit_10000_of_10240"),
        pytest.param(1000.0, 1.65, 65000, 65536, "ladder", "device",
                     "device", id="catchup_65000_of_65536"),
    ],
)
def test_engine_crossover(monkeypatch, link_mbps, rlc_us, n, b, winner,
                          ladder_binds, rlc_binds):
    """Which engine the stage model gives a mega-batch to, and which stage
    binds each engine. Device terms are the v5e's readings (PR 25): RLC's
    fixed part alone is above the ladder's whole batch, so RLC loses on the
    device at every size, and a slow host or a slow link only adds to it."""
    from cometbft_tpu.crypto import ed25519 as e

    if link_mbps is None:
        monkeypatch.setattr(e, "_HOST_TERMS", None)  # fresh calibration
        assert e._host_terms()["calibrated"]
    else:
        _pin_model(monkeypatch, link_mbps, rlc_us, ladder_us=1.05)
    m = e.dispatch_model(n, b)
    for path, binds in (("ladder", ladder_binds), ("rlc", rlc_binds)):
        if binds is None:  # a loaded host may bind; the loopback never
            assert _binding(m[path]) != "wire"
        else:
            assert _binding(m[path]) == binds
            assert m["t_" + path] == pytest.approx(m[path][binds])
    assert e._rlc_beats_ladder(n, b) == (winner == "rlc")


# device milliseconds a batch, each engine as submit() launches it, warm, from
# the profiler trace (my chip run, PR 25: `python chip_smoke.py --terms`)
CHIP_READINGS_MS = {
    ("ladder", 10000): 20.480,
    ("ladder", 65000): 130.870,
    ("rlc", 10000): 120.192,
    ("rlc", 65000): 278.314,
}


@pytest.mark.parametrize("engine, n", sorted(CHIP_READINGS_MS))
def test_device_terms_reproduce_the_chip_readings(monkeypatch, engine, n):
    """fixed + n * per-lane of each engine is within 15% of what the v5e
    read at the live lane counts of the cells' two buckets."""
    e = _pin_model(monkeypatch, link_mbps=1000.0, rlc_us=1.65)
    model_ms = e.dispatch_model(n, e._bucket(n))[engine]["device"] * 1e3
    assert model_ms == pytest.approx(CHIP_READINGS_MS[engine, n], rel=0.15)


@needs_native
@pytest.mark.parametrize("n", [10000, 65000])
def test_mega_batch_on_an_accelerator_takes_the_ladder(monkeypatch, tmp_path,
                                                       n):
    """The decision itself, as submit() makes it on a host with the chip:
    NATIVE_MAX as shipped, the real model, the real link probe and host
    calibration; only the two jitted ladder programs are stand-ins. A
    mega-batch filled by add_batch is counted and traced under `ladder`,
    never reaches the RLC layout and never expands its columns."""
    import json

    from cometbft_tpu.crypto import ed25519 as e
    from cometbft_tpu.crypto import rlc
    from cometbft_tpu.ops import ed25519_verify as ev
    from cometbft_tpu.utils import trace
    from cometbft_tpu.utils.metrics import crypto_metrics

    def refuse(*_a, **_kw):
        raise AssertionError("a mega-batch reached the RLC engine")

    expanded = []
    monkeypatch.setattr(e, "_ACCEL_BACKED", True)
    monkeypatch.setattr(e, "_mesh_engine", lambda: None)
    monkeypatch.setattr(e, "_A_CACHE", {})
    monkeypatch.setattr(rlc, "prepare", refuse)
    monkeypatch.setattr(e.Ed25519BatchVerifier, "_launch_rlc", refuse)
    monkeypatch.setattr(e.Ed25519BatchVerifier, "_materialize",
                        lambda self: expanded.append(self.count()))
    monkeypatch.setattr(ev, "decompress_pubkeys_jit", lambda a: (a, a))
    monkeypatch.setattr(
        ev, "verify_batch_cached_a_jit",
        lambda ok_a, neg_a, rsk, live: (np.asarray(live), np.asarray(True)))

    r = np.random.default_rng(n)
    sigs = r.integers(0, 256, (n, 64), np.uint8)
    sigs[:, 63] = 0  # S < L on every lane
    bv = e.Ed25519BatchVerifier(backend="tpu")
    bv.add_batch(r.integers(0, 256, (n, 32), np.uint8), sigs,
                 r.integers(0, 256, n * 100, np.uint8).tobytes(),
                 np.full(n, 100, np.uint32))

    sink = str(tmp_path / "spans.jsonl")
    trace.configure(sink)
    try:
        pending = bv.submit()
        ok, bits = pending.result()
        trace.flush()
        with open(sink, encoding="utf-8") as f:
            spans = [json.loads(line) for line in f]
    finally:
        trace.disable()
    assert ok and len(bits) == n and all(bits)
    assert pending._path == "ladder"
    picked = {k[0]: v for k, v in
              crypto_metrics().path_selected_total.values().items()
              if k[1] == "ed25519"}
    assert picked == {"ladder": 1.0}
    (batch,) = [sp for sp in spans if sp["name"] == "crypto.batch_verify"]
    assert (batch["path"], batch["n"], batch["bucket"]) == (
        "ladder", n, e._bucket(n))
    names = {sp["name"] for sp in spans}
    assert not names & {"crypto.rlc_prepare", "crypto.materialize"}
    assert expanded == []


def _pin_model_msm(monkeypatch, link_mbps, rlc_us, msm_us,
                   ladder_us=1.6):
    e = _pin_model(monkeypatch, link_mbps, rlc_us, ladder_us)
    e._HOST_TERMS["msm_us"] = float(msm_us)
    return e


def test_msm_path_absent_without_engine(monkeypatch):
    """A host without the native MSM engine models two paths exactly as
    before round 20 — no msm block, no t_msm."""
    e = _pin_model(monkeypatch, link_mbps=1000.0, rlc_us=1.1)
    m = e.dispatch_model(10000, 10240)
    assert "msm" not in m and "t_msm" not in m


def test_msm_path_shape(monkeypatch):
    """The MSM path is host-only: nothing ships to a device, so wire
    and device terms are zero and t_msm is the pure host fold cost."""
    e = _pin_model_msm(monkeypatch, link_mbps=1000.0, rlc_us=1.1,
                       msm_us=400.0)
    m = e.dispatch_model(10000, 10240)
    assert m["msm"]["wire"] == 0.0 and m["msm"]["device"] == 0.0
    assert m["t_msm"] == pytest.approx(10000 * 400.0e-6)


def test_msm_crossover_negative_at_every_batch_size(monkeypatch):
    """The round-20 crossover verdict, pinned with the measured terms
    (393 us/point at n=256 on the reference box): the ladder-vs-RLC-vs-
    MSM three-way pick NEVER selects MSM for signature dispatch — its
    host fold is ~190x the ladder's ~2 us/sig device term, and scaling
    n only scales both linearly (under RLC's fixed part a tiny batch's
    fold does fit, so the pick is against the better of the two). The
    engine's win is the KZG opening workload (WORKLOADS.json
    das_pc_multiproof), not this one."""
    e = _pin_model_msm(monkeypatch, link_mbps=1000.0, rlc_us=1.1,
                       msm_us=393.0)
    for n in (64, 256, 1024, 4096, 10240, 65536):
        m = e.dispatch_model(n, n)
        assert m["t_msm"] > m["t_ladder"], n
        assert m["t_msm"] > min(m["t_ladder"], m["t_rlc"]), n
    # even a 100x-parallel fantasy engine loses above the smallest tier
    e2 = _pin_model_msm(monkeypatch, link_mbps=1000.0, rlc_us=1.1,
                        msm_us=3.93)
    m = e2.dispatch_model(10240, 10240)
    assert m["t_msm"] > m["t_ladder"]


@needs_native
def test_msm_term_calibrates_with_engine(monkeypatch):
    """Fresh calibration on a host with the native MSM engine measures
    a real msm_us and dispatch_model grows the third path."""
    from cometbft_tpu.crypto import ed25519 as e

    if not native.g1_msm_available():
        pytest.skip("no native G1 MSM engine")
    monkeypatch.setattr(e, "_HOST_TERMS", None)
    terms = e._host_terms()
    assert terms["calibrated"] and terms["msm_us"] > 0
    m = e.dispatch_model(1024, 1024)
    assert m["t_msm"] == pytest.approx(1024 * terms["msm_us"] * 1e-6)
    # the negative result holds under REAL calibration too
    assert m["t_msm"] > m["t_ladder"]


def test_rlc_stream_length_is_tiered():
    """The wire stream must be padded to a coarse length tier: its true
    length varies with each batch's random z digits, and a distinct jit
    input shape per batch would recompile the multi-minute MSM graph
    once per submit instead of once per tier."""
    from cometbft_tpu.crypto import rlc

    lengths = set()
    for _ in range(3):  # each prepare() draws a fresh random layout
        items = _signed(64)
        prep = rlc.prepare(items, np.zeros(64, bool), 64)
        assert len(prep["stream"]) % (1 << 13) == 0
        # sign array covers every gatherable position incl. the sentinel
        assert len(prep["stream_neg"]) * 8 >= len(prep["stream"])
        lengths.add(len(prep["stream"]))
    assert len(lengths) == 1, "same-size batches must share one tier"


# -- mesh dispatch term (PR 7) ---------------------------------------------


class _StubMesh:
    """dispatch_terms()-shaped stand-in so the crossover is pinned by
    arithmetic, not by what hardware backs this test run."""

    n_devices = 8

    def __init__(self, put_fixed_s=100e-6, collective_s=60e-6):
        self._t = {
            "put_fixed_s": put_fixed_s,
            "collective_s": collective_s,
            "calibrated": True,
        }

    def dispatch_terms(self):
        return self._t


def test_mesh_term_absent_without_engine(monkeypatch):
    e = _pin_model(monkeypatch, link_mbps=1000.0, rlc_us=1.1)
    monkeypatch.setattr(e, "_mesh_engine", lambda: None)
    m = e.dispatch_model(10000, 10240)
    assert "mesh" not in m and "t_mesh" not in m
    assert not e._mesh_beats_single(10000, 10240)


def test_mesh_flips_device_bound_batch(monkeypatch):
    """Fast link, 8 chips: the per-lane part of the ladder's device
    stage splits 8 ways (its fixed part does not) and the mesh becomes
    HOST-bound at 16 ms — below the ladder's device stage and far below
    RLC's, so dispatch must flip to mesh exactly where splitting device
    time is what the batch needed."""
    e = _pin_model(monkeypatch, link_mbps=1000.0, rlc_us=1.1)
    monkeypatch.setattr(e, "_mesh_engine", lambda: _StubMesh())
    m = e.dispatch_model(10000, 10240)
    assert m["n_devices"] == 8
    assert m["mesh"]["device"] == pytest.approx(
        e._DEV_LADDER_FIXED_MS * 1e-3
        + 10000 * e._DEV_LADDER_US * 1e-6 / 8 + 60e-6)
    assert m["t_ladder"] == pytest.approx(m["ladder"]["device"])
    assert m["t_mesh"] == pytest.approx(10000 * 1.6e-6)  # host binds
    assert e._mesh_beats_single(10000, 10240)


def test_mesh_never_wins_wire_bound(monkeypatch):
    """30 MB/s link: the mesh ships the same 96 B/lane PLUS
    d fixed shard stagings, so its wire stage strictly exceeds the
    ladder's binding wire stage — splitting device time buys nothing
    and dispatch must keep the single chip."""
    e = _pin_model(monkeypatch, link_mbps=30.0, rlc_us=1.1)
    monkeypatch.setattr(e, "_mesh_engine", lambda: _StubMesh())
    m = e.dispatch_model(10000, 10240)
    assert m["mesh"]["wire"] > m["ladder"]["wire"]
    assert m["t_ladder"] == pytest.approx(m["ladder"]["wire"])  # wire-bound
    assert not e._mesh_beats_single(10000, 10240)


def test_mesh_loses_on_expensive_staging(monkeypatch):
    """100 ms fixed cost per shard device_put (a remote-device class of
    link, three orders above a local one):
    8 stagings = 0.8 s of wire overhead — the calibrated put term must
    keep the mesh off even on a device-bound batch."""
    e = _pin_model(monkeypatch, link_mbps=1000.0, rlc_us=1.1)
    monkeypatch.setattr(e, "_mesh_engine", lambda: _StubMesh(put_fixed_s=0.1))
    m = e.dispatch_model(10000, 10240)
    assert m["t_mesh"] >= 0.8
    assert not e._mesh_beats_single(10000, 10240)


@needs_native
def test_mesh_min_gates_submit(monkeypatch):
    """Below MESH_MIN submit() must not even consult the mesh model:
    commit-sized batches stay on the single-chip/native paths."""
    from cometbft_tpu.crypto import ed25519 as e

    calls = []

    def probe():
        calls.append(1)
        return None

    monkeypatch.setattr(e, "_mesh_engine", probe)
    monkeypatch.setattr(e, "NATIVE_MAX", 1024)
    items = _signed(8)
    bv = e.Ed25519BatchVerifier(backend="tpu")
    for p, m_, s in items:
        bv.add(e.Ed25519PubKey(p), m_, s)
    bv.submit().result()
    assert not calls
