"""Batch-size-aware backend dispatch: the host C++ engine for commit-sized
batches, the per-lane TPU ladder for mega-batches and for blame, the
sharded mesh where several chips are up and the stage model gives it the
batch (reference types/validation.go:26-53 + crypto/batch dispatch; sizing
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


def _pin_model(monkeypatch, link_mbps, ladder_us=1.6):
    from cometbft_tpu.crypto import ed25519 as e

    monkeypatch.setattr(e, "_LINK_MBPS", float(link_mbps))
    monkeypatch.setattr(e, "_HOST_TERMS", {
        "ladder_us": float(ladder_us), "calibrated": True,
    })
    return e


# device milliseconds a batch, the ladder as submit() launches it (given the
# cached pair, 32 windows), warm, from the profiler trace (my chip run, PR
# 39: `python chip_smoke.py --terms`; 20.480 / 130.870 with one point, PR 25)
CHIP_READINGS_MS = {
    ("ladder", 10000): 15.454,
    ("ladder", 65000): 98.692,
}


@pytest.mark.parametrize("engine, n", sorted(CHIP_READINGS_MS))
def test_device_terms_reproduce_the_chip_readings(monkeypatch, engine, n):
    """fixed + n * per-lane of the ladder is within 15% of what the v5e
    read at the live lane counts of the cells' two buckets."""
    e = _pin_model(monkeypatch, link_mbps=1000.0)
    model_ms = e.dispatch_model(n, e._bucket(n))[engine]["device"] * 1e3
    assert model_ms == pytest.approx(CHIP_READINGS_MS[engine, n], rel=0.15)


@needs_native
@pytest.mark.parametrize("n", [10000, 65000])
def test_mega_batch_on_an_accelerator_takes_the_ladder(monkeypatch, tmp_path,
                                                       n):
    """The decision itself, as submit() makes it on a host with the chip:
    NATIVE_MAX as shipped, the real model, the real link probe and host
    calibration; only the two jitted ladder programs are stand-ins. A
    mega-batch filled by add_batch is counted and traced under `ladder`
    and never expands its columns."""
    import json

    from cometbft_tpu.crypto import ed25519 as e
    from cometbft_tpu.ops import ed25519_verify as ev
    from cometbft_tpu.utils import trace
    from cometbft_tpu.utils.metrics import crypto_metrics

    expanded = []
    monkeypatch.setattr(e, "_ACCEL_BACKED", True)
    monkeypatch.setattr(e, "_mesh_engine", lambda: None)
    monkeypatch.setattr(e, "_A_CACHE", {})
    monkeypatch.setattr(e.Ed25519BatchVerifier, "_materialize",
                        lambda self: expanded.append(self.count()))
    monkeypatch.setattr(ev, "decompress_pubkeys_jit",
                        lambda a: (a, (a, a)))
    monkeypatch.setattr(
        ev, "verify_batch_cached_a_jit",
        lambda ok_a, a_points, rsk, live: (np.asarray(live),
                                           np.asarray(True)))

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
    assert "crypto.materialize" not in names
    assert expanded == []


# -- mesh dispatch term (PR 7) ---------------------------------------------


class _StubMesh:
    """dispatch_terms()-shaped stand-in so the crossover is pinned by
    arithmetic, not by what hardware backs this test run; submit() and
    next_device() stand in for the launches (test_dispatch_table)."""

    def __init__(self, put_fixed_s=100e-6, collective_s=60e-6, n_devices=8):
        self.n_devices = n_devices
        self._t = {
            "put_fixed_s": put_fixed_s,
            "collective_s": collective_s,
            "calibrated": True,
        }

    def dispatch_terms(self):
        return self._t

    def next_device(self):
        return None  # the default device: streamed placement is not the case

    def submit(self, pubkeys, rsk, live):
        assert rsk.shape[0] % self.n_devices == 0
        assert len(pubkeys) == 32 * int(live.sum())  # the blob, unpadded
        return np.asarray(True), np.asarray(live)


def test_mesh_term_absent_without_engine(monkeypatch):
    e = _pin_model(monkeypatch, link_mbps=1000.0)
    monkeypatch.setattr(e, "_mesh_engine", lambda: None)
    m = e.dispatch_model(10000, 10240)
    assert "mesh" not in m and "t_mesh" not in m
    assert not e._mesh_beats_single(10000, 10240)


def test_mesh_flips_device_bound_batch(monkeypatch):
    """Fast link, 8 chips: a shard runs the ladder's own program (the
    pair the engine staged, PR 44), so the per-lane part of the ladder's
    device stage splits 8 ways (its fixed part does not) and the mesh
    becomes HOST-bound at 12 ms — below the ladder's device stage
    (15.45 ms since PR 39's pair), so dispatch must flip to mesh exactly
    where splitting device time is what the batch needed."""
    e = _pin_model(monkeypatch, link_mbps=1000.0, ladder_us=1.2)
    monkeypatch.setattr(e, "_mesh_engine", lambda: _StubMesh())
    m = e.dispatch_model(10000, 10240)
    assert m["n_devices"] == 8
    assert m["mesh"]["device"] == pytest.approx(
        e._DEV_LADDER_FIXED_MS * 1e-3
        + 10000 * e._DEV_LADDER_US * 1e-6 / 8 + 60e-6)
    assert not hasattr(e, "_DEV_MESH_US")  # one line for both engines
    assert m["t_ladder"] == pytest.approx(m["ladder"]["device"])
    assert m["t_mesh"] == pytest.approx(10000 * 1.2e-6)  # host binds
    assert e._mesh_beats_single(10000, 10240)


def test_mesh_never_wins_wire_bound(monkeypatch):
    """30 MB/s link: the mesh ships the same 96 B/lane PLUS
    d fixed shard stagings, so its wire stage strictly exceeds the
    ladder's binding wire stage — splitting device time buys nothing
    and dispatch must keep the single chip."""
    e = _pin_model(monkeypatch, link_mbps=30.0)
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
    e = _pin_model(monkeypatch, link_mbps=1000.0)
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


# -- the whole table -------------------------------------------------------

# the three kinds of host a process can find itself on: (accelerator, mesh)
HOSTS = {
    "host": (False, None),   # no accelerator: jax is the CPU backend
    "chip": (True, None),    # one v5e
    "mesh4": (True, 4),      # one host with four (a 2x2 mesh)
}

# submit()'s path for every (lanes, host), read off the parent commit (PR
# 27, six engines) before PR 28 took three of them out: the table is the
# proof that no batch changed its path. Both sides of NATIVE_MAX (1024) and
# of MESH_MIN (4096), the cells' sizes (150, 10000, 65000) and one batch
# past the largest bucket.
DISPATCH_TABLE = {
    1: ("native", "native", "native"),
    150: ("native", "native", "native"),
    1023: ("native", "native", "native"),
    1024: ("native", "ladder", "ladder"),
    4095: ("native", "ladder", "ladder"),
    4096: ("native", "ladder", "mesh"),
    10000: ("native", "ladder", "mesh"),
    65000: ("native", "ladder", "mesh"),
    70000: ("native", "ladder", "mesh"),
}


def _stub_host(monkeypatch, host):
    """One of HOSTS: NATIVE_MAX and MESH_MIN as shipped, the stage model
    with the link and the host's pack term pinned to the chip host's class
    (1 GB/s, 1.05 us a lane); the device launches are stand-ins, so
    nothing compiles."""
    from cometbft_tpu.ops import ed25519_verify as ev

    accel, mesh_devices = HOSTS[host]
    e = _pin_model(monkeypatch, link_mbps=1000.0, ladder_us=1.05)
    mesh = _StubMesh(n_devices=mesh_devices) if mesh_devices else None
    monkeypatch.setattr(e, "_ACCEL_BACKED", accel)
    monkeypatch.setattr(e, "_mesh_engine", lambda: mesh)
    monkeypatch.setattr(e, "_A_CACHE", {})
    monkeypatch.setattr(ev, "decompress_pubkeys_jit",
                        lambda a: (a, (a, a)))
    monkeypatch.setattr(
        ev, "verify_batch_cached_a_jit",
        lambda ok_a, a_points, rsk, live: (np.asarray(live),
                                           np.asarray(True)))
    return e


def _columns(n):
    """add_batch() columns of n random lanes that pass the precheck."""
    r = np.random.default_rng(n)
    sigs = r.integers(0, 256, (n, 64), np.uint8)
    sigs[:, 63] = 0  # S < L on every lane
    return (r.integers(0, 256, (n, 32), np.uint8), sigs,
            r.integers(0, 256, n * 100, np.uint8).tobytes(),
            np.full(n, 100, np.uint32))


def _traced(tmp_path, fn):
    """(fn(), the span records it emitted)."""
    import json

    from cometbft_tpu.utils import trace

    sink = str(tmp_path / "spans.jsonl")
    trace.configure(sink)
    try:
        out = fn()
        trace.flush()
        with open(sink, encoding="utf-8") as f:
            return out, [json.loads(line) for line in f]
    finally:
        trace.disable()


@needs_native
@pytest.mark.parametrize(
    "n, host, want",
    [pytest.param(n, host, paths[i], id=f"{n}-{host}")
     for n, paths in DISPATCH_TABLE.items()
     for i, host in enumerate(HOSTS)])
def test_dispatch_table(monkeypatch, tmp_path, n, host, want):
    """Exactly three engines, and where each starts: NATIVE_MAX and
    MESH_MIN as shipped, the stage model with the link and the host's
    pack term pinned to the chip host's class (1 GB/s, 1.05 us a lane);
    the launches are stand-ins, so nothing compiles. The path is read
    where the ledger reads it: the crypto.batch_verify span and
    crypto_path_selected_total. force_perlane pins the ladder on every
    host at every size."""
    from cometbft_tpu.utils.metrics import crypto_metrics

    e = _stub_host(monkeypatch, host)
    monkeypatch.setattr(native, "batch_verify", lambda items: True)
    columns = _columns(n)

    def submit(force_perlane):
        bv = e.Ed25519BatchVerifier(backend="tpu",
                                    force_perlane=force_perlane)
        bv.add_batch(*columns)
        before = dict(crypto_metrics().path_selected_total.values())
        pending = bv.submit()
        ok, bits = pending.result()
        assert ok and len(bits) == n
        after = crypto_metrics().path_selected_total.values()
        counted = {k[0] for k, v in after.items()
                   if k[1] == "ed25519" and v > before.get(k, 0.0)}
        return pending._path, counted

    (path, counted), spans = _traced(
        tmp_path, lambda: submit(force_perlane=False))
    (batch,) = [sp for sp in spans if sp["name"] == "crypto.batch_verify"]
    assert (path, counted, batch["path"]) == (want, {want}, want)
    assert submit(force_perlane=True) == ("ladder", {"ladder"})


@needs_native
@pytest.mark.parametrize("host", ["chip", "mesh4"])
def test_every_device_dispatch_has_its_pack_span(monkeypatch, tmp_path, host):
    """The ladder's and the mesh's submit() both pack inside one
    crypto.pack span, a child of crypto.batch_verify, which says how many
    chunks the lanes went in and whether the worker pool took them; the
    same call counts under crypto_pack_total{mode}."""
    from cometbft_tpu.utils.metrics import crypto_metrics

    e = _stub_host(monkeypatch, host)
    n = 10000
    bv = e.Ed25519BatchVerifier(backend="tpu")
    bv.add_batch(*_columns(n))
    before = dict(crypto_metrics().pack_total.values())
    ok, spans = _traced(tmp_path, lambda: bv.submit().result()[0])
    assert ok
    (batch,) = [sp for sp in spans if sp["name"] == "crypto.batch_verify"]
    kids = [sp for sp in spans if sp.get("parent") == batch["id"]]
    want_path, want_kids = {
        "chip": ("ladder", {"crypto.pack", "crypto.device_launch"}),
        "mesh4": ("mesh", {"crypto.pack"}),  # the stub mesh has no span
    }[host]
    assert batch["path"] == want_path
    assert {sp["name"] for sp in kids} == want_kids
    (pack,) = [sp for sp in kids if sp["name"] == "crypto.pack"]
    chunks = min(native.rs_threads(), n // 1024)
    mode = "run" if chunks > 1 else "small"
    assert (pack["n"], pack["bucket"], pack["chunks"], pack["pool"]) == (
        n, 10240, chunks, mode)
    after = crypto_metrics().pack_total.values()
    assert after[(mode,)] == before.get((mode,), 0.0) + 1.0


def test_model_has_two_device_engines(monkeypatch):
    """dispatch_model() carries the ladder and, where a mesh is up, the
    mesh: no other engine has a key, a time or a host term."""
    e = _pin_model(monkeypatch, link_mbps=1000.0)
    monkeypatch.setattr(e, "_HOST_TERMS", None)  # the real calibration
    monkeypatch.setattr(e, "_mesh_engine", lambda: None)
    shared = {"link_mbps", "host_terms"}
    m = e.dispatch_model(10000, 10240)
    assert set(m) == shared | {"ladder", "t_ladder"}
    assert set(m["host_terms"]) == {"ladder_us", "calibrated"}
    monkeypatch.setattr(e, "_mesh_engine", lambda: _StubMesh(n_devices=4))
    m = e.dispatch_model(10000, 10240)
    assert set(m) == shared | {"ladder", "t_ladder", "mesh", "t_mesh",
                               "n_devices"}
    for engine in ("ladder", "mesh"):
        assert set(m[engine]) == {"wire", "device", "host"}
        assert m["t_" + engine] == max(m[engine].values())
