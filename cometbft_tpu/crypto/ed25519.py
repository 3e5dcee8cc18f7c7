"""Ed25519 key types and the TPU-backed batch verifier.

The signing path is host-side (consensus signs one vote at a time); the
verification path has two backends behind the BatchVerifier seam:

- `Ed25519BatchVerifier(backend="tpu")` — packs fixed-shape arrays, hashes
  SHA-512(R||A||M) host-side (cheap, ~us), and runs the batched ZIP-215
  kernel from cometbft_tpu.ops.ed25519_verify on device. Batches are padded
  to power-of-two buckets so each bucket compiles exactly once.
- `backend="cpu"` — pure-Python oracle (spec-exact, used for differential
  tests and as fallback).

Behavior parity: reference crypto/ed25519/ed25519.go (sign :91, verify
:180-187 with ZIP-215 options :36-41, batch :207-240). The reference's
LRU cache of expanded pubkeys (:43,68) has no analogue here: decompression
happens on-device inside the batch, where it is amortized across lanes.
"""

from __future__ import annotations

import time as _time

import numpy as np

from ..utils import trace as _trace
from ..utils.metrics import crypto_metrics
from . import ed25519_ref as ref
from .keys import BatchVerifier, PrivKey, PubKey, tmhash20

_L = ref.L  # ed25519 group order (host-side challenge reduction)

# (sha256(pubkey column), bucket) -> device-resident (ok_a, neg_a) from
# ops.ed25519_verify.decompress_pubkeys; see _launch_device.
_A_CACHE: dict = {}
_A_CACHE_SIZE = 4

KEY_TYPE = "tendermint/PubKeyEd25519"
PUB_KEY_SIZE = 32
PRIV_KEY_SIZE = 64  # seed || pubkey, matching common ed25519 private encoding
SIG_SIZE = 64

# Padded batch buckets: one compiled kernel per size. 10240 exists for
# the 10k-validator mega-commit workload (BASELINE config #5) — padding
# it up to 16384 would waste 38% of lanes on the hottest batch shape.
BUCKETS = (64, 256, 1024, 4096, 10240, 16384, 65536)

# At and above this size the RLC/MSM engine (ops/msm.py: one multi-scalar
# multiplication instead of N ladders, reference
# crypto/ed25519/ed25519.go:207-240) is a candidate beside the per-lane
# ladder kernel. The dispatch model carries host, wire and device terms for
# each engine; the slowest stage of an engine is its time, and the engine
# with the smaller time takes the batch.
#
# The device terms are readings of ONE v5e (DEVICE_KIND; the chip tool's
# machine, 2026-09-28, PR 25): each engine as submit() launches it, warm,
# device time a batch from the profiler trace (5 batches, by kernel scope,
# the reduction of tools/trace_analyze.py device), at the live lane counts
# of the two buckets the benchmark's cells use. Each engine's term is the
# line through its two readings, fixed + n * per-lane
# (`python chip_smoke.py --terms` measures all four again):
#   lanes (bucket)    ladder       RLC
#   10,000 (10240)    20.48 ms     120.19 ms
#   65,000 (65536)    130.87 ms    278.31 ms
# RLC's fixed part is its two one-lane-wide tails (`rlc.final_check` 35.1
# ms, `rlc.window_combine` 32.4 ms at either size) and the part of
# `rlc.accumulate` and `rlc.expand_stream` that does not shrink with the
# batch; its per-lane part alone is above the ladder's whole cost. So on
# this chip the model picks RLC at no size in BUCKETS: every device batch
# takes the ladder. The engine stays in the tree as a candidate the model
# never picks (ROADMAP D2 holds the verdict for a `simplicity` PR).
RLC_MIN = 4096
_DEV_LADDER_FIXED_MS = 0.41  # v5e profile, 2026-09-28, PR 25 (table above)
_DEV_LADDER_US = 2.007       # the same two readings
_DEV_RLC_FIXED_MS = 91.44    # v5e profile, 2026-09-28, PR 25 (table above)
_DEV_RLC_US = 2.875          # the same two readings
# Host-side per-sig terms are CALIBRATED at first dispatch decision
# (_host_terms: one small timed prepare / pack per engine) because they
# move with the host — core count, toolchain presence, numpy build.
# These constants are the documented fallbacks when calibration is
# skipped (COMETBFT_TPU_DISPATCH_CALIBRATE=0) or fails:
_HOST_RLC_US_NUMPY = 20.0    # numpy rlc.prepare, 1 core (r5 measured)
_HOST_RLC_US_NATIVE = 1.1    # native packer, ONE worker (r6 measured);
#                              scaled by rlc_packer_threads() at use
_HOST_LADDER_US = 1.6        # ladder submit packing (r4: ~15-22 ms/10k)
# BLS12-381 G1 Pippenger (csrc/g1_msm.inc): per-POINT host cost of the
# worker-pool MSM, calibrated like the terms above. Carried in the
# model as a third dispatch path for the crossover accounting in
# PROFILE.md round-20 — the measured verdict is NEGATIVE for signature
# dispatch (hundreds of us/point vs the ladder's ~2 us/sig device
# term); the engine earns its keep on its own workload (KZG openings,
# crypto/kzg.py), not here. r20 measured 393 us/point at n=256, 1 core.
_HOST_MSM_US = 400.0
_WIRE_LADDER_B = 96    # R||S||k per lane (73 on the delta fast path)
# R (32) + A (32, re-shipped each submit: the RLC path keys its random
# layout per batch, so there is no device-resident A cache analogue) +
# ~39 digit-stream entries (~2.1 B) + counts — measured 116 B/lane at
# 10k (bench instrumentation)
_WIRE_RLC_B = 116

_LINK_MBPS: float | None = None

# big-endian bytes of the group order, for the vectorized S < L precheck
_L_BE = np.frombuffer(
    (2**252 + 27742317777372353535851937790883648493).to_bytes(32, "big"),
    np.uint8,
)


def _link_mbps() -> float:
    """One-time host->device bandwidth probe (2 MiB device_put). Drives
    the ladder-vs-RLC dispatch; both paths are correct, this only picks
    the faster one for the hardware at hand."""
    global _LINK_MBPS
    if _LINK_MBPS is None:
        import time

        import jax

        buf = np.zeros(2 << 20, np.uint8)
        jax.device_put(buf).block_until_ready()  # warm the path
        t0 = time.perf_counter()
        jax.device_put(buf).block_until_ready()
        dt = max(time.perf_counter() - t0, 1e-6)
        _LINK_MBPS = max(2.0 / dt, 1.0)
    return _LINK_MBPS


_HOST_TERMS: dict | None = None


def _calibrate_host_terms() -> dict:
    """Measure the per-sig host cost of each engine's pack stage on THIS
    host: one small timed rlc.prepare (native packer when present, numpy
    otherwise) and one timed pack_rsk for the ladder. Returns fallback
    constants when calibration is disabled or anything goes wrong —
    dispatch must keep picking sanely on a box where the probe can't
    run."""
    import os as _os

    from . import native
    from . import rlc as _rlc

    threads = native.rlc_packer_threads()
    rlc_native = native.rlc_available()
    terms = {
        "ladder_us": _HOST_LADDER_US,
        "rlc_us": (_HOST_RLC_US_NATIVE / threads) if rlc_native
        else _HOST_RLC_US_NUMPY,
        "rlc_threads": threads,
        "rlc_native": rlc_native,
        "calibrated": False,
    }
    # the MSM term exists only where the native engine does — there is
    # no oracle fallback path worth modeling (three orders slower)
    if native.g1_msm_available():
        terms["msm_us"] = _HOST_MSM_US
    if _os.environ.get("COMETBFT_TPU_DISPATCH_CALIBRATE", "1") == "0":
        return terms
    try:
        import time

        n = 1024
        rnd = np.random.default_rng(0xD15BA7C4)
        pub_blob = rnd.integers(0, 256, n * 32, np.uint8).tobytes()
        sig_blob = rnd.integers(0, 256, n * 64, np.uint8).tobytes()
        msg_blob = rnd.integers(0, 256, n * 100, np.uint8).tobytes()
        msg_lens = np.full(n, 100, np.uint64)
        items = [
            (pub_blob[i * 32:(i + 1) * 32],
             msg_blob[i * 100:(i + 1) * 100],
             sig_blob[i * 64:(i + 1) * 64])
            for i in range(n)
        ]
        skip = np.zeros(n, bool)
        blobs = (pub_blob, sig_blob, msg_blob, msg_lens)
        best = float("inf")
        for _ in range(2):
            t0 = time.perf_counter()
            prep = _rlc.prepare(items, skip, n, blobs=blobs)
            best = min(best, time.perf_counter() - t0)
        if prep is not None:
            terms["rlc_us"] = best / n * 1e6
        if native.available():
            out_rsk = np.empty((n, 96), np.uint8)
            best = float("inf")
            for _ in range(2):
                t0 = time.perf_counter()
                okp = native.pack_rsk(n, sig_blob, pub_blob, msg_blob,
                                      msg_lens, out_rsk)
                best = min(best, time.perf_counter() - t0)
            if okp:
                terms["ladder_us"] = best / n * 1e6
        if "msm_us" in terms:
            import hashlib as _hl

            from .bls import G1X, G1Y, g1_compress
            nm = 256
            pb = g1_compress((G1X, G1Y)) * nm
            sb = b"".join(
                b"\x00" + _hl.sha256(b"msm-cal%d" % i).digest()[1:]
                for i in range(nm)
            )  # 248-bit hash scalars are always < r
            best = float("inf")
            for _ in range(2):
                t0 = time.perf_counter()
                okm = native.g1_msm(sb, pb, nm)
                best = min(best, time.perf_counter() - t0)
            if isinstance(okm, bytes):
                terms["msm_us"] = best / nm * 1e6
        terms["calibrated"] = True
    except Exception:
        return terms
    return terms


def _host_terms() -> dict:
    """Calibrated host-stage per-sig terms, measured once per process at
    the first dispatch decision (~a few ms native, ~40 ms numpy-only)."""
    global _HOST_TERMS
    if _HOST_TERMS is None:
        _HOST_TERMS = _calibrate_host_terms()
    return _HOST_TERMS


def dispatch_model(n: int, b: int) -> dict:
    """The modeled per-stage times (seconds) behind the ladder-vs-RLC
    dispatch, exposed for bench.py's `ceiling` accounting and the
    crossover tests: each path's pipelined throughput is bound by the
    slowest of its host / wire / device stages."""
    bw = _link_mbps() * 1e6  # bytes/sec
    host = _host_terms()
    ladder = {
        "wire": _WIRE_LADDER_B * b / bw,
        "device": _DEV_LADDER_FIXED_MS * 1e-3 + n * _DEV_LADDER_US * 1e-6,
        "host": n * host["ladder_us"] * 1e-6,
    }
    rlc = {
        "wire": _WIRE_RLC_B * b / bw,
        "device": _DEV_RLC_FIXED_MS * 1e-3 + n * _DEV_RLC_US * 1e-6,
        "host": n * host["rlc_us"] * 1e-6,
    }
    out = {
        "link_mbps": _LINK_MBPS,
        "host_terms": host,
        "ladder": ladder,
        "rlc": rlc,
        "t_ladder": max(ladder.values()),
        "t_rlc": max(rlc.values()),
    }
    if host.get("msm_us") is not None:
        # Third path (round 20): fold the batch behind one BLS12-381
        # G1 MSM on the native Pippenger engine. Host-only — nothing
        # ships to the device, so wire and device terms vanish — but
        # the per-point cost is hundreds of us against the ladder's
        # ~2 us/sig device term, so the crossover never happens for
        # signature dispatch at any n (the honest negative result in
        # PROFILE.md round-20; the engine's win is KZG openings).
        msm = {
            "wire": 0.0,
            "device": 0.0,
            "host": n * host["msm_us"] * 1e-6,
        }
        out["msm"] = msm
        out["t_msm"] = max(msm.values())
    eng = _mesh_engine()
    if eng is not None and eng.n_devices > 1:
        # Sharded-mesh term: the per-lane part of the ladder's device
        # time splits d ways (its fixed part is paid by every shard) but
        # the wire stage pays d separate shard stagings (each with the
        # calibrated fixed per-transfer cost) and every launch pays one
        # psum across the mesh. Host packing is the same 96 B/lane rsk
        # pack as the ladder. The mesh wins exactly when the batch is
        # device-bound — when wire or host binds, splitting device time
        # buys nothing and the fixed costs make it a strict loss.
        d = eng.n_devices
        terms = eng.dispatch_terms()
        mesh = {
            "wire": _WIRE_LADDER_B * b / bw + d * terms["put_fixed_s"],
            "device": (_DEV_LADDER_FIXED_MS * 1e-3
                       + n * _DEV_LADDER_US * 1e-6 / d
                       + terms["collective_s"]),
            "host": ladder["host"],
        }
        out["mesh"] = mesh
        out["t_mesh"] = max(mesh.values())
        out["n_devices"] = d
    return out


def _rlc_beats_ladder(n: int, b: int) -> bool:
    # pipelined throughput is bound by the slowest of the three
    # sequential-resource stages: host packing, wire, device
    m = dispatch_model(n, b)
    return m["t_rlc"] < m["t_ladder"]


def _mesh_beats_single(n: int, b: int) -> bool:
    """Sharded mesh vs the best single-chip path (ladder, or RLC where
    it applies): honest per-batch pick from the same stage model."""
    m = dispatch_model(n, b)
    if "t_mesh" not in m:
        return False
    best_single = m["t_ladder"]
    if n >= RLC_MIN:
        best_single = min(best_single, m["t_rlc"])
    return m["t_mesh"] < best_single


# Below this size the native C++ verifier wins: a commit-sized batch
# finishes in well under a TPU dispatch round trip (batch-size-aware
# dispatch — reference types/validation.go:26-53 picks batch vs single
# by support; we additionally pick the backend by size). The native
# engine is the 8-lane AVX-512 IFMA Pippenger when the host supports
# it (csrc/ed25519_ifma.inc), portable C++ otherwise.
NATIVE_MAX = 1024

# The device terms of the dispatch model (_DEV_LADDER_*, _DEV_RLC_*: PR
# 25's readings; _DEV_DELTA_US, _DEV_PREHASH_US: round 4's) and the
# wire-byte terms were measured on ONE device kind, a TPU v5e, which
# jax reports as this device_kind.
# They are not re-derived per device: an accelerator of another kind is
# an error (_accel_backed raises), not a v5e with different numbers.
DEVICE_KIND = "TPU v5 lite"

# Probed once: is jax backed by a real accelerator? When it is not,
# the "device" paths are XLA emulating the Pallas graphs on this same
# host — strictly dominated by the native C++ engine at every batch
# size, and their XLA compiles at mega-batch shapes take minutes on a
# small host. Dispatch must not send work to a device that does not
# exist. A runtime that fails to start is an error and propagates: it
# is not a CPU-only host.
_ACCEL_BACKED = None


def _accel_backed() -> bool:
    global _ACCEL_BACKED
    if _ACCEL_BACKED is None:
        import jax

        backed = jax.default_backend() != "cpu"
        if backed:
            kind = jax.devices()[0].device_kind
            if kind != DEVICE_KIND:
                raise RuntimeError(
                    f"dispatch constants describe {DEVICE_KIND!r}; this "
                    f"accelerator is {kind!r} and has not been measured"
                )
        _ACCEL_BACKED = backed
    return _ACCEL_BACKED


def _native_limit(n: int) -> int:
    """Batch-size ceiling for the native engine at this dispatch.

    NATIVE_MAX when a real accelerator backs jax (commit-sized batches
    stay native, mega-batches earn the device round trip); past every
    n when jax is CPU-only. NATIVE_MAX = 0 disables the native engine
    unconditionally (the test seam for forcing device paths)."""
    limit = NATIVE_MAX
    if limit and not _accel_backed():
        return n + 1
    return limit


# At and above this size the sharded mesh path is considered: below it
# the d separate per-shard H2D transfers (each paying the fixed staging
# cost) eat the device-time split, and the single-chip ladder pipeline
# already hides its wire under compute. Same order as RLC_MIN — both
# engines only make sense at mega-batch sizes.
MESH_MIN = 4096


def _mesh_engine():
    """The process-wide multi-device verify mesh, or None when the mesh
    path is off (CPU-only jax, a single device, or COMETBFT_TPU_MESH=0
    — parallel/mesh.get_engine owns the policy). A mesh that should be
    up and cannot be built raises: it does not become one chip.
    Imported lazily: the mesh module pulls in jax at import time and
    this module must stay importable without it."""
    from ..parallel import mesh as _mesh

    return _mesh.get_engine(accel_backed=_accel_backed())


# Minimum batch size for the structured-wire (delta) device path: below
# this the detection overhead isn't worth it and the native engine has
# already taken the batch anyway. The upper bucket bound keeps the
# on-device SHA + ladder graph at sizes whose XLA compile stays in the
# tens-of-seconds class — at 65536 lanes the combined graph took tens
# of minutes to compile on a small host in round 4 (not retried on
# today's stack, where the prehashed ladder compiles in ~25 s at every
# bucket up to 65536 and the delta graph in ~32 s at 4096), dwarfing
# the ~23 B/lane wire saving it buys (mega-batches use the prehashed
# 96-byte path instead).
DELTA_MIN = 256
DELTA_MAX_BUCKET = 16384

# Measured end-to-end per-sig times (round 4, 10k batches, depth-16
# pipeline): the delta path ships 23 fewer bytes/lane but pays device
# SHA-512 + reduce512 for every lane, and on this chip that costs more
# than the wire it saves (260k vs 194k sigs/s prehashed-vs-delta). The
# dispatch picks by modeled time against the probed link: delta only
# wins below ~19 MB/s.
_DEV_DELTA_US = 5.1     # device rebuild + hash + ladder, e2e per sig
_DEV_PREHASH_US = 3.8   # host-hashed k, ladder only, e2e per sig
_WIRE_DELTA_B = 73


def _delta_beats_prehashed(n: int, b: int) -> bool:
    bw = _link_mbps() * 1e6
    t_delta = max(_WIRE_DELTA_B * b / bw, n * _DEV_DELTA_US * 1e-6)
    t_pre = max(_WIRE_LADDER_B * b / bw, n * _DEV_PREHASH_US * 1e-6)
    return t_delta < t_pre


class Ed25519PubKey(PubKey):
    __slots__ = ("_b",)

    def __init__(self, b: bytes):
        if len(b) != PUB_KEY_SIZE:
            raise ValueError(f"ed25519 pubkey must be {PUB_KEY_SIZE} bytes")
        self._b = bytes(b)

    def address(self) -> bytes:
        return tmhash20(self._b)

    def bytes(self) -> bytes:
        return self._b

    def verify_signature(self, msg: bytes, sig: bytes) -> bool:
        # hot path for individually-gossiped votes: the native C++ engine
        # (csrc/ed25519_native.cpp, ~12x the pure-Python oracle); falls
        # back to the oracle when no toolchain is available
        from . import native

        crypto_metrics().path_selected_total.inc(1.0, "single", "ed25519")
        if native.available():
            return native.verify(self._b, msg, sig)
        return ref.verify(self._b, msg, sig)

    def type_tag(self) -> str:
        return KEY_TYPE

    def __repr__(self):
        return f"Ed25519PubKey({self._b.hex()[:16]}…)"


class Ed25519PrivKey(PrivKey):
    __slots__ = ("_seed", "_pub")

    def __init__(self, key_bytes: bytes):
        if len(key_bytes) == 32:
            self._seed = bytes(key_bytes)
            self._pub = ref.pubkey_from_seed(self._seed)
        elif len(key_bytes) == PRIV_KEY_SIZE:
            self._seed = bytes(key_bytes[:32])
            self._pub = bytes(key_bytes[32:])
        else:
            raise ValueError("ed25519 privkey must be 32 (seed) or 64 bytes")

    @classmethod
    def generate(cls) -> "Ed25519PrivKey":
        return cls(ref.generate_seed())

    def sign(self, msg: bytes) -> bytes:
        from . import native

        if native.available():
            return native.sign(self._seed, self._pub, msg)
        return ref.sign(self._seed, msg)

    def pub_key(self) -> Ed25519PubKey:
        return Ed25519PubKey(self._pub)

    def bytes(self) -> bytes:
        return self._seed + self._pub

    def type_tag(self) -> str:
        return KEY_TYPE


def _bucket(n: int) -> int:
    for b in BUCKETS:
        if n <= b:
            return b
    return ((n + BUCKETS[-1] - 1) // BUCKETS[-1]) * BUCKETS[-1]


class Ed25519BatchVerifier(BatchVerifier):
    """Batch verifier; `backend` selects tpu (default) or cpu oracle."""

    def __init__(
        self,
        backend: str = "tpu",
        force_perlane: bool = False,
        device_sha: bool = False,
    ):
        self._items: list[tuple[bytes, bytes, bytes]] = []
        self._precheck_fail: list[bool] = []
        self.backend = backend
        self._force_perlane = force_perlane
        self._device_sha = device_sha
        self._delta = None  # memoized message-structure detection
        # Wire blobs accumulate AT add() time: submit() used to spend
        # ~7 ms/10k on b"".join generator sweeps over the item list —
        # the single largest host-packing cost (round-5 profile); a
        # bytearray append per add is the same memcpy spread across
        # calls that were already touching the item.
        self._pub_buf = bytearray()
        self._sig_buf = bytearray()
        self._msg_buf = bytearray()
        self._msg_lens: list[int] = []
        # add_batch appends whole-commit columns here instead of 1000
        # (pub, msg, sig) tuples; _materialize() expands them into
        # _items only on the paths that need per-item access (blame,
        # RLC prepare, cpu oracle) — the happy path never does
        self._lazy: list[tuple] = []

    def _materialize(self) -> None:
        """Expand the lazy whole-commit columns into per-item tuples.
        Called once per dispatch by the paths that need them (add()
        calls it only when columns wait), so the span is per dispatch
        even where nothing waits (`n` 0: add() built the tuples)."""
        with _trace.span("crypto.materialize",
                         n=self.count() - len(self._items)):
            for pub_rows, sig_rows, msg_blob, lens in self._lazy:
                off = 0
                for i in range(len(lens)):
                    ln = int(lens[i])
                    self._items.append((
                        pub_rows[i].tobytes(),
                        bytes(msg_blob[off:off + ln]),
                        sig_rows[i].tobytes(),
                    ))
                    off += ln
            self._lazy.clear()

    def add_batch(self, pub_rows, sig_rows, msg_blob, msg_lens) -> None:
        """Vectorized add() for a whole commit's worth of ed25519 lanes.

        pub_rows (n,32) u8, sig_rows (n,64) u8, msg_blob bytes,
        msg_lens uint32/int array; the caller guarantees every row is a
        structurally-complete 64-byte signature (the replay fast path
        gates on sig_lens == 64 and falls back otherwise). The S < L
        precheck runs vectorized; failing lanes get a zeroed signature
        and precheck_fail=True, matching add() semantics exactly."""
        n = len(msg_lens)
        if n == 0:
            return
        # S >= L precheck, lexicographic on the big-endian view
        s_be = sig_rows[:, 63:31:-1]  # (n, 32) most-significant first
        neq = s_be != _L_BE[None, :]
        first = neq.argmax(axis=1)
        rows = np.arange(n)
        s_byte = s_be[rows, first]
        l_byte = _L_BE[first]
        bad = ~(neq.any(axis=1) & (s_byte < l_byte))  # S >= L
        if bad.any():
            sig_rows = sig_rows.copy()
            sig_rows[bad] = 0
        self._precheck_fail.extend(bad.tolist())
        self._pub_buf += pub_rows.tobytes()
        self._sig_buf += sig_rows.tobytes()
        self._msg_buf += msg_blob
        self._msg_lens.extend(np.asarray(msg_lens).tolist())
        self._lazy.append((pub_rows, sig_rows, msg_blob, msg_lens))
        self._delta = None

    def add(self, pub_key: PubKey, msg: bytes, sig: bytes) -> bool:
        if not isinstance(pub_key, Ed25519PubKey):
            return False
        if self._lazy:
            self._materialize()
        ok = len(sig) == SIG_SIZE
        if ok:
            s = int.from_bytes(sig[32:], "little")
            ok = s < ref.L  # non-canonical S rejected up front (ZIP-215 rule)
        pub = pub_key.bytes()
        sig_eff = sig if ok else b"\x00" * 64
        self._items.append((pub, msg, sig_eff))
        self._precheck_fail.append(not ok)
        self._pub_buf += pub
        self._sig_buf += sig_eff
        self._msg_buf += msg
        self._msg_lens.append(len(msg))
        self._delta = None  # structure detection invalidated
        return ok

    def count(self) -> int:
        return len(self._precheck_fail)

    def absorb(self, other: "Ed25519BatchVerifier") -> tuple[int, int]:
        """Append every queued lane of `other` onto this verifier,
        preserving order and precheck verdicts exactly; returns the
        half-open lane range [start, end) the absorbed request occupies
        in this verifier's bitmap. This is the merge seam the shared
        verify scheduler (crypto/sched.py) uses to coalesce many
        consumers' already-filled verifiers into one mega-batch dispatch
        without re-running prechecks or copying per-item Python tuples
        where a columnar add_batch chunk can ride through lazily.

        `other` is left logically intact (its buffers are not drained),
        but it must not be mutated or verified concurrently with the
        absorb."""
        start = self.count()
        if other._items:
            # logical order within a verifier is _items then _lazy;
            # interleaving other's eager items after our pending lazy
            # chunks would reorder OUR lanes, so expand ours first
            if self._lazy:
                self._materialize()
            self._items.extend(other._items)
        self._lazy.extend(other._lazy)
        self._precheck_fail.extend(other._precheck_fail)
        self._pub_buf += other._pub_buf
        self._sig_buf += other._sig_buf
        self._msg_buf += other._msg_buf
        self._msg_lens.extend(other._msg_lens)
        self._delta = None
        return start, self.count()

    def verify(self) -> tuple[bool, list[bool]]:
        if not self.count():
            return False, []
        if self.backend == "cpu":
            t0 = _time.perf_counter()
            with _trace.span("crypto.batch_verify", path="cpu",
                             n=self.count()):
                self._materialize()
                bits = [
                    (not bad) and ref.verify(p, m, s)
                    for (p, m, s), bad in zip(self._items,
                                              self._precheck_fail)
                ]
            dt = _time.perf_counter() - t0
            m = crypto_metrics()
            m.batch_size.observe(self.count())
            m.path_selected_total.inc(1.0, "cpu", "ed25519")
            m.verify_seconds.observe(dt, "cpu", "ed25519")
            return all(bits), bits
        return self.submit().result()

    def submit(self) -> "PendingBatch":
        """Launch device verification without blocking on the result.

        The device→host fetch carries a fixed latency (unmeasured on
        today's machine); a pipeline that submits several batches and
        collects them together (collect_pending) hides both that latency
        and the kernel time of all but the last batch. This is the async
        seam the reference gets from goroutine-per-reactor concurrency
        (reference: abci/client/socket_client.go:129 pipelined queue);
        ours overlaps host packing with device compute instead.
        """
        n = self.count()
        t0 = _time.perf_counter()
        pending = None
        path = "ladder"
        # one span per dispatch: the host time inside submit(), split by
        # its children (materialize, rlc_prepare, pack, device_launch or
        # native_verify)
        with _trace.span("crypto.batch_verify", n=n,
                         bucket=_bucket(n)) as sp:
            if not self._force_perlane:
                if n < _native_limit(n):
                    pending = self._native_batch()
                    if pending is not None:
                        path = "native"
                if pending is None and n >= MESH_MIN:
                    eng = _mesh_engine()
                    if eng is not None and _mesh_beats_single(
                            n, _bucket(n)):
                        pending = self._launch_mesh(eng)
                        if pending is not None:
                            path = "mesh"
                if (pending is None and n >= RLC_MIN
                        and _rlc_beats_ladder(n, _bucket(n))):
                    pending = self._launch_rlc()
                    if pending is not None:
                        path = "rlc"
            if pending is None:
                bits, all_ok = self._launch_device()
                path = self._device_path
                # Snapshot per-batch state: the verifier may be
                # reused/mutated after submit() without corrupting
                # in-flight results.
                pending = PendingBatch(
                    bits,
                    all_ok,
                    n,
                    list(self._precheck_fail),
                    [self._items[i] for i in self._oversize],
                    list(self._oversize),
                )
            sp.add(path=path)
        # dispatch observability: per-path selection counter, batch-size
        # histogram and, through the handle, the submit→result latency
        m = crypto_metrics()
        m.batch_size.observe(n)
        m.path_selected_total.inc(1.0, path, "ed25519")
        pending._path = path
        pending._t0 = t0
        pending._batch = sp.id
        return pending

    def _native_batch(self):
        """Synchronous C++ RLC batch for commit-sized batches; None when
        the native engine is unavailable (caller tries device paths)."""
        from . import native

        if not native.available():
            return None
        self._materialize()
        with _trace.span("crypto.native_verify", n=self.count()) as sp:
            live = [
                it for it, bad in zip(self._items, self._precheck_fail)
                if not bad
            ]
            ok = bool(live) and native.batch_verify(live)
            sp.add(ok=ok)
            if ok:
                bits = [not bad for bad in self._precheck_fail]
                return DonePending(all(bits), bits)
            # blame via per-signature native verification
            bits = []
            for (pub, msg, sig), bad in zip(self._items,
                                            self._precheck_fail):
                bits.append(not bad and native.verify(pub, msg, sig))
            return DonePending(all(bits), bits)

    def _launch_rlc(self):
        """RLC/MSM path: one multi-scalar multiplication for the whole
        batch. The wire carries R plus the dense digit stream (~2 B per
        contribution, ops/msm.py expand_stream rebuilds the gather table
        on device). Returns None when the host layout declines (a
        window's lane budget overflows; counted in
        crypto_gave_way_total{reason="rlc_declined"}) so the per-lane
        kernel takes over."""
        import jax

        from ..ops.msm import rlc_verify_stream_jit
        from . import rlc as _rlc

        self._materialize()
        n = len(self._items)
        b = _bucket(n)
        skip = np.asarray(self._precheck_fail, bool)
        # the columnar blobs already exist on this path: hand them to the
        # native packer so it skips the per-item join (~0.35 us/sig)
        with _trace.span("crypto.rlc_prepare", n=n) as sp:
            prep = _rlc.prepare(
                self._items, skip, b,
                blobs=(self._pub_buf, self._sig_buf, self._msg_buf,
                       np.asarray(self._msg_lens, np.uint64)),
            )
            sp.add(declined=prep is None)
        if prep is None:
            crypto_metrics().gave_way_total.inc(1.0, "rlc_declined")
            return None
        with _trace.span("crypto.pack", n=n, bucket=b):
            a_bytes = np.zeros((b, 32), np.uint8)
            r_bytes = np.zeros((b, 32), np.uint8)
            live = np.zeros((b,), bool)
            pub_arr = np.frombuffer(
                bytes(self._pub_buf), np.uint8).reshape(n, 32)
            sig_arr = np.frombuffer(
                bytes(self._sig_buf), np.uint8).reshape(n, 64)
            a_bytes[:n] = pub_arr
            r_bytes[:n] = sig_arr[:, :32]
            live[:n] = ~skip
            # pad the round count to a power of two (min 8): S is a
            # static jit arg and the batch's max lane occupancy moves
            # with the random z digits, so tiering keeps the
            # compiled-variant count at ~2 per bucket instead of one per
            # distinct occupancy
            s_pad = 8
            while s_pad < prep["s_rounds"]:
                s_pad *= 2
            wire = (a_bytes, r_bytes, live, prep["stream"],
                    prep["stream_neg"], prep["counts"], prep["weights"],
                    prep["c_digits"])
        global _LAST_WIRE_B_PER_LANE
        _LAST_WIRE_B_PER_LANE = round(
            (
                32 * b  # R encodings
                + prep["stream"].nbytes
                + prep["stream_neg"].nbytes
                + prep["counts"].nbytes
            )
            / b
        )
        with _trace.span("crypto.device_launch",
                         bytes=sum(a.nbytes for a in wire)):
            ok = rlc_verify_stream_jit(
                *jax.device_put(wire), s_rounds=s_pad)
        return PendingRLC(
            ok, n, list(self._precheck_fail), list(self._items)
        )

    def _launch_device(self):
        """Pack host-side, hash host-side, launch the curve kernel.

        The challenge k = SHA-512(R||A||M) mod L is computed on the host
        (hashlib, ~1 us/sig): shipping 32 bytes of scalar instead of 256
        bytes of padded message halves the wire cost twice over, and on a
        bandwidth-limited host->device link the transfer is what bounds
        sustained throughput. The on-device-SHA kernel remains available
        via device_sha=True (it is the fully-fused showcase path and the
        differential tests cover both)."""
        import hashlib

        import jax

        from ..ops.ed25519_verify import (
            decompress_pubkeys_jit,
            verify_batch_cached_a_jit,
        )

        self._device_path = "ladder"
        if self._device_sha:
            self._materialize()
            self._device_path = "device_sha"
            return self._launch_device_sha()

        n = self.count()
        b = _bucket(n)
        # structured-message fast path: when the batch's messages share a
        # common prefix + suffix (replay/commit sign bytes differ only in
        # the vote timestamp), ship R||S + the per-lane delta and rebuild
        # + hash the messages on device — fewer wire bytes per lane than
        # the 96-byte R||S||k path on a bandwidth-limited link
        if (
            DELTA_MIN <= n
            and b <= DELTA_MAX_BUCKET
            and _delta_beats_prehashed(n, b)
        ):
            if self._delta is None:
                self._materialize()
                self._delta = _detect_delta(self._items) or False
            if self._delta:
                self._materialize()
                self._device_path = "delta"
                return self._launch_device_delta(self._delta)
        with _trace.span("crypto.pack", n=n, bucket=b):
            rsk, live, pub_blob = self._pack_rsk_live(n, b)
        # Streamed placement: when a multi-device mesh is up, each whole
        # single-chip batch lands on the next device round-robin, so d
        # independent commits verify concurrently with no collective at
        # all; device_put is async, so H2D staging for device i+1
        # overlaps compute on device i (double-buffered by the in-flight
        # pipeline — submit()s queue, collect_pending fans in).
        eng = _mesh_engine()
        dev = None
        if eng is not None and eng.n_devices > 1:
            dev = eng.next_device()
        # Device-resident pubkey cache: replay verifies the SAME validator
        # set every height, so A ships + decompresses once per set change
        # (keyed by content hash — 1 ms vs 50 ms of wire + exponentiation;
        # streamed batches key per device so each chip keeps its own copy).
        fp = (hashlib.sha256(pub_blob).digest(), b, dev)
        global _LAST_WIRE_B_PER_LANE
        _LAST_WIRE_B_PER_LANE = _WIRE_LADDER_B
        with _trace.span("crypto.device_launch",
                         bytes=rsk.nbytes + live.nbytes) as sp:
            cached = _A_CACHE.get(fp)
            if cached is None:
                a_bytes = np.zeros((b, 32), np.uint8)
                a_bytes[:n] = np.frombuffer(
                    pub_blob, np.uint8).reshape(n, 32)
                sp.add(bytes=rsk.nbytes + live.nbytes + a_bytes.nbytes)
                cached = decompress_pubkeys_jit(
                    jax.device_put(a_bytes, dev))
                _A_CACHE[fp] = cached
                while len(_A_CACHE) > _A_CACHE_SIZE:
                    _A_CACHE.pop(next(iter(_A_CACHE)))
            ok_a, neg_a = cached
            if dev is not None and _trace.enabled:
                _trace.emit("crypto.stream_place", "event",
                            device=str(getattr(dev, "id", dev)), n=n, b=b)
            return verify_batch_cached_a_jit(
                ok_a, neg_a, *jax.device_put((rsk, live), dev)
            )

    def _pack_rsk_live(self, n: int, b: int):
        """Pack the (b,96) R||S||k rows + live mask shared by the
        single-chip prehashed ladder and the sharded mesh paths (k
        hashed host-side; see _launch_device's docstring)."""
        import hashlib

        pub_blob = self._pub_buf  # zero-copy; hashed + copied by callers
        rsk = np.zeros((b, 96), np.uint8)
        live = np.zeros((b,), bool)
        live[:n] = True
        self._oversize = []  # host hashing has no message-length limit
        from . import native

        packed = native.available() and native.pack_rsk(
            n, self._sig_buf, pub_blob, self._msg_buf,
            np.asarray(self._msg_lens, np.uint64), rsk,
        )
        if not packed:
            self._materialize()
            sig_blob = bytes(self._sig_buf)
            rsk[:n, :64] = np.frombuffer(sig_blob, np.uint8).reshape(n, 64)
            sha = hashlib.sha512
            ks = b"".join(
                (
                    int.from_bytes(
                        sha(sig[:32] + pub + msg).digest(), "little"
                    )
                    % _L
                ).to_bytes(32, "little")
                for pub, msg, sig in self._items
            )
            rsk[:n, 64:] = np.frombuffer(ks, np.uint8).reshape(n, 32)
        return rsk, live, pub_blob

    def _launch_mesh(self, eng):
        """Shard one mega-batch over every mesh device: same 96 B/lane
        prehashed wire as the ladder path, padded so B divides the mesh
        (dead lanes ride live=False and are masked from the psum), with
        the pubkey column staged once per validator set in the engine's
        sharded cache. Returns a PendingBatch over the un-fetched
        replicated all-ok scalar + sharded bitmap."""
        import hashlib

        from ..parallel.mesh import pad_to_shards

        n = self.count()
        b = pad_to_shards(n, eng.n_devices, bucket=_bucket(n))
        rsk, live, pub_blob = self._pack_rsk_live(n, b)
        a_bytes = np.zeros((b, 32), np.uint8)
        a_bytes[:n] = np.frombuffer(bytes(pub_blob), np.uint8).reshape(n, 32)
        fp = hashlib.sha256(bytes(pub_blob)).digest()
        global _LAST_WIRE_B_PER_LANE
        _LAST_WIRE_B_PER_LANE = _WIRE_LADDER_B
        all_ok, bits = eng.submit(a_bytes, rsk, live, fp=fp)
        self._device_path = "mesh"
        return PendingBatch(
            bits, all_ok, n, list(self._precheck_fail), [], []
        )

    def _launch_device_delta(self, d):
        """Pack R||S + per-lane mid bytes; prefix/suffix/pubkey encodings
        live on device (ops.ed25519_verify.verify_batch_delta)."""
        import hashlib

        import jax

        from ..ops.ed25519_verify import (
            decompress_pubkeys_jit,
            verify_batch_delta_jit,
        )

        n = len(self._items)
        b = _bucket(n)
        self._oversize = []
        pub_blob = bytes(self._pub_buf)
        sig_arr = np.frombuffer(bytes(self._sig_buf), np.uint8).reshape(n, 64)
        midmax = d["midmax"]
        lcp, lcs = d["lcp"], d["lcs"]
        # one packed per-lane array + one tiny meta array: each
        # device_put pays a fixed per-transfer cost, unmeasured on
        # today's machine (same packing rationale as the 96-byte rsk
        # array)
        packed = np.zeros((b, 64 + midmax + 1), np.uint8)
        packed[:n, :64] = sig_arr
        take = min(midmax, d["arr"].shape[1] - lcp)
        if take > 0:
            packed[:n, 64 : 64 + take] = d["arr"][:, lcp : lcp + take]
        packed[:n, -1] = d["mid_lens"]
        from ..ops.ed25519_verify import (
            DELTA_META_HEADER as _MH,
            DELTA_META_LEN as _ML,
            DELTA_PMAX as _PM,
        )

        meta = np.zeros((_ML,), np.uint8)
        meta[0] = lcp
        meta[1] = lcs
        meta[2] = n & 0xFF
        meta[3] = (n >> 8) & 0xFF
        meta[4] = (n >> 16) & 0xFF
        meta[_MH : _MH + lcp] = d["arr"][0, :lcp]
        l0 = int(d["lens"][0])
        meta[_MH + _PM : _MH + _PM + lcs] = d["arr"][0, l0 - lcs : l0]
        # device-resident pubkey cache: decompressed points AND the raw
        # encodings (the SHA preimage needs A's 32 bytes on device)
        fp = (hashlib.sha256(pub_blob).digest(), b, "delta")
        cached = _A_CACHE.get(fp)
        if cached is None:
            a_bytes = np.zeros((b, 32), np.uint8)
            a_bytes[:n] = np.frombuffer(pub_blob, np.uint8).reshape(n, 32)
            a_dev = jax.device_put(a_bytes)
            ok_a, neg_a = decompress_pubkeys_jit(a_dev)
            cached = (ok_a, neg_a, a_dev)
            _A_CACHE[fp] = cached
            while len(_A_CACHE) > _A_CACHE_SIZE:
                _A_CACHE.pop(next(iter(_A_CACHE)))
        ok_a, neg_a, a_dev = cached
        global _LAST_WIRE_B_PER_LANE
        _LAST_WIRE_B_PER_LANE = packed.shape[1]
        return verify_batch_delta_jit(
            ok_a, neg_a, a_dev, *jax.device_put((packed, meta))
        )

    def _launch_device_sha(self):
        """Pack host-side (vectorized numpy, no per-item loops) and launch
        the fully-fused kernel (SHA-512 + Barrett + curve on device);
        returns the un-fetched (bucket,) device bitmap."""
        import jax.numpy as jnp

        from ..ops.ed25519_verify import verify_batch_jit
        from ..ops.sha512 import MAX_INPUT_BYTES, PADDED_BYTES, pad_messages

        n = len(self._items)
        b = _bucket(n)
        pub_arr = np.frombuffer(bytes(self._pub_buf), np.uint8).reshape(n, 32)
        sig_arr = np.frombuffer(bytes(self._sig_buf), np.uint8).reshape(n, 64)
        a_bytes = np.zeros((b, 32), np.uint8)
        r_bytes = np.zeros((b, 32), np.uint8)
        s_raw = np.zeros((b, 32), np.uint8)
        live = np.zeros((b,), bool)
        a_bytes[:n] = pub_arr
        r_bytes[:n] = sig_arr[:, :32]
        s_raw[:n] = sig_arr[:, 32:]
        live[:n] = True

        msg_words = np.zeros((b, 64), np.uint32)
        two_blocks = np.zeros((b,), bool)
        lens = np.asarray(self._msg_lens, np.int64)
        self._oversize = []
        max_msg = MAX_INPUT_BYTES - 64  # R||A prefix is 64 bytes
        if n and (lens == lens[0]).all() and lens[0] <= max_msg:
            # Uniform-length fast path (commit sign-bytes share a length):
            # build the padded SHA-512 blocks with whole-batch numpy ops.
            ln = int(lens[0])
            total = 64 + ln
            buf = np.zeros((n, PADDED_BYTES), np.uint8)
            buf[:, :32] = sig_arr[:, :32]
            buf[:, 32:64] = pub_arr
            if ln:
                buf[:, 64:total] = np.frombuffer(
                    bytes(self._msg_buf), np.uint8
                ).reshape(n, ln)
            buf[:, total] = 0x80
            bitlen = np.asarray(total * 8, dtype=">u8").tobytes()
            if total > 111:
                buf[:, 248:256] = np.frombuffer(bitlen, np.uint8)
                two_blocks[:n] = True
            else:
                buf[:, 120:128] = np.frombuffer(bitlen, np.uint8)
            msg_words[:n] = buf.reshape(n, 64, 4).astype(np.uint32) @ np.array(
                [1 << 24, 1 << 16, 1 << 8, 1], np.uint32
            )
        else:
            preimages = []
            for i, (pub, msg, sig) in enumerate(self._items):
                pre = sig[:32] + pub + msg
                if len(pre) > MAX_INPUT_BYTES:
                    self._oversize.append(i)  # host fallback at result()
                    crypto_metrics().gave_way_total.inc(1.0, "oversize")
                    pre = b""
                    live[i] = False
                preimages.append(pre)
            msg_words[:n], two_blocks[:n] = pad_messages(preimages)
        # Explicit async device_put: letting jit convert fresh numpy inputs
        # takes a synchronous path (its cost is unmeasured on today's
        # machine); device_put overlaps the copies with device compute.
        import jax

        return verify_batch_jit(
            *jax.device_put((a_bytes, r_bytes, s_raw, msg_words, two_blocks, live))
        )

def _observe_latency(p) -> None:
    """Record submit→result wall time into the per-path verify-latency
    histogram; idempotent (the first resolution wins)."""
    t0 = getattr(p, "_t0", None)
    if t0 is None:
        return
    p._t0 = None
    crypto_metrics().verify_seconds.observe(
        _time.perf_counter() - t0,
        getattr(p, "_path", None) or "unknown", "ed25519"
    )


def _await_verdict(p) -> tuple[bool, list[bool]]:
    """result() of an in-flight device batch: block on the summary
    scalar, then finalize. The span's `batch` is the id of the
    crypto.batch_verify span that submitted it (the two are one unit of
    work but not nested in time); `since_submit_ms`, taken as the fetch
    returns, is the device program as the host sees it."""
    with _trace.span("crypto.verdict_wait", path=p._path, n=p._n,
                     batch=p._batch) as sp:
        dev_all_ok = bool(np.asarray(p._all_ok))
        if p._t0 is not None:
            sp.add(since_submit_ms=round(
                (_time.perf_counter() - p._t0) * 1e3, 3))
        sp.add(blame_rerun=not dev_all_ok)
        return p._finalize_fast(dev_all_ok)


def _prefetch_summary(arr) -> None:
    """Start an async device->host copy of a summary scalar (no-op for
    host-resident or stubbed summaries)."""
    try:
        arr.copy_to_host_async()
    except AttributeError:
        pass


class PendingBatch:
    """Handle to an in-flight device batch; result() fetches and finalizes.

    Holds a snapshot of the per-batch host state, so the originating
    verifier can be mutated or reused after submit() without corrupting
    in-flight results. The happy path fetches only the device-reduced
    all-ok scalar (pure round-trip latency); the full bitmap transfers
    only when some lane failed."""

    __slots__ = ("_dev", "_all_ok", "_n", "_precheck_fail",
                 "_oversize_items", "_oversize_idx", "_path", "_t0",
                 "_batch")

    def __init__(self, dev, all_ok, n, precheck_fail, oversize_items,
                 oversize_idx):
        self._dev = dev
        self._all_ok = all_ok
        self._n = n
        self._precheck_fail = precheck_fail
        self._oversize_items = oversize_items
        self._oversize_idx = oversize_idx
        self._path = None
        self._t0 = None
        self._batch = None

    def _finalize(self, bits) -> tuple[bool, list[bool]]:
        out = [bool(x) and not bad for x, bad in zip(bits, self._precheck_fail)]
        for i, (pub, msg, sig) in zip(self._oversize_idx, self._oversize_items):
            out[i] = ref.verify(pub, msg, sig)  # rare >2-block messages
        return all(out), out

    def _finalize_fast(self, dev_all_ok: bool) -> tuple[bool, list[bool]]:
        """Resolve from the scalar summary alone when possible; falls back
        to the bitmap transfer on any failure."""
        _observe_latency(self)
        if dev_all_ok and not any(self._precheck_fail):
            bits = [True] * self._n
            ok = True
            for i, (pub, msg, sig) in zip(
                self._oversize_idx, self._oversize_items
            ):
                bits[i] = ref.verify(pub, msg, sig)
                ok = ok and bits[i]
            return ok, bits
        return self._finalize(np.asarray(self._dev)[: self._n])

    def prefetch(self) -> None:
        """Start the device->host copy of the summary scalar without
        blocking: the fetch costs a fixed round trip (unmeasured on
        today's machine), which a pipelined consumer (replay) can
        overlap with other work by prefetching as soon as the NEXT
        batch is queued."""
        _prefetch_summary(self._all_ok)

    def result(self) -> tuple[bool, list[bool]]:
        return _await_verdict(self)


class DonePending:
    """Already-resolved batch (native CPU path) behind the pending API."""

    __slots__ = ("_ok", "_bits", "_all_ok", "_path", "_t0", "_batch")

    def __init__(self, ok, bits):
        self._ok = ok
        self._bits = bits
        self._all_ok = np.asarray(ok)  # collect_pending stacks this
        self._path = None
        self._t0 = None
        self._batch = None

    def _finalize_fast(self, _dev_all_ok) -> tuple[bool, list[bool]]:
        _observe_latency(self)
        return self._ok, self._bits

    def prefetch(self) -> None:
        pass  # already host-resident

    def result(self) -> tuple[bool, list[bool]]:
        _observe_latency(self)
        return self._ok, self._bits


class PendingRLC:
    """In-flight RLC/MSM batch: a single device bool. On success every
    live lane verified (random-linear-combination soundness); on failure
    the per-lane bitmap kernel re-runs to attribute blame, mirroring the
    reference's batch->single fallback (types/validation.go:304-311)."""

    __slots__ = ("_all_ok", "_n", "_precheck_fail", "_items", "_path",
                 "_t0", "_batch")

    def __init__(self, all_ok, n, precheck_fail, items):
        self._all_ok = all_ok
        self._n = n
        self._precheck_fail = precheck_fail
        self._items = items
        self._path = None
        self._t0 = None
        self._batch = None

    def _finalize_fast(self, dev_all_ok: bool) -> tuple[bool, list[bool]]:
        _observe_latency(self)
        if dev_all_ok:
            bits = [not bad for bad in self._precheck_fail]
            return all(bits), bits
        # batch failed: per-lane fallback attributes individual blame
        bv = Ed25519BatchVerifier(backend="tpu", force_perlane=True)
        for pub, msg, sig in self._items:
            bv.add(Ed25519PubKey(pub), msg, sig)
        return bv.submit().result()

    def prefetch(self) -> None:
        _prefetch_summary(self._all_ok)

    def result(self) -> tuple[bool, list[bool]]:
        return _await_verdict(self)


def collect_pending(pendings: list[PendingBatch]) -> list[tuple[bool, list[bool]]]:
    """Resolve many in-flight batches with ONE tiny device→host transfer.

    Stacks the per-batch all-ok scalars on device and fetches them in a
    single round trip; only batches whose summary reports a failure pay
    the bitmap transfer."""
    import jax.numpy as jnp

    if not pendings:
        return []
    try:
        summaries = np.asarray(jnp.stack([p._all_ok for p in pendings]))
    except ValueError:
        # Streamed batches live on different mesh devices — jnp.stack
        # refuses committed arrays on conflicting devices. Fan in by
        # starting every D2H copy async first, then fetching: the
        # transfers overlap across chips, so the wall cost stays one
        # round trip, not one per device.
        for p in pendings:
            p.prefetch()
        summaries = np.asarray(
            [np.asarray(p._all_ok) for p in pendings]
        )
    return [p._finalize_fast(bool(s)) for p, s in zip(pendings, summaries)]


_LAST_WIRE_B_PER_LANE = _WIRE_LADDER_B  # introspection for bench/tools


def _detect_delta(items):
    """Longest-common-prefix/suffix structure detection over a batch's
    messages (vectorized numpy). Commit/replay sign bytes differ per
    lane only in the embedded vote timestamp, so most of the message is
    shared; the device rebuilds it (ops.ed25519_verify.build_delta_msgs)
    and only ~8-16 delta bytes cross the wire per lane. Returns the
    packing dict, or None when the messages don't share enough structure
    to beat the 96 B/lane host-hashed path."""
    from ..ops.sha512 import MAX_INPUT_BYTES

    msgs = [it[1] for it in items]
    n = len(msgs)
    if n == 0:
        return None
    lens = np.fromiter((len(m) for m in msgs), np.int64, n)
    maxlen = int(lens.max())
    minlen = int(lens.min())
    if minlen == 0 or maxlen > MAX_INPUT_BYTES - 64:
        return None
    flat = np.frombuffer(b"".join(msgs), np.uint8)
    off = np.concatenate([[0], np.cumsum(lens)])
    idx = off[:-1, None] + np.arange(maxlen)[None, :]
    arr = flat[np.clip(idx, 0, len(flat) - 1)] * (
        np.arange(maxlen) < lens[:, None]
    ).astype(np.uint8)
    inrange = np.arange(maxlen) < minlen
    common = (arr == arr[0:1]).all(axis=0) & inrange
    lcp = minlen if common.all() else int(np.argmin(common))
    ridx = off[1:, None] - 1 - np.arange(maxlen)[None, :]
    rev = flat[np.clip(ridx, 0, len(flat) - 1)]
    commons = (rev == rev[0:1]).all(axis=0) & inrange
    lcs = minlen if commons.all() else int(np.argmin(commons))
    lcs = min(lcs, minlen - lcp)
    mid_lens = lens - lcp - lcs
    midmax = max(8, -(-int(mid_lens.max()) // 8) * 8)
    if 64 + midmax + 1 >= _WIRE_LADDER_B:
        return None  # not enough shared structure to beat R||S||k
    return {
        "arr": arr,
        "lens": lens,
        "lcp": lcp,
        "lcs": lcs,
        "midmax": midmax,
        "mid_lens": mid_lens,
    }


def batch_verifier(backend: str = "tpu") -> Ed25519BatchVerifier:
    return Ed25519BatchVerifier(backend=backend)
