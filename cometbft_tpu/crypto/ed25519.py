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

# (sha256(pubkey column), bucket) -> device-resident (ok_a, (-A, [2^128](-A)))
# from ops.ed25519_verify.decompress_pubkeys; see _launch_device.
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

# The dispatch model (dispatch_model) carries host, wire and device terms
# for each device engine; the slowest stage of an engine is its time.
#
# The device terms are readings of ONE v5e (DEVICE_KIND; the chip tool's
# machine): the ladder as submit() launches it, warm, device time a batch
# from the profiler trace (5 batches, by kernel scope, the reduction of
# tools/trace_analyze.py device), at the live lane counts of the two
# buckets the benchmark's cells use. A term is the line through its two
# readings, fixed + n * per-lane (`python chip_smoke.py --terms` measures
# both again; with `--chips 4` the mesh's line beside this one over d):
#   lanes (bucket)    the cached pair, 32 windows (2026-10-01, PR 39)
#   10,000 (10240)    15.45 ms
#   65,000 (65536)    98.69 ms
# Both device engines run this program: the single chip's ladder is given
# the pair (A, [2^128]A) that _A_CACHE keeps, a mesh's shards the pair the
# engine staged on them (parallel/mesh.py), so the mesh's term is this
# line's per-lane part over the device count, plus the collective. Read
# on four v5e beside it (`chip_smoke.py --terms --chips 4`, 2026-10-04,
# PR 44; device time a shard from the profile, the mean over the devices):
#   lanes (bucket)    a shard of the mesh    this line over 4 + collective
#   10,000 (10240)     3.889 ms               4.16 ms
#   65,000 (65536)    24.701 ms              24.97 ms
# which is 0.10 ms + n * 0.378 us: the per-lane part to the digit, the
# fixed part 0.27 ms under what the model carries (it errs toward the
# single chip). A column the mesh has not staged pays the staging program
# first: 3.72 / 13.46 ms as a blocked call at the two buckets (the single
# chip's miss: 8.62 / 46.95 ms). The model describes a hit, for both.
# (The one-point, 64-window program read 20.48 / 130.87 ms, PR 25; nothing
# launches it since PR 44. The RLC/MSM engine read 120.19 / 278.31 ms
# there and was removed by PR 28.)
_DEV_LADDER_FIXED_MS = 0.32  # v5e profile, 2026-10-01, PR 39 (the pair)
_DEV_LADDER_US = 1.513       # the same two readings
# The host-side per-sig term is CALIBRATED at the first dispatch decision
# (_host_terms: one small timed pack_rsk) because it moves with the host:
# core speed, toolchain presence. This is the fallback when that fails:
_HOST_LADDER_US = 1.6        # pack_rsk on one thread (the v5e's host: 1.0-1.2)
_WIRE_LADDER_B = 96          # R||S||k per lane

_LINK_MBPS: float | None = None

# big-endian bytes of the group order, for the vectorized S < L precheck
_L_BE = np.frombuffer(
    (2**252 + 27742317777372353535851937790883648493).to_bytes(32, "big"),
    np.uint8,
)


def _link_mbps() -> float:
    """One-time host->device bandwidth probe (2 MiB device_put). Drives
    the wire terms of dispatch_model; every path is correct, the model
    only picks the faster one for the hardware at hand."""
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
    """Measure the per-sig host cost of the ladder's pack stage on THIS
    host: one timed pack_rsk over 1024 lanes, which the packer does in
    one chunk on the calling thread. So the term is the ONE-THREAD rate:
    what a batch pays a lane when the worker pool is taken, and an upper
    bound on what a mega-batch pays when it is free (a fifth of it on
    the v5e's 13-core host: `python chip_smoke.py --terms`). The ladder
    and the mesh share the term, so it cannot turn their choice; their
    device terms decide. Returns the
    fallback constant when anything goes wrong: dispatch must keep
    picking sanely on a box where the probe can't run."""
    from . import native

    terms = {"ladder_us": _HOST_LADDER_US, "calibrated": False}
    try:
        if native.available():
            n = 1024
            rnd = np.random.default_rng(0xD15BA7C4)
            pub_blob = rnd.integers(0, 256, n * 32, np.uint8).tobytes()
            sig_blob = rnd.integers(0, 256, n * 64, np.uint8).tobytes()
            msg_blob = rnd.integers(0, 256, n * 100, np.uint8).tobytes()
            msg_lens = np.full(n, 100, np.uint64)
            out_rsk = np.empty((n, 96), np.uint8)
            best = float("inf")
            for _ in range(2):
                t0 = _time.perf_counter()
                okp = native.pack_rsk(n, sig_blob, pub_blob, msg_blob,
                                      msg_lens, out_rsk)
                best = min(best, _time.perf_counter() - t0)
            if okp is not None:
                terms["ladder_us"] = best / n * 1e6
        terms["calibrated"] = True
    except Exception:
        pass
    return terms


def _host_terms() -> dict:
    """Calibrated host-stage per-sig terms, measured once per process at
    the first dispatch decision (~a few ms)."""
    global _HOST_TERMS
    if _HOST_TERMS is None:
        _HOST_TERMS = _calibrate_host_terms()
    return _HOST_TERMS


def dispatch_model(n: int, b: int) -> dict:
    """The modeled per-stage times (seconds) behind the mesh-vs-ladder
    dispatch, exposed for the crossover tests: each path's pipelined
    throughput is bound by the slowest of its host / wire / device
    stages."""
    bw = _link_mbps() * 1e6  # bytes/sec
    host = _host_terms()
    ladder = {
        "wire": _WIRE_LADDER_B * b / bw,
        "device": _DEV_LADDER_FIXED_MS * 1e-3 + n * _DEV_LADDER_US * 1e-6,
        "host": n * host["ladder_us"] * 1e-6,
    }
    out = {
        "link_mbps": _LINK_MBPS,
        "host_terms": host,
        "ladder": ladder,
        "t_ladder": max(ladder.values()),
    }
    eng = _mesh_engine()
    if eng is not None and eng.n_devices > 1:
        # Sharded-mesh term: a shard runs the ladder's own program on
        # its lanes, so the per-lane part of the ladder's device time
        # splits d ways (its fixed part is paid by every shard) but
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


def _mesh_beats_single(n: int, b: int) -> bool:
    """Sharded mesh vs the single-chip ladder: honest per-batch pick
    from the same stage model."""
    m = dispatch_model(n, b)
    return "t_mesh" in m and m["t_mesh"] < m["t_ladder"]


# Below this size the native C++ verifier wins: a commit-sized batch
# finishes in well under a TPU dispatch round trip (batch-size-aware
# dispatch — reference types/validation.go:26-53 picks batch vs single
# by support; we additionally pick the backend by size). The native
# engine is the 8-lane AVX-512 IFMA Pippenger when the host supports
# it (csrc/ed25519_ifma.inc), portable C++ otherwise.
NATIVE_MAX = 1024

# The device terms of the dispatch model (_DEV_LADDER_*: PR 39's
# readings) and the wire-byte term were measured on ONE device kind, a
# TPU v5e, which jax reports as this device_kind.
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
# already hides its wire under compute.
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
    ):
        self._items: list[tuple[bytes, bytes, bytes]] = []
        self._precheck_fail: list[bool] = []
        self.backend = backend
        self._force_perlane = force_perlane
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
        # _items only on the paths that need per-item access (the host
        # engine, the cpu oracle, the Python packing fallback): the
        # device paths never do
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

        Three engines, picked from what the process observes: the host
        C++ engine below _native_limit, the sharded mesh where one is up
        and the model gives it the batch, the single-chip ladder
        otherwise (and always under force_perlane).
        """
        n = self.count()
        t0 = _time.perf_counter()
        pending = None
        # one span per dispatch: the host time inside submit(), split by
        # its children (materialize, pack, device_launch or native_verify)
        with _trace.span("crypto.batch_verify", n=n,
                         bucket=_bucket(n)) as sp:
            if not self._force_perlane:
                if n < _native_limit(n):
                    path, pending = "native", self._native_batch()
                if pending is None and n >= MESH_MIN:
                    eng = _mesh_engine()
                    if eng is not None and _mesh_beats_single(
                            n, _bucket(n)):
                        path, pending = "mesh", self._launch_mesh(eng)
            if pending is None:
                path, pending = "ladder", self._launch_device()
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
        """Synchronous batch on the host C++ engine (its own random-
        linear-combination batch equation, one Pippenger MSM), then
        per-signature blame if it fails; None when the native engine is
        unavailable (caller tries device paths)."""
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

    def _launch_device(self) -> "PendingBatch":
        """The single-chip per-lane ladder: pack host-side, hash
        host-side, launch the curve kernel.

        The challenge k = SHA-512(R||A||M) mod L is computed on the host
        (~1 us/sig): shipping 32 bytes of scalar instead of 256 bytes of
        padded message halves the wire cost twice over, and on a
        bandwidth-limited host->device link the transfer is what bounds
        sustained throughput."""
        import hashlib

        import jax

        from ..ops.ed25519_verify import (
            decompress_pubkeys_jit,
            verify_batch_cached_a_jit,
        )

        n = self.count()
        b = _bucket(n)
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
        with _trace.span("crypto.device_launch",
                         bytes=rsk.nbytes + live.nbytes) as sp:
            cached = _A_CACHE.get(fp)
            a_cache = "miss" if cached is None else "hit"
            sp.add(a_cache=a_cache)
            crypto_metrics().a_cache_total.inc(1.0, a_cache)
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
            ok_a, a_points = cached
            if dev is not None and _trace.enabled:
                _trace.emit("crypto.stream_place", "event",
                            device=str(getattr(dev, "id", dev)), n=n, b=b)
            bits, all_ok = verify_batch_cached_a_jit(
                ok_a, a_points, *jax.device_put((rsk, live), dev)
            )
        # Snapshot per-batch state: the verifier may be reused/mutated
        # after submit() without corrupting in-flight results.
        return PendingBatch(bits, all_ok, n, list(self._precheck_fail))

    def _pack_rsk_live(self, n: int, b: int):
        """Pack the (b,96) R||S||k rows + live mask shared by the
        single-chip prehashed ladder and the sharded mesh paths (k
        hashed host-side; see _launch_device's docstring). The native
        packer spreads the lanes over the C++ worker pool when it is
        free; `pool` on the span and `mode` on the counter say what it
        did: run (pooled), busy (another engine held the pool: packed
        on this thread), small (too few lanes to split), python (no
        native library)."""
        import hashlib

        pub_blob = self._pub_buf  # zero-copy; hashed + copied by callers
        rsk = np.zeros((b, 96), np.uint8)
        live = np.zeros((b,), bool)
        live[:n] = True
        from . import native

        with _trace.span("crypto.pack", n=n, bucket=b) as sp:
            chunks = native.pack_rsk(
                n, self._sig_buf, pub_blob, self._msg_buf,
                np.asarray(self._msg_lens, np.uint64), rsk,
            ) if native.available() else None
            if chunks is None:
                self._materialize()
                sig_blob = bytes(self._sig_buf)
                rsk[:n, :64] = np.frombuffer(
                    sig_blob, np.uint8).reshape(n, 64)
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
            mode = ("python" if chunks is None else "busy" if chunks == 0
                    else "small" if chunks == 1 else "run")
            sp.add(chunks=chunks or 1, pool=mode)
        crypto_metrics().pack_total.inc(1.0, mode)
        return rsk, live, pub_blob

    def _launch_mesh(self, eng):
        """Shard one mega-batch over every mesh device: same 96 B/lane
        prehashed wire as the ladder path, padded so B divides the mesh
        (dead lanes ride live=False and are masked from the psum), with
        the pubkey column decompressed once per validator set and kept
        on the engine's shards: a column the engine has seen costs this
        call one hash of its bytes, and no array of it is built.
        Returns a PendingBatch over the un-fetched replicated all-ok
        scalar + sharded bitmap."""
        from ..parallel.mesh import pad_to_shards

        n = self.count()
        b = pad_to_shards(n, eng.n_devices, bucket=_bucket(n))
        rsk, live, pub_blob = self._pack_rsk_live(n, b)
        # the engine reads the column only when it is new to it
        all_ok, bits = eng.submit(pub_blob, rsk, live)
        return PendingBatch(bits, all_ok, n, list(self._precheck_fail))


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

    __slots__ = ("_dev", "_all_ok", "_n", "_precheck_fail", "_path", "_t0",
                 "_batch")

    def __init__(self, dev, all_ok, n, precheck_fail):
        self._dev = dev
        self._all_ok = all_ok
        self._n = n
        self._precheck_fail = precheck_fail
        self._path = None
        self._t0 = None
        self._batch = None

    def _finalize_fast(self, dev_all_ok: bool) -> tuple[bool, list[bool]]:
        """Resolve from the scalar summary alone when possible; falls back
        to the bitmap transfer on any failure."""
        _observe_latency(self)
        if dev_all_ok and not any(self._precheck_fail):
            return True, [True] * self._n
        bits = np.asarray(self._dev)[: self._n]
        out = [bool(x) and not bad
               for x, bad in zip(bits, self._precheck_fail)]
        return all(out), out

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


def batch_verifier(backend: str = "tpu") -> Ed25519BatchVerifier:
    return Ed25519BatchVerifier(backend=backend)
