"""Device-mesh scale-out for the signature data plane.

The reference scales by fanning goroutines over peers (SURVEY §2.15); our
data-parallel axis is the *signature batch*: a 10k-validator commit becomes
one mega-batch sharded across TPU chips via shard_map, with a single psum
for the all-valid bit riding ICI (reference's equivalent "communication
backend" is its in-process NCCL-free TCP stack, p2p/ — on-device we use XLA
collectives instead; SURVEY §5.7/§5.8).

No NCCL/MPI translation: lay out the batch on the mesh, let XLA insert the
collectives.
"""

from __future__ import annotations

import hashlib
import logging
import os
import time as _time

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map as _shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops import ed25519_verify
from ..ops import field as _field
from ..utils import trace as _trace
from ..utils.metrics import crypto_metrics

_log = logging.getLogger(__name__)


def make_mesh(devices=None, axis: str = "sig") -> Mesh:
    devices = devices if devices is not None else jax.devices()
    return Mesh(np.asarray(devices), (axis,))


def make_mesh_2d(devices=None, hosts: int = 2) -> Mesh:
    """Hierarchical (host, sig) mesh for multi-host pods: the outer axis
    maps to hosts (collectives cross DCN), the inner to the chips of one
    host (collectives ride ICI). Lay out the batch over BOTH axes
    (sharded_verify_rsk_fn(mesh, ("host", "sig"))) and reduce
    hierarchically so only one scalar per host crosses DCN — the layout
    discipline from the scaling playbook (slow axis outermost)."""
    devices = devices if devices is not None else jax.devices()
    n = len(devices)
    if n % hosts:
        raise ValueError(f"{n} devices do not split over {hosts} hosts")
    return Mesh(
        np.asarray(devices).reshape(hosts, n // hosts), ("host", "sig")
    )


def pad_to_shards(n: int, parts: int, bucket: int | None = None) -> int:
    """Smallest padded batch size that (a) holds n lanes, (b) is at
    least the pre-bucketed size (so mesh submits reuse the bucket-tier
    compile discipline), and (c) divides evenly over `parts` shards.

    Handles every mesh-boundary edge case: n < parts (every device
    still gets an equal, partially-dead shard), prime n, and n == 0
    (one all-dead shard per device so the compiled graph shape holds).
    Dead lanes ride with live=False and are masked out of the psum.
    """
    b = max(int(bucket or 0), int(n), 1)
    return -(-b // parts) * parts


def _specs(axes_t: tuple[str, ...]):
    """(lanes-first spec, lanes-last spec) over the named axes: the byte
    and flag arrays are (B, ...), a point's limb arrays (NLIMBS, B)."""
    ax = axes_t if len(axes_t) > 1 else axes_t[0]
    return P(ax), P(None, ax)


def sharded_decompress_pubkeys_fn(
        mesh: Mesh, axes: str | tuple[str, ...] = "sig"):
    """The mesh's staging program: ops/ed25519_verify.decompress_pubkeys
    in every shard, no collective.

    In: a_bytes (B,32)u8 sharded over the named axes. Out: what the
    single chip's decompress_pubkeys_jit returns, (ok_a, (-A,
    [2^128](-A))), left where it was computed: ok_a (B,) sharded like
    the bytes, every limb array of the two points (NLIMBS, B) sharded on
    its last axis, the lanes. The mapped function is decompress_pubkeys
    itself, so the program is jit(decompress_pubkeys) to jax.monitoring
    and to a device trace, as the single chip's is."""
    axes_t = (axes,) if isinstance(axes, str) else tuple(axes)
    spec_b, spec_limbs = _specs(axes_t)
    fn = _shard_map(
        ed25519_verify.decompress_pubkeys,
        mesh=mesh,
        in_specs=(spec_b,),
        out_specs=(spec_b, spec_limbs),
        check_vma=False,
    )
    return jax.jit(fn)


def sharded_verify_rsk_fn(mesh: Mesh, axes: str | tuple[str, ...] = "sig"):
    """The production mesh verifier: prehashed 96-byte R||S||k lanes
    against a column that sharded_decompress_pubkeys_fn staged.

    Inputs: ok_a (B,) bool and a_points, the pair (-A, [2^128](-A)) with
    limb arrays (NLIMBS, B), both as the staging program left them on
    the shards; rsk (B,96)u8 packed R||S||k rows (k = SHA-512(R||A||M)
    mod L hashed host-side — the same wire diet the single-chip ladder
    path won with), live (B,) bool. B must divide by the product of the
    named mesh axes (pad_to_shards). Every shard runs what the single
    chip runs, ed25519_verify.verify_batch_cached_a: R's decompression
    and the 32-window ladder over the kept pair; nothing of A is
    computed here.

    Returns (all_ok scalar replicated, bits (B,) sharded). The
    invalid-lane count psums innermost-axis-first: on a hierarchical
    (host, sig) mesh partial sums ride ICI within each host and one
    scalar per host crosses DCN.
    """
    axes_t = (axes,) if isinstance(axes, str) else tuple(axes)

    def local(ok_a, a_points, rsk, live):
        bits, _ = ed25519_verify.verify_batch_cached_a(
            ok_a, a_points, rsk, live)
        bad = jnp.sum((~bits & live).astype(jnp.int32))
        for ax in reversed(axes_t):  # innermost (fast) axis first
            bad = jax.lax.psum(bad, ax)
        return bad == 0, bits

    spec_b, spec_limbs = _specs(axes_t)
    fn = _shard_map(
        local,
        mesh=mesh,
        in_specs=(spec_b, spec_limbs, spec_b, spec_b),
        out_specs=(P(), spec_b),
        check_vma=False,
    )
    return jax.jit(fn)


# Dispatch-term fallbacks when calibration fails. put_fixed: each shard's
# H2D staging pays a fixed per-transfer cost on top of the bytes (the
# same fixed cost the single-chip path's array-packing work avoids;
# ~100 us is the class of a local PCIe link, not a reading taken on
# today's machine — dispatch_terms() measures it at first use).
# collective: one psum across the mesh per launch (ICI hop latency
# class, not bandwidth; unmeasured on chips).
_PUT_FIXED_US_FALLBACK = 100.0
_COLLECTIVE_US_FALLBACK = 60.0

_A_CACHE_SIZE = 4


class MeshVerifyEngine:
    """Owns a device mesh, the compiled sharded programs for it and the
    validator columns staged on its shards.

    What is kept (_a_cache, _A_CACHE_SIZE columns, keyed by the column's
    sha256 and the padded batch size): ops/ed25519_verify's decompressed
    pair (ok_a, (-A, [2^128](-A))) of a pubkey column, every shard
    holding its own lanes (704 B a lane: 1.8 MB a device an entry at the
    10240 bucket). Replay and consensus verify the SAME validator set
    height after height, so A's sqrt decompression and the 128 doublings
    run once a set and every submit's ladder is the single chip's 32
    windows; a column not seen before pays the staging program once.

    Two serving modes, both driven from ed25519's dispatch:

    - submit(): ONE mega-batch sharded over every device (batch axis =
      'sig'; on multi-process pods the outer 'host' axis keeps the psum
      hierarchical). Used when a single batch is big enough that
      splitting its device time d ways beats one chip.
    - next_device(): round-robin placement for *independent* batches
      (streamed commits): each whole batch lands on one chip, so d
      commits verify concurrently with no collective at all. The
      caller's in-flight pipeline (submit()/collect_pending) is the
      per-device queue; H2D staging for device i+1 overlaps compute on
      device i because device_put is async.
    """

    def __init__(self, devices=None, hosts: int | None = None):
        devices = list(devices if devices is not None else jax.devices())
        if not devices:
            raise ValueError("mesh engine needs at least one device")
        self.devices = devices
        self.n_devices = len(devices)
        if hosts is None:
            nproc = getattr(jax, "process_count", lambda: 1)()
            hosts = nproc if nproc > 1 and self.n_devices % nproc == 0 else 1
        if hosts > 1:
            self.axes = ("host", "sig")
            self.mesh = Mesh(
                np.asarray(devices).reshape(hosts, self.n_devices // hosts),
                self.axes,
            )
        else:
            self.axes = ("sig",)
            self.mesh = Mesh(np.asarray(devices), self.axes)
        self._spec = _specs(self.axes)[0]
        self._sharding = NamedSharding(self.mesh, self._spec)
        self._fns: dict[int, object] = {}  # padded B -> compiled verifier
        self._stage = sharded_decompress_pubkeys_fn(self.mesh, self.axes)
        # (sha256(pub col), B) -> staged (ok_a, (-A, [2^128](-A)))
        self._a_cache: dict = {}
        self._rr = 0
        self._terms: dict | None = None
        crypto_metrics().mesh_devices.set(float(self.n_devices))

    # -- dispatch terms ------------------------------------------------

    def dispatch_terms(self) -> dict:
        """{'put_fixed_s', 'collective_s', 'calibrated'} for
        dispatch_model's mesh entry; the H2D fixed cost is measured on
        THIS runtime at first use (one tiny staged transfer — no kernel
        compile, so first dispatch stays cheap), the collective term is
        the documented fallback until a bench refines it via
        set_collective_s()."""
        if self._terms is None:
            terms = {
                "put_fixed_s": _PUT_FIXED_US_FALLBACK * 1e-6,
                "collective_s": _COLLECTIVE_US_FALLBACK * 1e-6,
                "calibrated": False,
            }
            try:
                buf = np.zeros((self.n_devices * 64, 96), np.uint8)
                jax.block_until_ready(
                    jax.device_put(buf, self._sharding))  # warm path
                best = float("inf")
                for _ in range(2):
                    t0 = _time.perf_counter()
                    jax.block_until_ready(
                        jax.device_put(buf, self._sharding))
                    best = min(best, _time.perf_counter() - t0)
                # per-device share of the fixed staging cost
                terms["put_fixed_s"] = best / self.n_devices
                terms["calibrated"] = True
            except Exception:
                pass
            self._terms = terms
        return self._terms

    def set_collective_s(self, seconds: float) -> None:
        """Refine the collective-latency term from a measured sharded
        run (bench/workloads feed this back)."""
        self.dispatch_terms()["collective_s"] = max(float(seconds), 0.0)

    # -- sharded mega-batch path ---------------------------------------

    def _fn(self, b: int):
        fn = self._fns.get(b)
        if fn is None:
            shard = b // self.n_devices
            if _field._on_tpu() and not _field.kernel_width(shard):
                _log.warning(
                    "mesh batch %d over %d devices gives %d lanes a shard, "
                    "which the Pallas kernels do not tile: this shape runs "
                    "the XLA value-form", b, self.n_devices, shard)
            fn = self._fns[b] = sharded_verify_rsk_fn(self.mesh, self.axes)
        return fn

    @staticmethod
    def _column(pubkeys, b: int) -> np.ndarray:
        """The (b,32) array of a column's encodings, zero rows behind
        the last key (dead lanes: live=False masks them)."""
        rows = np.frombuffer(pubkeys, np.uint8).reshape(-1, 32)
        a_bytes = np.zeros((b, 32), np.uint8)
        a_bytes[:len(rows)] = rows
        return a_bytes

    def stage_pubkeys(self, pubkeys, b: int):
        """The decompressed pair of a pubkey column on the shards:
        (staged, "hit" | "miss"). `pubkeys` is the column's 32-byte
        encodings as one buffer, at most b of them.

        A column seen before (same bytes, same b) is a hit and costs a
        dict lookup: nothing of A is built, crosses the link or is
        computed. A miss pads the column to b rows, puts it on the
        shards and runs the staging program there once
        (sharded_decompress_pubkeys_fn); what that returns stays where
        it was computed and is what every later submit of the column
        hands the verifier. The oldest of more than _A_CACHE_SIZE
        columns goes. crypto_a_cache_total{result} counts both, as it
        does for the single chip's _A_CACHE."""
        key = (hashlib.sha256(pubkeys).digest(), b)
        staged = self._a_cache.get(key)
        a_cache = "miss" if staged is None else "hit"
        crypto_metrics().a_cache_total.inc(1.0, a_cache)
        if staged is None:
            staged = self._stage(
                jax.device_put(self._column(pubkeys, b), self._sharding))
            self._a_cache[key] = staged
            while len(self._a_cache) > _A_CACHE_SIZE:
                self._a_cache.pop(next(iter(self._a_cache)))
        return staged, a_cache

    def submit(self, pubkeys, rsk: np.ndarray, live: np.ndarray):
        """Launch one sharded verify; returns un-fetched device arrays
        (all_ok scalar, bits (B,)). B = rsk.shape[0] must be a
        pad_to_shards() multiple of n_devices; dead lanes carry
        live=False and are masked from the psum. `pubkeys` is
        stage_pubkeys': the column is hashed every call and read only
        when it is not staged yet."""
        b = rsk.shape[0]
        if b % self.n_devices:
            raise ValueError(
                f"batch {b} does not shard over {self.n_devices} devices "
                "(pad with pad_to_shards)"
            )
        t0 = _time.perf_counter()
        (ok_a, a_points), a_cache = self.stage_pubkeys(pubkeys, b)
        rsk_dev, live_dev = jax.device_put((rsk, live), self._sharding)
        all_ok, bits = self._fn(b)(ok_a, a_points, rsk_dev, live_dev)
        m = crypto_metrics()
        for i in range(self.n_devices):
            m.mesh_batches_total.inc(1.0, str(i), "shard")
        if _trace.enabled:
            _trace.emit(
                "crypto.mesh_submit", "span",
                dur_ms=round((_time.perf_counter() - t0) * 1e3, 3),
                n=int(live.sum()), b=b, n_devices=self.n_devices,
                shard_lanes=b // self.n_devices,
                a_cache=a_cache,
                **({"bytes": b * 32} if a_cache == "miss" else {}),
            )
        return all_ok, bits

    # -- streamed independent-batch path -------------------------------

    def next_device(self):
        """Round-robin target for the next independent (streamed) batch;
        the per-device counter is the flight recorder's skew signal."""
        i = self._rr % self.n_devices
        self._rr += 1
        crypto_metrics().mesh_batches_total.inc(1.0, str(i), "stream")
        return self.devices[i]


_ENGINE = None
_ENGINE_PROBED = False


def get_engine(accel_backed: bool = True):
    """Process-wide engine, or None when the mesh path is off.

    Policy (COMETBFT_TPU_MESH):
      - "0"/"off": disabled.
      - unset: auto — enabled when a real accelerator backs jax AND
        more than one device exists (on CPU-only hosts the native
        engine dominates every device path, so virtual-device meshes
        never capture production batches by default).
      - "1"/"on"/"auto": enabled over every device (the bench/test seam
        for the virtual CPU mesh).
      - N >= 2: enabled over the first N devices.
    """
    global _ENGINE, _ENGINE_PROBED
    if _ENGINE_PROBED:
        return _ENGINE
    env = os.environ.get("COMETBFT_TPU_MESH", "").strip().lower()
    engine = None
    # a mesh that the policy turns on and that cannot be built raises
    # (as does a value that is none of the spellings above): the
    # caller must not carry on with one chip without a word
    if env in ("", "1", "on", "auto"):
        if (env or accel_backed) and len(jax.devices()) > 1:
            engine = MeshVerifyEngine()
    elif env not in ("0", "off"):
        n = int(env)
        devs = jax.devices()
        if n >= 2 and len(devs) >= 2:
            engine = MeshVerifyEngine(devs[: min(n, len(devs))])
    _ENGINE = engine
    _ENGINE_PROBED = True
    return _ENGINE


def reset_engine() -> None:
    """Test seam: drop the cached engine so the next get_engine() call
    re-reads the environment."""
    global _ENGINE, _ENGINE_PROBED
    _ENGINE = None
    _ENGINE_PROBED = False
