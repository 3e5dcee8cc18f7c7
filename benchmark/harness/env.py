"""What the harness observes of the process: the device, the compile cache,
what compiled and when (jax.monitoring), the program's own counters, and the
interpreter's garbage collector."""

from __future__ import annotations

import collections
import gc
import json
import os
import sys
import threading
import time

from .spec import ROOT

DEVICE_PATHS = ("ladder", "mesh")
HOST_PATHS = ("native", "cpu")
# The jitted verify programs of the program's data plane, by the fun_name
# jax.monitoring gives a compile: ops/ed25519_verify's two, and the mesh's
# (parallel/mesh.sharded_verify_rsk_fn jits a shard_map of its inner `local`,
# so the event says jit(local) and the device trace jit_local). One of these
# compiling, or being read from the cache, inside the measured window makes
# the run incorrect.
VERIFY_PROGRAMS = ("decompress_pubkeys", "verify_batch_cached_a", "jit(local)")


def log(msg: str) -> None:
    print(msg, flush=True)


def place_compile_cache() -> str:
    """jax's persistent cache at ONE fixed path inside the checkout (the path
    is part of the cache key), unless JAX_COMPILATION_CACHE_DIR says where.
    Called before the program is imported, so the program's own default
    (the same directory) never decides."""
    import jax

    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not env:
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(ROOT, ".jax_cache"))
    cache = jax.config.jax_compilation_cache_dir
    n = len(os.listdir(cache)) if os.path.isdir(cache) else 0
    log(f"compile cache: {cache} "
        f"({'from JAX_COMPILATION_CACHE_DIR' if env else 'fixed path in the checkout'}), "
        f"{n} entries")
    return cache


def claim_device(chips: int, rehearse: bool) -> dict:
    """The device as jax reports it; exits 2 unless it is `chips` TPU chips
    (or --rehearse)."""
    import jax

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if not rehearse and (device["platform"] != "tpu" or len(devs) < chips):
        print(f"benchmark: this cell needs {chips} TPU chip(s); jax found "
              f"{device}. (--rehearse runs a shrunk cell on any platform.)",
              file=sys.stderr)
        raise SystemExit(2)
    if device["platform"] == "tpu":
        peaks(device["kind"])  # a device that is not in the table is an error
    log(f"device: {json.dumps(device)} jax {jax.__version__}"
        + (" REHEARSAL: sizes shrunk, no number here is a device number"
           if rehearse else ""))
    return device


def memory_peak_bytes() -> int:
    import jax

    peak = 0
    for d in jax.devices():
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


class CompileWatch:
    """Every backend compile (or cache read) with its wall-clock time."""

    def __init__(self):
        import jax

        self.events: list[dict] = []
        self._hit = threading.local()
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(self._on_dur)

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self._hit.flag = True

    def _on_dur(self, event: str, secs: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.events.append({
                "fn": str(kw.get("fun_name", "?")), "s": secs,
                "t": time.perf_counter(),
                "cache_hit": bool(getattr(self._hit, "flag", False)),
            })
            self._hit.flag = False

    @staticmethod
    def is_verify(fn: str) -> bool:
        return any(v in fn for v in VERIFY_PROGRAMS)

    def between(self, t0: float, t1: float) -> list[dict]:
        return [e for e in self.events if t0 <= e["t"] <= t1]

    def summary(self) -> dict:
        ev = self.events
        ver = [e for e in ev if self.is_verify(e["fn"])]
        return {"programs": len(ev), "seconds": sum(e["s"] for e in ev),
                "verify_compiled": sum(not e["cache_hit"] for e in ver),
                "verify_cache_hits": sum(e["cache_hit"] for e in ver)}


class Counters:
    """Snapshots of the program's crypto counters; `delta` is what moved."""

    def __init__(self):
        from cometbft_tpu.utils.metrics import crypto_metrics

        self.m = crypto_metrics()

    def snap(self) -> dict:
        return {
            "path_selected_total": dict(self.m.path_selected_total.values()),
            "gave_way_total": dict(self.m.gave_way_total.values()),
            "batch_size": {k: dict(v) for k, v in
                           self.m.batch_size.snapshot().items()},
        }

    @staticmethod
    def delta(a: dict, b: dict) -> dict:
        out = {}
        for name in ("path_selected_total", "gave_way_total"):
            out[name] = {k: v - a[name].get(k, 0.0) for k, v in b[name].items()
                         if v != a[name].get(k, 0.0)}
        lanes = batches = 0.0
        for k, v in b["batch_size"].items():
            prev = a["batch_size"].get(k, {"count": 0, "sum": 0.0})
            lanes += v["sum"] - prev["sum"]
            batches += v["count"] - prev["count"]
        out["lanes"] = lanes
        out["batches"] = batches
        return out


class GcWatch:
    """Every collection of the interpreter's garbage collector with its
    generation, start and pause (gc.callbacks); on in traced runs only. A
    full collection (generation 2) walks every tracked object: at 1.5 M
    objects it pauses the caller for about half a second (PR 23)."""

    def __init__(self):
        self.pauses: list[tuple[int, float, float]] = []  # (gen, t0, s)
        self._t0 = 0.0
        gc.callbacks.append(self._on)

    def _on(self, phase: str, info: dict) -> None:
        now = time.perf_counter()
        if phase == "start":
            self._t0 = now
        else:
            self.pauses.append((info["generation"], self._t0, now - self._t0))

    def close(self) -> None:
        if self._on in gc.callbacks:
            gc.callbacks.remove(self._on)

    def between(self, generation: int, t0: float, t1: float) -> list[float]:
        return [s for g, t, s in self.pauses if g == generation and t0 <= t <= t1]


def tracked_objects(census: int = 0):
    """How many objects the collector tracks (what a full collection walks);
    with `census`, also the most numerous types."""
    objs = gc.get_objects()
    if not census:
        return len(objs), []
    kinds = collections.Counter(type(o).__name__ for o in objs)
    return len(objs), kinds.most_common(census)


def dispatch_counts(delta: dict) -> tuple[float, float]:
    """(device batches, host batches) among the ed25519 dispatch's own labels
    of crypto_path_selected_total (it also carries verify_commit's per-curve
    partition labels, which are not dispatch decisions)."""
    dev = host = 0.0
    for key, v in delta["path_selected_total"].items():
        path, curve = key[0], key[1] if len(key) > 1 else ""
        if curve != "ed25519":
            continue
        if path in DEVICE_PATHS:
            dev += v
        elif path in HOST_PATHS:
            host += v
    return dev, host


def peaks(device_kind: str) -> dict:
    """The table of peaks, keyed by device_kind; an unknown device is an
    error, never a default."""
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "peaks.json"), encoding="utf-8") as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks recorded for device kind {device_kind!r}")
    return table[device_kind]
