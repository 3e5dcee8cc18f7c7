"""From the profiler's trace to busy and idle time, the device operations that
took most time, and the idle gaps by what the host was doing.

Two steps, so the second can be tested on a small recorded trace:
  load_xplane(path) -> {"planes": [{"name", "lines": [{"name", "events":
                        [[name, start_ns, dur_ns], ...]}]}]}
  reduce_trace(trace, host_names) -> the numbers.
Device planes are the ones named /device:TPU:<i>; their "XLA Ops" line holds
one event per operation executed, "XLA Modules" one per program run.
"""

from __future__ import annotations

import bisect
import glob
import os
import time

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


class Profiler:
    """jax.profiler around one steady stretch; host tracing at its lowest
    level that still keeps TraceAnnotations, no Python tracer."""

    def __init__(self, out_dir: str):
        self.dir = out_dir
        self.active = False
        self.t_on = self.t_off = None  # perf_counter at start(), after stop()

    def start(self) -> None:
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        self.t_on = time.perf_counter()
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.active = True

    def stop(self) -> None:
        import jax

        jax.profiler.stop_trace()
        self.active = False
        self.t_off = time.perf_counter()

    def xplane(self) -> str:
        found = sorted(glob.glob(os.path.join(
            self.dir, "plugins", "profile", "*", "*.xplane.pb")))
        if not found:
            raise RuntimeError(f"the profiler left no trace under {self.dir}")
        return found[-1]


def load_xplane(path: str, keep_host: tuple = ()) -> dict:
    """Device planes in full; of the host planes only events whose name is in
    keep_host (the harness's own annotations)."""
    import jax

    pd = jax.profiler.ProfileData.from_file(path)
    planes = []
    for plane in pd.planes:
        is_dev = plane.name.startswith("/device:")
        if not is_dev and not plane.name.startswith("/host:"):
            continue
        lines = []
        for line in plane.lines:
            evs = [[e.name, float(e.start_ns), float(e.duration_ns)]
                   for e in line.events
                   if is_dev or e.name in keep_host]
            if evs:
                lines.append({"name": line.name, "events": evs})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def merge(intervals):
    """Union of [start, end) intervals, sorted, as a list of [start, end]."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _covering(spans, t):
    """Name of the innermost (shortest) span [name, s, e] that holds t."""
    best = None
    for name, s, e in spans:
        if s <= t < e and (best is None or e - s < best[1]):
            best = (name, e - s)
    return best[0] if best else None


def _module_of(mod_events, starts, t):
    """Name of the program run (sorted, non-overlapping on one device) that
    holds t."""
    i = bisect.bisect_right(starts, t) - 1
    if i >= 0:
        name, s, d = mod_events[i]
        if s <= t < s + d:
            return name.split("(")[0]
    return None


def reduce_trace(trace: dict, window_ns: tuple | None = None,
                 module: str | None = None) -> dict:
    """busy_s: seconds in which an operation ran, union per device, averaged
    over the device planes that ran any; window_s: the traced stretch
    (first to last event of any kept plane, or window_ns); device_ops: top 10
    operations by total time as [module/op, s]; idle_gaps: idle seconds of the
    busiest device by the harness annotation the host was in, top 10.
    `module` keeps only operations of programs whose name holds it."""
    dev = [p for p in trace["planes"] if p["name"].startswith("/device:")]
    host = [p for p in trace["planes"] if p["name"].startswith("/host:")]
    lo = hi = None
    for p in trace["planes"]:
        for ln in p["lines"]:
            for _n, s, d in ln["events"]:
                lo = s if lo is None else min(lo, s)
                hi = s + d if hi is None else max(hi, s + d)
    if window_ns is not None:
        lo, hi = window_ns
    if lo is None:
        return {"busy_s": 0.0, "window_s": 0.0, "devices": 0,
                "device_ops": [], "idle_gaps": [], "modules": {}}

    per_dev_busy = []
    op_time: dict[str, float] = {}
    mod_time: dict[str, float] = {}
    busiest = None
    for p in dev:
        ops = next((ln["events"] for ln in p["lines"]
                    if ln["name"] == OPS_LINE), [])
        mods = next((ln["events"] for ln in p["lines"]
                     if ln["name"] == MODULES_LINE), [])
        mods = sorted(mods, key=lambda e: e[1])
        starts = [e[1] for e in mods]
        iv = []
        for name, s, d in ops:
            if s + d <= lo or s >= hi:
                continue
            mod = _module_of(mods, starts, s) if mods else None
            if module is not None and (mod is None or module not in mod):
                continue
            iv.append((max(s, lo), min(s + d, hi)))
            op = name.split(" = ", 1)[0].lstrip("%")  # the HLO text is long
            key = f"{mod}/{op}" if mod else op
            op_time[key] = op_time.get(key, 0.0) + d / 1e9
        for name, s, d in mods:
            if lo <= s < hi:
                k = name.split("(")[0]
                mod_time[k] = mod_time.get(k, 0.0) + d / 1e9
        merged = merge(iv)
        busy = sum(e - s for s, e in merged) / 1e9
        if busy > 0:
            per_dev_busy.append(busy)
            if busiest is None or busy > busiest[0]:
                busiest = (busy, merged)

    gaps: dict[str, float] = {}
    if busiest is not None:
        spans = [(n, s, s + d) for p in host for ln in p["lines"]
                 for n, s, d in ln["events"]]
        edges = [lo] + [x for iv in busiest[1] for x in iv] + [hi]
        for g0, g1 in zip(edges[0::2], edges[1::2]):
            if g1 - g0 <= 0:
                continue
            # split the gap where the host's innermost annotation changes
            cuts = sorted({g0, g1} | {x for _n, s, e in spans for x in (s, e)
                                      if g0 < x < g1})
            for c0, c1 in zip(cuts, cuts[1:]):
                name = _covering(spans, (c0 + c1) / 2) or "host:unannotated"
                gaps[name] = gaps.get(name, 0.0) + (c1 - c0) / 1e9

    def top(d):
        return [[k, v] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:10]]

    return {
        "busy_s": (sum(per_dev_busy) / len(per_dev_busy)) if per_dev_busy else 0.0,
        "window_s": (hi - lo) / 1e9,
        "devices": len(per_dev_busy),
        "device_ops": top(op_time),
        "idle_gaps": top(gaps),
        "modules": mod_time,
    }
