#!/usr/bin/env python3
"""One run of one cell of the benchmark.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, which holds the chip from its first jax call to its exit and
starts no child. It builds the cell's data from --seed, warms up every shape
the cell uses (set-up), measures for --seconds through the entry point a user
calls, checks what the timed path produced, and prints one JSON object as the
last line of stdout. --trace 0 gives the cell's end-to-end metrics with every
tracer off; --trace 1 gives its per-layer metrics (the program's JSONL spans,
the harness's timers around named calls, and a profiler trace of a short
steady stretch). Off a TPU it exits 2 and prints no result; --rehearse shrinks
the sizes for a CPU rehearsal whose line says platform cpu and rehearsal true.

benchmark/README.md says how a cell, a configuration, a traffic kind and a
layer metric are each a file found by name.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


class Ctx:
    """What a driver gets from the harness."""

    def __init__(self, cell, seed, workdir, counters, trace_path, profiler):
        self.cell = cell
        self.seed = seed
        self.workdir = workdir
        self.counters = counters
        self.trace_path = trace_path
        self.profiler = profiler  # None unless --trace 1
        self.t_open = None
        self.snap_open = None
        self.trace_off = 0
        self.tracked: list[tuple[str, int]] = []  # (stage, objects tracked)

    def objects_tracked(self, stage: str, census: int = 0) -> None:
        """Notes how many objects the garbage collector tracks at this stage
        of the run: what a full collection has to walk, by who made them."""
        from benchmark.harness.env import log, tracked_objects

        n, kinds = tracked_objects(census)
        self.tracked.append((stage, n))
        if kinds:
            log(f"   objects tracked by type at {stage}: {dict(kinds)}")

    def window_opens(self, now: float) -> None:
        """The driver calls this at the instant its measured window opens:
        set-up ends here."""
        self.t_open = now
        self.snap_open = self.counters.snap()
        self.gc_open = [g["collections"] for g in gc.get_stats()]
        if self.trace_path:
            from cometbft_tpu.utils import trace

            trace.flush()
            self.trace_off = os.path.getsize(self.trace_path)

    @contextlib.contextmanager
    def perlane_forced(self):
        """Every batch verifier made inside takes the per-lane ladder (its
        own `force_perlane` argument), whatever the dispatch would pick: a
        warm-up inside compiles and loads the ladder at its batch's bucket."""
        from cometbft_tpu.crypto import ed25519 as E

        orig = E.Ed25519BatchVerifier.__init__

        def init(self, *a, **kw):
            kw["force_perlane"] = True
            orig(self, *a, **kw)

        E.Ed25519BatchVerifier.__init__ = init
        try:
            yield
        finally:
            E.Ed25519BatchVerifier.__init__ = orig


def read_spans(path: str, start: int) -> list[dict]:
    from cometbft_tpu.utils import trace

    trace.flush()
    out = []
    with open(path, encoding="utf-8") as f:
        f.seek(start)
        for line in f:
            out.append(json.loads(line))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="shrunk sizes, any platform; never a device number")
    ap.add_argument("--fault", default=None,
                    help="break the timed path on purpose (tests, controls)")
    args = ap.parse_args(argv)

    from benchmark.harness import check as C
    from benchmark.harness import env, faults, readers
    from benchmark.harness.profile import Profiler, load_xplane, reduce_trace
    from benchmark.harness.spec import Cell, SpecError, load_benchmark
    from benchmark.harness.wrap import CallRecorder

    log = env.log
    try:
        cell = Cell(load_benchmark(), args.workload, args.rehearse)
    except SpecError as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 3
    try:
        import cometbft_tpu  # noqa: F401
    except ImportError:
        print("benchmark: the program (cometbft_tpu/) is not in this "
              "checkout; there is nothing to measure", file=sys.stderr)
        return 3

    env.place_compile_cache()
    device = env.claim_device(cell.chips, args.rehearse)
    on_chip = device["platform"] == "tpu"

    from cometbft_tpu.crypto import ed25519 as E
    from cometbft_tpu.crypto import native
    from cometbft_tpu.utils import trace

    log(f"cell {cell.name}: config {cell.entry['config']}, driver "
        f"{cell.driver_name}, seed {args.seed}, {args.seconds:g}s, trace "
        f"{args.trace}" + (f", FAULT {args.fault}" if args.fault else ""))
    log(f"   parameters: {json.dumps(cell.params, sort_keys=True)}")
    if not native.available():
        raise SystemExit(f"FAIL: the host C++ engine is not available: "
                         f"{native.build_state()}")
    bs = native.build_state()
    log(f"native engine: {native.engine()}, "
        f"{'built here' if bs['built'] else 'matched an existing build'}")
    if on_chip and not E._accel_backed():
        raise SystemExit("FAIL: dispatch does not see the accelerator")
    if args.rehearse and cell.params.get("device_from_lanes") is not None:
        # off a chip the dispatch keeps every batch on the host engine; the
        # program's own seam (NATIVE_MAX = 0: no native engine) sends the
        # rehearsal's batches down the device path on XLA:CPU instead, so the
        # path checks and the host_path control run through the timed path
        E.NATIVE_MAX = 0

    workdir = os.path.join(ROOT, "benchmark", ".cache", "run",
                           f"{cell.name}-{args.seed}-{os.getpid()}")
    os.makedirs(workdir)
    recorder = gc_watch = None
    try:
        watch = env.CompileWatch()
        counters = env.Counters()
        trace_path = profiler = None
        layer = cell.layer_metrics() if args.trace else []
        if args.trace:
            trace_path = os.path.join(workdir, "spans.jsonl")
            trace.configure(trace_path)
            recorder = CallRecorder()
            for target in readers.wrap_targets(s for _, s in layer):
                recorder.wrap(target)
            profiler = Profiler(os.path.join(workdir, "profile"))
            gc_watch = env.GcWatch()
        ctx = Ctx(cell, args.seed, workdir, counters, trace_path, profiler)
        if args.fault:
            faults.FAULTS[args.fault]()

        driver = cell.driver().Driver(ctx)
        ctx.objects_tracked("harness and program imported")
        log("== set-up")
        driver.setup()
        if on_chip:
            log(f"   link probe _link_mbps() = {E._link_mbps():.0f} MB/s "
                f"(swings run to run; not a metric)")
        log(f"   compiles so far: {json.dumps(watch.summary())}")
        log("== window")
        driver.window(args.seconds)
        snap_close = counters.snap()
        setup_s = ctx.t_open - T_PROCESS
        delta = counters.delta(ctx.snap_open, snap_close)
        spans = read_spans(trace_path, ctx.trace_off) if args.trace else None
        log(f"   set-up {setup_s:.2f}s; window {driver.t1 - driver.t0:.2f}s")
        log(f"   python gc inside the window: collections per generation "
            f"{[g['collections'] - o for g, o in zip(gc.get_stats(), ctx.gc_open)]}")
        ctx.objects_tracked("window closes", census=8)
        log(f"   objects the collector tracks, by stage: {dict(ctx.tracked)}")
        log("   crypto_path_selected_total += " + json.dumps(
            {"/".join(k): v for k, v in
             sorted(delta["path_selected_total"].items())}))
        gave = {k[0]: v for k, v in delta["gave_way_total"].items()}
        log(f"   batches {int(delta['batches'])}, lanes {int(delta['lanes'])}; "
            f"gave way: {json.dumps(gave) if gave else 'none'}")
        if spans is not None:
            by: dict = {}
            for r in spans:
                if r.get("name") == "crypto.batch_verify" and "path" in r:
                    key = (int(r["n"]), r["path"])
                    by[key] = by.get(key, 0) + 1
            for (n, path), cnt in sorted(by.items()):
                log(f"   batch n={n} bucket={E._bucket(n)} -> {path} x{cnt}")

        metrics = driver.metrics()
        checks = C.path_checks(
            delta, spans, cell.params.get("device_from_lanes"),
            driver.expected_batches())
        checks += C.compile_checks(watch, driver.t0, driver.t1)

        profile = trace_data = None
        if args.trace:
            log("== traced stretch")
            driver.profile_stretch()
            log(f"   profiler on from {profiler.t_on - driver.t1:+.2f}s to "
                f"{profiler.t_off - driver.t1:+.2f}s after the window closed, "
                f"{driver.profile_units} units: "
                + ("outside every span of the measured window"
                   if profiler.t_on >= driver.t1 else
                   "INSIDE the measured window: its spans hold the profiler"))
            t_load = time.perf_counter()
            xplane = profiler.xplane()
            trace_data = load_xplane(xplane, keep_host=tuple(recorder.names()))
            profile = reduce_trace(trace_data)
            log(f"   trace {os.path.getsize(xplane) / 1e6:.1f} MB, read and "
                f"reduced in {time.perf_counter() - t_load:.1f}s")
            log(f"   profile: {profile['devices']} device plane(s) busy "
                f"{profile['busy_s']:.4f}s of {profile['window_s']:.4f}s; "
                f"programs: {json.dumps(profile['modules'])}")
        log("== checks")
        checks += driver.verify()
        for c in checks:
            c.show()
        correct = all(c.ok for c in checks)

        dev = dict(device, memory_peak_bytes=env.memory_peak_bytes())
        if args.trace:
            src = {"spans": spans, "counters": delta, "recorder": recorder,
                   "t0": driver.t0, "t1": driver.t1, "trace": trace_data,
                   "profile": profile, "gc": gc_watch,
                   "series": getattr(driver, "series", dict)(),
                   "units": {"profile": driver.profile_units,
                             "attempted": driver.attempted()}}
            out_metrics = readers.read_all(layer, src)
            dev.update(busy_s=profile["busy_s"], window_s=profile["window_s"])
        else:
            metrics["setup_s"] = setup_s
            out_metrics = {}
            for m in cell.end_to_end():
                if m["name"] not in metrics:
                    raise SystemExit(f"FAIL: the run gave no {m['name']}")
                out_metrics[m["name"]] = {"value": metrics[m["name"]],
                                          "unit": m["unit"]}
        line = {"correct": bool(correct), "attempted": driver.attempted(),
                "failed": driver.failed, "metrics": out_metrics,
                "device": dev}
        if args.trace:
            line["breakdown"] = {"device_ops": profile["device_ops"],
                                 "idle_gaps": profile["idle_gaps"]}
        if args.rehearse:
            line["rehearsal"] = True
        if args.fault:
            line["fault"] = args.fault
        log(f"   compiles, whole run: {json.dumps(watch.summary())}")
        # each number compared beside its limit: last in the line, and the
        # last lines on standard error
        line["checks"] = {c.name: {"value": c.value, "limit": c.limit,
                                   "ok": c.ok} for c in checks}
    finally:
        if recorder is not None:
            recorder.unwrap_all()
        if gc_watch is not None:
            gc_watch.close()
        trace.disable()
        shutil.rmtree(workdir, ignore_errors=True)
    for name, c in line["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']}) "
              f"{'ok' if c['ok'] else 'FAIL'}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
