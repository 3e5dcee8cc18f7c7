#!/usr/bin/env python3
"""Flight-recorder analyzer: merge per-node trace sinks and answer
"where did the time go" / "why is it stuck" from the command line.

    python tools/trace_analyze.py summary       <paths...>
    python tools/trace_analyze.py timeline      <paths...> [--height H]
    python tools/trace_analyze.py critical-path <paths...> [--height H]
    python tools/trace_analyze.py stall         <paths...>
    python tools/trace_analyze.py device        <paths...> --xplane <file>
    python tools/trace_analyze.py threads       <paths...>

`device` joins the span sinks with a profiler trace (`*.xplane.pb`, or a
directory that jax.profiler wrote one under) taken while tracing was on:
the busiest device's idle time by the thread whose launch ended each
gap and by the innermost program span that thread was in, the least the
two clocks disagree by (`host_device_skew_ms`), and device time by
kernel scope (trace.KERNEL_SCOPES), for the whole trace or `--stretch
LO:HI` (milliseconds since the session began).

`threads` needs the sinks alone: one row a thread, over the window its
spans cover: on a CPU (its root spans' cpu_ms), inside the spans where
it only waits (trace.WAIT_SPANS), the rest of its roots (another
thread's turn at the interpreter, the OS, or native code's threads
working for it), and outside every span.

`paths` are trace sink files or directories (an e2e workdir is
expanded to every ``node*/data/trace.jsonl`` under it; default: the
current directory). `--json` prints the raw analysis dict instead of
text. `stall` exits 1 when a live-but-stalled node is detected, so it
can gate CI and the e2e runner's failure path.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from cometbft_tpu.utils import traceview  # noqa: E402


def _records(paths) -> list[dict]:
    out = []
    for sink in traceview.discover(paths):
        out += traceview.load_records(sink)
    return out


def device(args) -> int:
    import glob

    from cometbft_tpu.utils import xplane
    from cometbft_tpu.utils.trace import KERNEL_SCOPES

    if not args.xplane:
        print("trace_analyze: device needs --xplane", file=sys.stderr)
        return 2
    path = args.xplane
    if os.path.isdir(path):
        found = sorted(glob.glob(os.path.join(
            path, "**", "*.xplane.pb"), recursive=True))
        if not found:
            print(f"trace_analyze: no *.xplane.pb under {path}",
                  file=sys.stderr)
            return 2
        path = found[-1]
    records = _records(args.paths or [])
    stretch = None
    if args.stretch:
        lo, hi = (float(x) * 1e6 for x in args.stretch.split(":"))
        stretch = (lo, hi)
    try:
        j = traceview.device_join(xplane.load(path), records, stretch,
                                  scopes=KERNEL_SCOPES)
    except ValueError as e:
        print(f"trace_analyze: {e}", file=sys.stderr)
        return 2
    print(json.dumps(j, indent=2) if args.as_json
          else traceview.render_device_join(j))
    return 0


def threads(args) -> int:
    from cometbft_tpu.utils.trace import WAIT_SPANS

    rows = traceview.thread_table(_records(args.paths or ["."]), WAIT_SPANS)
    if not rows:
        print("trace_analyze: no span with a thread (tid, self_ms) in "
              f"{args.paths or ['.']!r}", file=sys.stderr)
        return 2
    print(json.dumps(rows, indent=2) if args.as_json
          else traceview.render_thread_table(rows))
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("command", choices=(
        "summary", "timeline", "critical-path", "stall", "device",
        "threads"))
    ap.add_argument("paths", nargs="*", default=None,
                    help="trace sink files or node/workdir directories "
                         "(default: .)")
    ap.add_argument("--height", type=int, default=None,
                    help="height to analyze (default: last committed)")
    ap.add_argument("--limit", type=int, default=200,
                    help="timeline: show at most N records (0 = all)")
    ap.add_argument("--xplane", default=None,
                    help="device: the profiler trace to join with")
    ap.add_argument("--stretch", default=None, metavar="LO:HI",
                    help="device: only this stretch, in ms since the "
                         "profiler session began")
    ap.add_argument("--json", action="store_true", dest="as_json",
                    help="emit the raw analysis dict as JSON")
    args = ap.parse_args(argv)

    if args.command == "device":
        return device(args)
    if args.command == "threads":
        return threads(args)

    try:
        mt = traceview.merge(args.paths or ["."])
    except ValueError as e:
        print(f"trace_analyze: {e}", file=sys.stderr)
        return 2

    if args.command == "summary":
        if args.as_json:
            print(json.dumps(mt.summary(), indent=2, default=str))
        else:
            print(traceview.render_summary(mt))
        return 0

    if args.command == "timeline":
        recs = mt.timeline(height=args.height)
        if args.as_json:
            print(json.dumps(recs[-args.limit:] if args.limit else recs,
                             default=str))
        else:
            print(traceview.render_timeline(recs, mt, limit=args.limit))
        return 0

    if args.command == "critical-path":
        heights = [args.height] if args.height is not None else (
            mt.heights() or [])
        if not heights:
            print("critical-path: no committed heights in trace",
                  file=sys.stderr)
            return 2
        if args.height is None:
            heights = heights[-1:]
        for h in heights:
            cp = mt.critical_path(h)
            if args.as_json:
                print(json.dumps(cp, default=str))
            else:
                print(traceview.render_critical_path(cp))
        return 0

    # stall
    rep = mt.stall_report()
    if args.as_json:
        print(json.dumps(rep, indent=2, default=str))
    else:
        print(traceview.render_stall_report(rep))
    return 1 if rep["status"] == "stall" else 0


if __name__ == "__main__":
    raise SystemExit(main())
