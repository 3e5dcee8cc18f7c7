#!/usr/bin/env python3
"""Trace lint: the span-name registry and the call sites must agree.

The flight-recorder analysis layer (utils/traceview.py,
tools/trace_analyze.py) keys its reconstruction on literal span names,
so a name emitted but not declared in `trace.SPAN_REGISTRY` is
invisible to triage docs, and a declared name with no live call site is
a stale promise. This lint extracts every literal first argument to
trace.span()/trace.open_span()/trace.event()/trace.emit() across the
package (plus tools/ and bench.py, and the three names the tracer writes
itself) and checks both directions; `trace.WAIT_SPANS` may name
registered spans only. It holds `trace.KERNEL_SCOPES` to the same rule against
the phase (jax.named_scope) and pallas_call names in ops/, which the join of a
profiler trace (utils/traceview.device_join) keys device time on. Exits
1 on any mismatch.

Run directly (`python tools/trace_lint.py`) or via the tier-1 suite
(tests/test_observability.py wraps main()).
"""

from __future__ import annotations

import os
import re
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = os.path.join(REPO, "cometbft_tpu")

# the tracer itself and the analyzers mention names generically or as
# data, not as emission sites
EXCLUDE = {
    os.path.join(PKG, "utils", "trace.py"),
    os.path.join(PKG, "utils", "traceview.py"),
    os.path.abspath(__file__),
}

# literal name in trace.span("x")/trace.open_span("x")/trace.event("x")/
# trace.emit("x", ...)
# including the `_trace` alias used by modules avoiding name clashes
CALL_RE = re.compile(
    r"\b_?trace\.(?:span|open_span|event|emit)\(\s*[\"']([^\"']+)[\"']")
# the tracer's own records (trace.clock, trace.thread, runtime.gc_pause)
TRACER_RE = re.compile(
    r"\b(?:event|_envelope|_Span)\(\s*[\"']([^\"']+)[\"']")
# kernel scopes in ops/: jax.named_scope("x"),
# pallas_call(..., name="x") and the name handed to
# field._pallas_binop(kernel, "x", ...)
SCOPE_RE = re.compile(
    r"\bnamed_scope\(\s*[\"']([^\"']+)[\"']"
    r"|\bname=[\"']([^\"']+)[\"']"
    r"|_pallas_binop\(\s*\w+,\s*[\"']([^\"']+)[\"']")


def _source_files():
    roots = [PKG, os.path.join(REPO, "tools")]
    for root in roots:
        for dirpath, _dirnames, filenames in os.walk(root):
            for fn in sorted(filenames):
                if fn.endswith(".py"):
                    yield os.path.join(dirpath, fn)
    bench = os.path.join(REPO, "bench.py")
    if os.path.exists(bench):
        yield bench


def _agree(what: str, table: str, used: dict, declared) -> bool:
    """Both directions between the names in use and a registry."""
    undeclared = sorted(set(used) - set(declared))
    unused = sorted(set(declared) - set(used))
    if undeclared:
        print(f"{what} in use but missing from trace.{table}:",
              file=sys.stderr)
        for n in undeclared:
            print(f"  {n}  ({', '.join(sorted(set(used[n])))})",
                  file=sys.stderr)
    if unused:
        print(f"{what} declared in trace.{table} but never used:",
              file=sys.stderr)
        for n in unused:
            print(f"  {n}", file=sys.stderr)
    return not undeclared and not unused


def main() -> int:
    sys.path.insert(0, REPO)
    from cometbft_tpu.utils.trace import (
        KERNEL_SCOPES, SPAN_REGISTRY, WAIT_SPANS)

    tracer = os.path.join(PKG, "utils", "trace.py")
    ops = os.path.join(PKG, "ops") + os.sep
    used: dict[str, list[str]] = {}
    scopes: dict[str, list[str]] = {}
    for path in _source_files():
        path = os.path.abspath(path)
        if path in EXCLUDE and path != tracer:
            continue
        with open(path, encoding="utf-8") as f:
            src = f.read()
        rel = os.path.relpath(path, REPO)
        for m in (TRACER_RE if path == tracer else CALL_RE).finditer(src):
            used.setdefault(m.group(1), []).append(rel)
        if path.startswith(ops):
            for m in SCOPE_RE.finditer(src):
                scopes.setdefault(next(filter(None, m.groups())),
                                  []).append(rel)

    ok = _agree("span names", "SPAN_REGISTRY", used, SPAN_REGISTRY)
    ok &= _agree("kernel scopes", "KERNEL_SCOPES", scopes, KERNEL_SCOPES)
    unknown = sorted(set(WAIT_SPANS) - set(SPAN_REGISTRY))
    if unknown:
        print("trace.WAIT_SPANS names spans that trace.SPAN_REGISTRY "
              f"does not: {', '.join(unknown)}", file=sys.stderr)
        ok = False
    if not ok:
        return 1
    print(f"trace lint: {len(SPAN_REGISTRY)} registered span names and "
          f"{len(KERNEL_SCOPES)} kernel scopes, all in use and declared")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
