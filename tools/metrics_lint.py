#!/usr/bin/env python3
"""Metrics lint: every metric declared in a utils.metrics bundle must be
driven somewhere in the codebase.

A metric that is registered but never incremented exports a permanent
zero — it looks wired on a dashboard while measuring nothing. This lint
instantiates every bundle against a fresh Registry, then greps the
package for a mutation call (`.<attr>.inc/set/add/observe(`) on each
bundle attribute. Exits 1 listing any dead metrics.

Run directly (`python tools/metrics_lint.py`) or via the tier-1 suite
(tests/test_observability.py wraps main()).
"""

from __future__ import annotations

import os
import re
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = os.path.join(REPO, "cometbft_tpu")

# the file where bundles are declared does not count as a driver
DECL_FILE = os.path.join(PKG, "utils", "metrics.py")

MUTATORS = ("inc", "set", "add", "observe")

# Registered for a reader outside the package, driven by nothing in it:
# benchmark/harness/env.py:131 snapshots crypto gave_way_total and
# check.py:192 expects its `oversize` reason at 0. PR 28 removed the two
# paths that drove it (a `simplicity` PR may not edit benchmark/);
# ROADMAP D9's `benchmark` PR drops the reads, then the metric and this.
READ_BY_THE_BENCHMARK = {"CryptoMetrics.gave_way_total"}


def _bundle_metrics():
    """{bundle_class_name: [(attr, n_labels), ...]} for every *Metrics
    bundle."""
    sys.path.insert(0, REPO)
    from cometbft_tpu.utils import metrics as M

    out = {}
    for name in dir(M):
        if not name.endswith("Metrics") or name.startswith("_"):
            continue
        cls = getattr(M, name)
        if not isinstance(cls, type):
            continue
        bundle = cls(M.Registry())
        attrs = [
            (a, len(v.labels)) for a, v in vars(bundle).items()
            if isinstance(v, M._Metric)
        ]
        if attrs:
            out[name] = attrs
    return out


def _package_sources() -> str:
    chunks = []
    for dirpath, _dirnames, filenames in os.walk(PKG):
        for fn in filenames:
            if not fn.endswith(".py"):
                continue
            path = os.path.join(dirpath, fn)
            if os.path.abspath(path) == os.path.abspath(DECL_FILE):
                continue
            with open(path, encoding="utf-8") as f:
                chunks.append(f.read())
    return "\n".join(chunks)


def main() -> int:
    bundles = _bundle_metrics()
    src = _package_sources()
    dead: list[str] = []
    unlabeled: list[str] = []
    for bundle, attrs in sorted(bundles.items()):
        for attr, n_labels in attrs:
            pat = re.compile(
                r"\." + re.escape(attr) + r"\.(?:" + "|".join(MUTATORS)
                + r")\("
            )
            if not pat.search(src):
                if f"{bundle}.{attr}" not in READ_BY_THE_BENCHMARK:
                    dead.append(f"{bundle}.{attr}")
                continue
            if not n_labels:
                continue
            # Labeled metrics (e.g. the per-device mesh counters) must
            # pass label values at every mutation site: a bare
            # `.inc(1.0)` on a labeled counter raises at runtime, but
            # only on the code path that hits it — catch it here
            # instead. Only single-line calls with no nested parens are
            # parseable by regex; sites that span lines or compute args
            # are skipped (lenient: the lint flags the metric only when
            # EVERY parseable site lacks a label argument).
            site_pat = re.compile(
                r"\." + re.escape(attr) + r"\.(?:" + "|".join(MUTATORS)
                + r")\(([^()\n]*)\)"
            )
            sites = site_pat.findall(src)
            if sites and not any("," in s for s in sites):
                unlabeled.append(
                    f"{bundle}.{attr} ({n_labels} labels)"
                )
    rc = 0
    if dead:
        print("dead metrics (registered but never driven):", file=sys.stderr)
        for d in dead:
            print(f"  {d}", file=sys.stderr)
        rc = 1
    if unlabeled:
        print("labeled metrics driven without label values:",
              file=sys.stderr)
        for d in unlabeled:
            print(f"  {d}", file=sys.stderr)
        rc = 1
    if rc:
        return rc
    total = sum(len(a) for a in bundles.values())
    print(f"metrics lint: {total} metrics across {len(bundles)} bundles, "
          "all driven")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
