"""Device-time decomposition of ONE fused rlc_verify_stream executable.

Wall-clock around separate executables lied on the round-5 machine
(unmeasured on today's; tools/msm_experiment.py:
large arrays crossing executable boundaries pay a ~300 ms staging cost
that vanishes inside a fused graph), so the only trustworthy
decomposition is xprof op-level device accounting of the production
graph itself — the round-2 methodology (PROFILE.md).

Prints the top ops by self device time, grouped into stages:
  gather    the random niels row-gather feeding the accumulate
  pallas    the fused accumulate/weight kernel
  sort      (absent today; present in restructure candidates)
  other     decompress chain, tree reduce, Horner, fixed-base
"""

from __future__ import annotations

import glob
import gzip
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

N_SIGS = 10_000
TRACE_DIR = "/tmp/msm_trace"


def main():
    import jax
    import jax.numpy as jnp

    from cometbft_tpu.crypto import rlc
    from cometbft_tpu.crypto.testgen import generate_signed_batch
    from cometbft_tpu.ops import msm as M

    items = generate_signed_batch(N_SIGS, seed=0, msg_len=100,
                                         vote_shaped=True)
    prep = rlc.prepare(items, np.zeros(N_SIGS, bool), N_SIGS)
    assert prep is not None
    S = prep["s_rounds"]
    args = (
        jnp.asarray(np.stack([np.frombuffer(it[0], np.uint8)
                              for it in items])),
        jnp.asarray(np.stack([np.frombuffer(it[2][:32], np.uint8)
                              for it in items])),
        jnp.ones(N_SIGS, bool),
        jnp.asarray(prep["stream"].astype(np.int32)),
        jnp.asarray(prep["stream_neg"]),
        jnp.asarray(prep["counts"]),
        jnp.asarray(prep["weights"]),
        jnp.asarray(prep["c_digits"]),
    )

    def full():
        return M.rlc_verify_stream_jit(*args, s_rounds=S)

    full().block_until_ready()  # compile
    os.makedirs(TRACE_DIR, exist_ok=True)
    with jax.profiler.trace(TRACE_DIR):
        for _ in range(3):
            out = full()
        out.block_until_ready()
        time.sleep(0.2)

    # ---- parse: op_profile via xprof ---------------------------------
    files = glob.glob(os.path.join(TRACE_DIR, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        print("no xplane captured", file=sys.stderr)
        sys.exit(1)
    xplane = max(files, key=os.path.getmtime)
    from xprof.convert import raw_to_tool_data as r2t

    data, _ = r2t.xspace_to_tool_data([xplane], "op_profile", {})
    if isinstance(data, bytes):
        data = data.decode()
    prof = json.loads(data)

    # walk byProgram/byCategory tree collecting leaf ops
    leaves = []

    def walk(node, path):
        children = node.get("children", [])
        m = node.get("metrics", {})
        name = node.get("name", "?")
        if not children:
            leaves.append((name, path, m.get("rawTime", m.get("time", 0)),
                           m))
            return
        for ch in children:
            walk(ch, path + [name])

    root = prof.get("byCategory") or prof.get("byProgram") or prof
    walk(root, [])
    tot = sum(t for _, _, t, _ in leaves) or 1
    leaves.sort(key=lambda x: -x[2])
    print(f"{'op':60s} {'self':>12s} {'%':>6s}")
    for name, path, t, m in leaves[:15]:
        print(f"{name[:60]:60s} {t:12.0f} {100*t/tot:6.1f}")

    # aggregate by op-name prefix (strip trailing .<id>)
    agg: dict[str, list] = {}
    for name, path, t, m in leaves:
        base = name.rsplit(".", 1)[0] if name.rsplit(".", 1)[-1].isdigit() \
            else name
        a = agg.setdefault(base, [0.0, 0])
        a[0] += t
        a[1] += 1
    print(f"\n{'op class':40s} {'count':>6s} {'total_ms/exec':>14s} {'%':>6s}")
    rows = sorted(agg.items(), key=lambda kv: -kv[1][0])
    for base, (t, cnt) in rows[:25]:
        print(f"{base[:40]:40s} {cnt:6d} {t/3/1e9:14.3f} {100*t/tot:6.1f}")
    print(json.dumps({
        "total_device_ms_per_exec": round(tot / 3 / 1e9, 2),
        "top": {b: round(t / 3 / 1e9, 3) for b, (t, c) in rows[:12]},
    }))


if __name__ == "__main__":
    main()
