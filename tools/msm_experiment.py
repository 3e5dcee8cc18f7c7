"""Sort-by-bucket MSM accumulate experiment (round 5).

Round 4 measured the RLC/MSM engine at 41.7k sigs/s vs the per-lane
ladder's 178k on the real chip and blamed the Pippenger accumulate's
random niels gather (PROFILE.md round-4 notes). The one untried
algorithmic idea is restructuring the accumulate so the device reads
contiguous per-bucket segments (VERDICT r4 #1). Before building that,
this measures every primitive a restructure could be built from, at
production shape (10k-signature batch), on the real chip.

Timing protocol: a large, variable fixed dispatch/fetch latency made
single-shot wall clocks lie in both directions on the round-2 machine
(unmeasured on today's). Every measurement here submits PIPE=8
back-to-back executions alternating TWO distinct input variants (the
runtime must execute each; identical-buffer reruns can be served
impossibly fast) and syncs once, reporting (total / PIPE) minus nothing
— the same steady-state protocol bench.py uses. A `null` op calibrates
the residual per-dispatch cost.

Measured ops:
  null          trivial jitted add — per-dispatch floor
  full          current rlc_verify_stream end-to-end
  decompress    ZIP-215 decompress of A,R + niels concat
  gather_rand   jnp.take of (M,22) niels rows, real random indices, S*WK rows
  gather_dense  same, dense L rows (no S-padding waste)
  gather_mono   same volume, sorted (monotone) indices
  repeat_pts    jnp.repeat point expansion (monotone by construction)
  sort_small    lax.sort (key, iota) — permutation without payload
  sort_payload  lax.sort carrying all 3x22 limb payloads (tiled key)
  scatter_rows  out.at[dest].set(rows) — random-write permutation
  build_stream  the production gather+concat that feeds the kernel
  kernel_only   the pallas accumulate fed a PRE-materialized stream
  tail          region tree sum + window combine + fixed-base + check

Decision rule: the sort-restructure candidate costs repeat_pts +
sort_payload + kernel_only; it beats the current path iff that sum is
well under build_stream + kernel_only. If kernel_only alone dominates
`full`, data movement is NOT the bottleneck and the restructure idea is
dead regardless — the book closes on kernel-internal grounds.
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

N_SIGS = 10_000
PIPE = 8
REPS = 3


def bench(fn, variants):
    """Pipelined steady-state: PIPE back-to-back calls cycling input
    variants, one sync; best of REPS rounds; returns seconds/call."""
    out = fn(*variants[0])
    for x in (out if isinstance(out, (tuple, list)) else [out]):
        x.block_until_ready()
    best = float("inf")
    for _ in range(REPS):
        t0 = time.perf_counter()
        outs = [fn(*variants[i % len(variants)]) for i in range(PIPE)]
        for out in outs:
            for x in (out if isinstance(out, (tuple, list)) else [out]):
                x.block_until_ready()
        best = min(best, (time.perf_counter() - t0) / PIPE)
    return best


def main():
    import jax
    import jax.numpy as jnp

    from cometbft_tpu.crypto import rlc
    from cometbft_tpu.crypto.testgen import generate_signed_batch
    from cometbft_tpu.ops import msm as M
    from cometbft_tpu.ops import curve as C
    from cometbft_tpu.ops import field as F

    print(f"devices: {jax.devices()}", file=sys.stderr)
    results = {}

    def run(name, fn, variants):
        t = bench(fn, variants)
        results[name + "_ms"] = round(t * 1e3, 2)
        print(f"{name}: {t*1e3:.2f} ms", file=sys.stderr)

    # ---- null: dispatch floor ----------------------------------------
    nul = [(jnp.ones((8, 128), jnp.int32) * k,) for k in (1, 2)]
    run("null", jax.jit(lambda x: x + 1), nul)

    # ---- inputs: two distinct prepared batches -----------------------
    preps, inputs = [], []
    for seed in (0, 1):
        items = generate_signed_batch(N_SIGS, seed=seed, msg_len=100,
                                             vote_shaped=True)
        skip = np.zeros(N_SIGS, bool)
        prep = rlc.prepare(items, skip, N_SIGS)
        assert prep is not None
        preps.append(prep)
        inputs.append((
            jnp.asarray(np.stack([np.frombuffer(it[0], np.uint8)
                                  for it in items])),
            jnp.asarray(np.stack([np.frombuffer(it[2][:32], np.uint8)
                                  for it in items])),
        ))
    # pad both to a common (max) S and stream tier so one jit serves both
    S = max(p["s_rounds"] for p in preps)
    L_pad = max(len(p["stream"]) for p in preps)
    for p in preps:
        if len(p["stream"]) < L_pad:
            pad = L_pad - len(p["stream"])
            sent = p["stream"][-1]
            p["stream"] = np.concatenate(
                [p["stream"], np.full(pad, sent, p["stream"].dtype)])
            p["stream_neg"] = np.packbits(
                np.concatenate([np.unpackbits(p["stream_neg"],
                                              bitorder="little"),
                                np.zeros(pad, np.uint8)]),
                bitorder="little")
    n_contrib = int(preps[0]["counts"].astype(np.int64).sum())
    Mrows = 2 * N_SIGS + 1
    results.update(n_sigs=N_SIGS, contribs=n_contrib,
                   padded_stream=L_pad, s_rounds=S, sxwk=S * M.WK)
    print(f"contribs={n_contrib} L={L_pad} S={S} SxWK={S*M.WK}",
          file=sys.stderr)

    live = jnp.ones(N_SIGS, bool)
    full_vars = []
    for p, (a_b, r_b) in zip(preps, inputs):
        full_vars.append((
            a_b, r_b, live,
            jnp.asarray(p["stream"].astype(np.int32)),
            jnp.asarray(p["stream_neg"]),
            jnp.asarray(p["counts"]),
            jnp.asarray(p["weights"]),
            jnp.asarray(p["c_digits"]),
        ))

    def full(a, r, lv, st, sn, cn, w, cd):
        return M.rlc_verify_stream_jit(a, r, lv, st, sn, cn, w, cd,
                                       s_rounds=S)

    run("full", full, full_vars)

    # ---- decompress + niels ------------------------------------------
    @jax.jit
    def decompress_niels(a, r):
        _, a_pt = C.decompress(a)
        _, r_pt = C.decompress(r)
        na = C.to_niels(a_pt)
        nr = C.to_niels(r_pt)
        ident = M._identity_niels(1)
        return tuple(
            jnp.concatenate([r_c, a_c, i_c], axis=1)
            for r_c, a_c, i_c in zip(nr[:3], na[:3], ident)
        )

    run("decompress", decompress_niels, inputs)
    rows_v = []  # (M, 22) per coord, per variant
    for a_b, r_b in inputs:
        rows_v.append(tuple(c.T for c in decompress_niels(a_b, r_b)))

    # ---- gathers ------------------------------------------------------
    gidx_v, flat_v = [], []
    for p in preps:
        gi, gn = M.expand_stream(
            jnp.asarray(p["stream"].astype(np.int32)),
            jnp.asarray(p["stream_neg"]),
            jnp.asarray(p["counts"]), S)
        gidx_v.append((gi, gn))
        flat_v.append(gi.reshape(-1))

    @jax.jit
    def gather3(r0, r1, r2, f):
        return (jnp.take(r0, f, axis=0), jnp.take(r1, f, axis=0),
                jnp.take(r2, f, axis=0))

    run("gather_rand", gather3,
        [(*rows_v[i], flat_v[i]) for i in range(2)])
    run("gather_dense", gather3,
        [(*rows_v[i], jnp.asarray(preps[i]["stream"].astype(np.int32)))
         for i in range(2)])
    mono_v = [jnp.sort(f) for f in flat_v]
    run("gather_mono", gather3,
        [(*rows_v[i], mono_v[i]) for i in range(2)])

    # ---- repeat (point-major expansion) ------------------------------
    rep_v = []
    for p in preps:
        rc = np.bincount(
            p["stream"][:int(p["counts"].astype(np.int64).sum())]
            .astype(np.int64), minlength=Mrows)
        rc[-1] += L_pad - rc.sum()  # pad via trailing sentinel repeats
        rep_v.append(jnp.asarray(rc.astype(np.int32)))

    @jax.jit
    def repeat3(r0, r1, r2, rc):
        return tuple(
            jnp.repeat(r, rc, axis=0, total_repeat_length=L_pad)
            for r in (r0, r1, r2)
        )

    run("repeat_pts", repeat3,
        [(*rows_v[i], rep_v[i]) for i in range(2)])

    # ---- sorts --------------------------------------------------------
    rng = np.random.default_rng(0)
    dest_v = [jnp.asarray(rng.permutation(L_pad).astype(np.int32))
              for _ in range(2)]
    iota = jnp.arange(L_pad, dtype=jnp.int32)

    run("sort_small",
        jax.jit(lambda k, v: jax.lax.sort((k, v), num_keys=1)),
        [(dest_v[i], iota) for i in range(2)])

    expanded_v = [repeat3(*rows_v[i], rep_v[i]) for i in range(2)]

    @jax.jit
    def sort_payload(k, p0, p1, p2):
        kt = jnp.broadcast_to(k[:, None], p0.shape)
        s = jax.lax.sort((kt, p0, p1, p2), num_keys=1, dimension=0)
        return s[1], s[2], s[3]

    run("sort_payload", sort_payload,
        [(dest_v[i], *expanded_v[i]) for i in range(2)])

    @jax.jit
    def scatter_rows(d, p0, p1, p2):
        return tuple(
            jnp.zeros((L_pad, F.NLIMBS), jnp.int32).at[d].set(p)
            for p in (p0, p1, p2)
        )

    run("scatter_rows", scatter_rows,
        [(dest_v[i], *expanded_v[i]) for i in range(2)])

    # ---- production stream build + kernel + tail ---------------------
    nl = F.NLIMBS
    WK = M.WK

    @jax.jit
    def build_stream(r0, r1, r2, gi, gn):
        fl = gi.reshape(-1)
        pad2 = jnp.zeros((S, 1, WK), jnp.int32)
        streams = []
        for rows in (r0, r1, r2):
            g = jnp.take(rows, fl, axis=0).reshape(S, WK, nl)
            streams.append(g.transpose(0, 2, 1))
        neg_row = gn.astype(jnp.int32)[:, None, :]
        return jnp.concatenate(
            [streams[0], neg_row, pad2,
             streams[1], pad2, pad2,
             streams[2], pad2, pad2], axis=1,
        ).reshape(S * 72, WK)

    run("build_stream", build_stream,
        [(*rows_v[i], *gidx_v[i]) for i in range(2)])
    stream_mat_v = [build_stream(*rows_v[i], *gidx_v[i]) for i in range(2)]

    from jax.experimental import pallas as _pl
    from jax.experimental.pallas import tpu as pltpu
    M.pl = _pl

    w_v = [jnp.asarray(p["weights"]).reshape(1, WK).astype(jnp.int32)
           for p in preps]
    bias = jnp.asarray(F._SUB_BIAS)
    consts = jnp.asarray(C._CONSTS_NP)
    tile = 512
    n_tiles = WK // tile

    def kernel_call(sm, w):
        stream_spec = _pl.BlockSpec((72, tile), lambda tt, s: (s, tt),
                                    memory_space=pltpu.VMEM)
        w_spec = _pl.BlockSpec((1, tile), lambda tt, s: (0, tt),
                               memory_space=pltpu.VMEM)
        bias_spec = _pl.BlockSpec((nl, 1), lambda tt, s: (0, 0),
                                  memory_space=pltpu.VMEM)
        consts_spec = _pl.BlockSpec((3 * nl, 1), lambda tt, s: (0, 0),
                                    memory_space=pltpu.VMEM)
        out_spec = _pl.BlockSpec((nl, tile), lambda tt, s: (0, tt),
                                 memory_space=pltpu.VMEM)
        return _pl.pallas_call(
            M._accum_weight_kernel,
            out_shape=[jax.ShapeDtypeStruct((nl, WK), jnp.int32)] * 4,
            grid=(n_tiles, S),
            in_specs=[stream_spec, w_spec, bias_spec, consts_spec],
            out_specs=[out_spec] * 4,
            scratch_shapes=[pltpu.VMEM((4 * nl, tile), jnp.int32)],
        )(sm, w, bias, consts)

    kernel_jit = jax.jit(kernel_call)
    run("kernel_only", kernel_jit,
        [(stream_mat_v[i], w_v[i]) for i in range(2)])

    @jax.jit
    def tail(w0, w1, w2, w3, cd):
        win_sums = M._region_tree_sum((w0, w1, w2, w3))
        msmv = M._window_combine(win_sums)
        total = C.add(msmv, C.fixed_base(cd))
        return C.is_identity(C.mul8(total))[0]

    weighted_v = [kernel_jit(stream_mat_v[i], w_v[i]) for i in range(2)]
    cd_v = [jnp.asarray(p["c_digits"]) for p in preps]
    run("tail", tail, [( *weighted_v[i], cd_v[i]) for i in range(2)])

    results["restructure_candidate_ms"] = round(
        results["repeat_pts_ms"] + results["sort_payload_ms"]
        + results["kernel_only_ms"] + results["decompress_ms"]
        + results["tail_ms"], 2)
    results["current_path_ms"] = round(
        results["decompress_ms"] + results["build_stream_ms"]
        + results["kernel_only_ms"] + results["tail_ms"], 2)
    print(json.dumps(results))


if __name__ == "__main__":
    main()
