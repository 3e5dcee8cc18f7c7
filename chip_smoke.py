#!/usr/bin/env python3
"""chip_smoke.py — the verify data plane, end to end, on the attached TPU.

    python chip_smoke.py              # one chip: what the driver runs
    python chip_smoke.py --chips 4    # the sharded mesh only, on four chips
    python chip_smoke.py --rehearse   # tiny sizes, any platform (tier-1 test)

One process, which holds the chip from its first jax call to its exit; it
starts no child that needs jax. Every phase goes through the entry points a
user calls (verify_commit, ReplayEngine, cli init + Node) at a deployment's
size, and is checked against something independent of the device path: the
host C++ engine lane for lane, the pure-Python reference, the generator's
app hash. A phase that fails raises; nothing is caught and logged.

The last line of stdout is exactly
    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}
and is printed only when every phase passed. Without an accelerator (and
without --rehearse) the script exits 2 before doing any work.

--rehearse only shrinks sizes and skips the platform assertion: off the chip
dispatch keeps every batch on the host engine, so the rehearsal proves the
script's control flow, not the kernels (tests/test_tpu_device.py asks the
chip's compiler about those).
"""

from __future__ import annotations

import argparse
import copy
import json
import os
import shutil
import statistics
import sys
import tempfile
import threading
import time
import urllib.request

DEVICE_PATHS = ("ladder", "mesh")
CHAIN = "chip-smoke"


def log(msg: str) -> None:
    print(msg, flush=True)


class Probe:
    """Observers only: what compiled (jax.monitoring), which jitted verify
    programs ran at which shapes (recording wrappers around ops' jits, which
    crypto/ed25519.py imports at call time), which path each batch took
    (the program's own crypto.batch_verify trace spans)."""

    VERIFY_FNS = ("decompress_pubkeys", "verify_batch_cached_a")

    def __init__(self, workdir: str, on_chip: bool):
        import jax

        from cometbft_tpu.ops import ed25519_verify as EV
        from cometbft_tpu.utils import trace

        self.on_chip = on_chip
        self.compiles: list[dict] = []
        self._hit = threading.local()
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(self._on_dur)
        self.programs: dict[tuple, tuple] = {}  # signature -> (jit, a, kw)
        self._checked: set[tuple] = set()
        for name in self.VERIFY_FNS:
            setattr(EV, name + "_jit",
                    self._recorded(name, getattr(EV, name + "_jit")))
        self.workdir = workdir
        self.trace_path = os.path.join(workdir, "smoke_trace.jsonl")
        trace.configure(self.trace_path)
        self._trace_off = 0

    # -- compiles ------------------------------------------------------

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self._hit.flag = True

    def _on_dur(self, event: str, secs: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles.append({
                "fn": str(kw.get("fun_name", "?")), "s": secs,
                "cache_hit": bool(getattr(self._hit, "flag", False)),
            })
            self._hit.flag = False

    def is_verify(self, fun_name: str) -> bool:
        return fun_name in [f"jit({v})" for v in self.VERIFY_FNS]

    # -- programs ------------------------------------------------------

    def _recorded(self, name, jit_fn):
        import jax

        def call(*a, **kw):
            sig = (name, tuple((tuple(x.shape), str(x.dtype))
                               for x in jax.tree_util.tree_leaves(a)),
                   tuple(sorted(kw.items())))
            self.programs[sig] = (jit_fn, a, kw)
            return jit_fn(*a, **kw)

        return call

    def latest(self, name: str, lanes: int):
        """Last recorded call of `name` whose batch axis is `lanes`."""
        for sig in reversed(list(self.programs)):
            if sig[0] == name and any(lanes in shp for shp, _ in sig[1]):
                return self.programs[sig]
        return None

    def check_kernels(self) -> list[str]:
        """Every verify program run so far must hold the Pallas kernels'
        custom call in its lowered form (on the chip; off it the XLA
        value-form is what is meant to run)."""
        out = []
        for sig, (jit_fn, a, kw) in self.programs.items():
            if sig in self._checked:
                continue
            self._checked.add(sig)
            label = f"{sig[0]}[{sig[1][0][0][0]}]"  # first operand's lanes
            if not self.on_chip:
                out.append(f"{label}: not checked off the chip")
                continue
            t0 = time.perf_counter()
            n = jit_fn.lower(*a, **kw).as_text().count("@tpu_custom_call")
            if n == 0:
                raise SystemExit(
                    f"FAIL: {label} lowered without the Pallas kernel "
                    f"(shapes {sig[1]})")
            out.append(f"{label}: {n} tpu_custom_call (lowered again in "
                       f"{time.perf_counter() - t0:.1f}s to look)")
        return out

    # -- paths ---------------------------------------------------------

    def new_batches(self) -> list[tuple[int, str]]:
        """(n, path) of every ed25519 batch dispatched since the last call."""
        from cometbft_tpu.utils import trace

        trace.flush()
        out = []
        with open(self.trace_path, encoding="utf-8") as f:
            f.seek(self._trace_off)
            for line in f:
                rec = json.loads(line)
                if rec.get("name") == "crypto.batch_verify" and "path" in rec:
                    out.append((int(rec["n"]), rec["path"]))
            self._trace_off = f.tell()
        return out


def run_phase(probe: Probe, name: str, fn):
    """Run one phase, print what it compiled and which path every batch
    took, and fail unless each batch meant for the device (n >= NATIVE_MAX)
    took a device path."""
    from cometbft_tpu.crypto import ed25519 as E
    from cometbft_tpu.utils.metrics import crypto_metrics

    cm = crypto_metrics()
    before = dict(cm.path_selected_total.values())
    gave0 = dict(cm.gave_way_total.values())
    c0 = len(probe.compiles)
    probe.new_batches()
    log(f"== phase {name}")
    t0 = time.perf_counter()
    out = fn()
    dt = time.perf_counter() - t0

    compiles = probe.compiles[c0:]
    big = [c for c in compiles if c["s"] >= 0.5 or probe.is_verify(c["fn"])]
    for c in big:
        log(f"   compile {c['fn']}: {c['s']:.1f}s "
            f"{'cache hit' if c['cache_hit'] else 'compiled'}")
    verify = [c for c in compiles if probe.is_verify(c["fn"])]
    log(f"   compiles: {len(compiles)} programs "
        f"{sum(c['s'] for c in compiles):.1f}s; verify shapes: "
        f"{sum(not c['cache_hit'] for c in verify)} compiled, "
        f"{sum(c['cache_hit'] for c in verify)} cache hits")
    for line in probe.check_kernels():
        log(f"   kernel {line}")

    after = dict(cm.path_selected_total.values())
    diff = {k: after[k] - before.get(k, 0.0) for k in after
            if after[k] != before.get(k, 0.0)}
    log("   crypto_path_selected_total += "
        + json.dumps({"/".join(k): v for k, v in sorted(diff.items())}))
    batches = probe.new_batches()
    by_size: dict[tuple[int, str], int] = {}
    for n, path in batches:
        by_size[(n, path)] = by_size.get((n, path), 0) + 1
    for (n, path), cnt in sorted(by_size.items()):
        log(f"   batch n={n} bucket={E._bucket(n)} -> {path} x{cnt}")
    spans = len(batches)
    # the counter also carries verify_commit's per-curve partition labels
    # and single-signature verifies; the dispatch's own labels are these
    counted = sum(v for k, v in diff.items() if k[1] == "ed25519"
                  and k[0] in DEVICE_PATHS + ("native", "cpu"))
    if spans != counted:
        raise SystemExit(f"FAIL: {spans} dispatch spans but the counter "
                         f"moved by {counted}")
    hidden = [(n, p) for n, p in batches
              if n >= E.NATIVE_MAX and p not in DEVICE_PATHS]
    if probe.on_chip and hidden:
        raise SystemExit(f"FAIL: batches meant for the device took a host "
                         f"path: {hidden[:5]}")
    device_batches = sum(1 for n, p in batches if p in DEVICE_PATHS)
    gave = {k[0]: v - gave0.get(k, 0.0)
            for k, v in cm.gave_way_total.values().items()
            if v != gave0.get(k, 0.0)}
    log(f"   device batches: {device_batches}; gave way: "
        f"{json.dumps(gave) if gave else 'none'}")
    if gave.get("oversize"):
        raise SystemExit("FAIL: lanes went to the host at result()")
    log(f"   phase {name}: ok in {dt:.1f}s")
    return out, {"name": name, "s": dt, "gave": gave,
                 "device_batches": device_batches}


# ---------------------------------------------------------------------
# data


def noncanonical_identity_keys() -> list[bytes]:
    """Two non-canonical encodings of the identity that ZIP-215 accepts:
    y = p + 1 (>= p), and the same with the sign bit set on x = 0."""
    y = (2**255 - 19) + 1
    e0 = y.to_bytes(32, "little")
    e1 = bytearray(e0)
    e1[31] |= 0x80
    return [e0, bytes(e1)]


def build_commit(n: int, seed: int):
    """One commit over n ed25519 validators built with utils/factories;
    two of them hold non-canonical ZIP-215 keys (the identity, scalar 0:
    R = [r]B, S = r verifies for any message)."""
    from cometbft_tpu.utils import factories as fx

    signers = fx.make_signers(n - 2, seed=seed)
    signers += [fx.ScalarSigner(0, enc) for enc in noncanonical_identity_keys()]
    vals = fx.make_validator_set(signers)
    by_addr = {s.address(): s for s in signers}
    bid = fx.make_block_id(b"chip-smoke-%d" % seed)
    commit = fx.make_commit(CHAIN, 1, 0, bid, vals, by_addr,
                            sign_seed=seed + 1)
    weird = [i for i, v in enumerate(vals.validators)
             if by_addr[v.address].scalar == 0]
    return vals, bid, commit, weird


def corrupt_commit(commit, weird: list[int], seed: int):
    """A copy with a handful of bad lanes; returns (commit, {idx: why})."""
    import numpy as np

    from cometbft_tpu.crypto import ed25519_ref as ref

    n = len(commit.signatures)
    rng = np.random.default_rng(seed + 2)
    free = [i for i in rng.permutation(n).tolist() if i not in weird]
    bad = copy.deepcopy(commit)
    why = {}

    def mutate(idx, fn, label):
        sig = bytearray(bad.signatures[idx].signature)
        fn(sig)
        bad.signatures[idx].signature = bytes(sig)
        why[idx] = label

    def flip_r(sig):
        sig[3] ^= 0x10

    def flip_s(sig):
        sig[40] ^= 0x01

    def s_plus_l(sig):
        s = int.from_bytes(sig[32:], "little") + ref.L
        sig[32:] = s.to_bytes(32, "little")

    def garbage(sig):
        sig[:] = rng.bytes(32) + (1).to_bytes(32, "little")

    mutate(free[0], flip_r, "flipped bit in R")
    mutate(free[1], flip_s, "flipped bit in S")
    mutate(free[2], s_plus_l, "S >= L")
    # of the two non-canonical keys one keeps its valid signature (every
    # engine must ACCEPT it), the other gets a wrong one
    mutate(weird[1], garbage, "non-canonical A, wrong signature")
    bad.invalidate_memos()
    return bad, why


def commit_lanes(vals, commit):
    return [(vals.validators[i].pub_key,
             commit.vote_sign_bytes(CHAIN, i), cs.signature)
            for i, cs in enumerate(commit.signatures)]


def verifier(lanes, **kw):
    """An Ed25519BatchVerifier(backend="tpu") holding these lanes."""
    from cometbft_tpu.crypto.ed25519 import Ed25519BatchVerifier

    bv = Ed25519BatchVerifier(backend="tpu", **kw)
    for pub, msg, sig in lanes:
        bv.add(pub, msg, sig)
    return bv


def check_bitmap(bits, lanes, why: dict, weird: list[int], seed: int):
    """bits == the host C++ engine's verdict on every lane, and the
    pure-Python reference's on the bad lanes, the non-canonical lanes and
    a seeded sample of the good ones."""
    import numpy as np

    from cometbft_tpu.crypto import ed25519_ref as ref
    from cometbft_tpu.crypto import native

    n = len(lanes)
    host = [native.verify(p.bytes(), m, s) for p, m, s in lanes]
    if bits != host:
        d = [i for i in range(n) if bits[i] != host[i]]
        raise SystemExit(f"FAIL: device bitmap != host engine at lanes {d[:8]}")
    expect = [i not in why for i in range(n)]
    if bits != expect:
        raise SystemExit("FAIL: bitmap is not 'all lanes but the bad ones'")
    rng = np.random.default_rng(seed + 3)
    sample = set(why) | set(weird) | set(
        rng.choice(n, size=min(16, n), replace=False).tolist())
    for i in sorted(sample):
        p, m, s = lanes[i]
        if ref.verify(p.bytes(), m, s) != bits[i]:
            raise SystemExit(f"FAIL: device bitmap != reference at lane {i}")
    return len(sample)


# ---------------------------------------------------------------------
# phases (one chip)


def phase_megacommit(n: int, seed: int):
    from cometbft_tpu.types.validation import ErrInvalidSignature, verify_commit

    t0 = time.perf_counter()
    vals, bid, commit, weird = build_commit(n, seed)
    log(f"   built a {n}-validator commit in {time.perf_counter() - t0:.1f}s "
        f"(non-canonical keys at {weird})")
    verify_commit(CHAIN, vals, bid, 1, commit)  # backend="tpu", the default
    log("   honest commit: verify_commit accepted")
    lanes = commit_lanes(vals, commit)
    ok1, bits1 = verifier(lanes).verify()
    ok2, bits2 = verifier(lanes).verify()
    if not (ok1 and ok2 and bits1 == bits2 and all(bits1)):
        raise SystemExit("FAIL: honest bitmap not all-true twice")
    sampled = check_bitmap(bits1, lanes, {}, weird, seed)
    log(f"   honest bitmap: all {n} true twice; == host engine on {n} "
        f"lanes, == reference on {sampled}")

    bad, why = corrupt_commit(commit, weird, seed)
    try:
        verify_commit(CHAIN, vals, bid, 1, bad)
    except ErrInvalidSignature as e:
        first = min(why)
        if f"index {first}" not in str(e):
            raise SystemExit(f"FAIL: expected blame on index {first}: {e}")
        log(f"   corrupted commit: verify_commit refused ({e})")
    else:
        raise SystemExit("FAIL: corrupted commit was accepted")
    # the same bytes as a node gets them, off the wire: a fresh decode
    # carries its columns, so verify_commit judges them with no CommitSig
    # built, and must blame the same lane from the device's bitmap
    from cometbft_tpu.types import Commit

    for label, c, want in (("honest", commit, None),
                           ("corrupted", bad, f"index {min(why)}")):
        fresh = Commit.decode(c.encode())
        try:
            verify_commit(CHAIN, vals, bid, 1, fresh)
            got = None
        except ErrInvalidSignature as e:
            got = str(e)
        if (got is None) != (want is None) or (want and want not in got):
            raise SystemExit(f"FAIL: decoded {label} commit: {got}")
        if getattr(fresh.signatures, "_real", True) is not None:
            raise SystemExit(f"FAIL: decoded {label} commit took the "
                             f"per-slot path")
    log("   decoded commits (columnar entry): honest accepted, corrupted "
        "blamed on the same index")
    bad_lanes = commit_lanes(vals, bad)
    ok1, bits1 = verifier(bad_lanes).verify()
    ok2, bits2 = verifier(bad_lanes).verify()
    if ok1 or ok2 or bits1 != bits2:
        raise SystemExit("FAIL: corrupted bitmap not identical twice")
    sampled = check_bitmap(bits1, bad_lanes, why, weird, seed)
    log(f"   corrupted bitmap: false exactly at "
        f"{ {i: why[i] for i in sorted(why)} }, same twice; == host engine "
        f"on {n} lanes, == reference on {sampled}")
    return lanes


def _median_call_s(jit_fn, a, kw, reps: int = 5) -> float:
    import jax

    jax.block_until_ready(jit_fn(*a, **kw))
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(jit_fn(*a, **kw))
        ts.append(time.perf_counter() - t0)
    return statistics.median(ts)


def more_lanes(lanes, n: int, seed: int):
    """`lanes` grown to n signed lanes with further commits of the same
    size (a catch-up window's batch is 65 commits' lanes in one)."""
    out, k = list(lanes), 0
    while len(out) < n:
        k += 1
        vals, _bid, commit, _weird = build_commit(len(lanes), seed + 100 * k)
        out += commit_lanes(vals, commit)
    return out[:n]


def _engine_timings(probe: Probe, lanes, engine: str, calls: int = 5):
    """One device engine as submit() launches it, warm: medians over
    `calls` batches of submit() (host) and submit -> verdict in ms, and
    device ms a batch from a profiler trace of the same calls (busy time
    under the ladder's kernel scopes, which the mesh's shards run too, the
    reduction of tools/trace_analyze.py device; for the mesh the mean over
    its devices). The ladder is pinned by force_perlane, the mesh launched
    as submit() launches it where the model gives it the batch."""
    import glob

    import jax

    from cometbft_tpu.crypto import ed25519 as E
    from cometbft_tpu.utils import traceview, xplane
    from cometbft_tpu.utils.trace import KERNEL_SCOPES

    mesh = E._mesh_engine()

    def one():
        bv = verifier(lanes, force_perlane=True)
        t0 = time.perf_counter()
        pend = bv._launch_mesh(mesh) if engine == "mesh" else bv.submit()
        t1 = time.perf_counter()
        ok, _bits = pend.result()
        t2 = time.perf_counter()
        if not ok:
            raise SystemExit(f"FAIL: {engine} refused honest lanes")
        return (t1 - t0) * 1e3, (t2 - t0) * 1e3

    prof = os.path.join(probe.workdir, f"profile_{engine}_{len(lanes)}")
    # compiles or loads, fills the pubkey cache: of every device for the
    # ladder, whose streamed placement takes the devices in turn
    for _ in range(mesh.n_devices if mesh and engine == "ladder" else 1):
        one()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0  # device operations and our spans only
    # off the chip XLA:CPU books every thunk as a host event: 84 MB and
    # half a minute for three calls, and no device plane to read
    opts.host_tracer_level = 1 if probe.on_chip else 0
    jax.profiler.start_trace(prof, profiler_options=opts)
    try:
        took = [one() for _ in range(calls)]
    finally:
        jax.profiler.stop_trace()
    out = {"submit_ms": statistics.median(t[0] for t in took),
           "submit_to_verdict_ms": statistics.median(t[1] for t in took),
           "device_ms": None}
    pbs = sorted(glob.glob(os.path.join(prof, "**", "*.xplane.pb"),
                           recursive=True))
    try:
        j = traceview.device_join(xplane.load(pbs[-1]), scopes=KERNEL_SCOPES)
    except (IndexError, ValueError):
        return out  # no device plane in the trace (off the chip)
    by_scope = {k: v for k, v in j["busy_by_scope"] if k.startswith("ladder.")}
    if by_scope:
        share = calls * (mesh.n_devices if engine == "mesh" else 1)
        out["device_ms"] = sum(by_scope.values()) / share * 1e3
        out["by_scope_ms"] = {k: round(v / share * 1e3, 3)
                              for k, v in by_scope.items()}
    return out


def _packer_timings(lanes, calls: int = 5):
    """The host term beside the device terms: native.pack_rsk over these
    lanes' columns as the packer splits them itself (chunks over the C++
    worker pool) and pinned to one chunk (the calling thread alone).
    {"pooled" | "one chunk": (median ms of `calls`, chunks it reported)}."""
    import numpy as np

    from cometbft_tpu.crypto import ed25519 as E
    from cometbft_tpu.crypto import native

    bv = verifier(lanes)
    n = bv.count()
    rsk = np.zeros((E._bucket(n), 96), np.uint8)
    lens = np.asarray(bv._msg_lens, np.uint64)
    out = {}
    for label, nchunks in (("pooled", 0), ("one chunk", 1)):
        took = []
        for _ in range(calls):
            t0 = time.perf_counter()
            ran = native.pack_rsk(n, bv._sig_buf, bv._pub_buf, bv._msg_buf,
                                  lens, rsk, nchunks)
            took.append(time.perf_counter() - t0)
        out[label] = (statistics.median(took) * 1e3, ran)
    return out


def phase_device_terms(probe: Probe, lanes, sizes, seed: int):
    """The dispatch model's device terms, measured again: the ladder (and,
    where a mesh is up, the mesh) as submit() launches it, warm, at the
    live lane counts of the cells' two buckets (`sizes`), and from the two
    sizes each engine's fixed and per-lane term beside what
    crypto/ed25519.py assumes; beside them the host term, the packer at the
    calibration probe's lane count and at both sizes. Nothing is re-derived
    here: the printout is what a change of the constants is made from."""
    import jax
    import numpy as np

    from cometbft_tpu.crypto import ed25519 as E

    host = E._host_terms()
    log(f"   link probe _link_mbps() = {E._link_mbps():.1f} MB/s; host "
        f"term ladder {host['ladder_us']:.3f} us/sig, "
        f"calibrated={host['calibrated']}")
    if not host["calibrated"]:
        raise SystemExit("FAIL: host dispatch terms fell back to the "
                         "written-down constants (calibration failed)")
    where = "device" if probe.on_chip else "NOT A DEVICE NUMBER (rehearsal)"
    mesh = E._mesh_engine()
    engines = ("ladder", "mesh") if mesh is not None else ("ladder",)

    got: dict[str, dict[int, dict]] = {eng: {} for eng in engines}

    def log_packer(ls):
        log(f"   packer n={len(ls)}, host clock: " + ", ".join(
            f"{k} {ms:.3f} ms in {ran} chunk(s)"
            for k, (ms, ran) in _packer_timings(ls).items()))

    log_packer(lanes[:1024])  # the lane count of the calibration probe
    for n in sizes:
        big = more_lanes(lanes, n, seed)
        b = E._bucket(n)
        log_packer(big)
        mdl = E.dispatch_model(n, b)
        log(f"   n={n} bucket={b}: the model says " + ", ".join(
            f"{eng} {mdl[eng]['device'] * 1e3:.2f} ms of device "
            f"(t_{eng} {mdl['t_' + eng] * 1e3:.2f})" for eng in engines))
        for eng in engines:
            r = got[eng][n] = _engine_timings(probe, big, eng)
            # the ladder's program called again, its inputs on the device
            # and what an A-cache miss adds before it: the column's
            # decompression and the 128 doublings of the cached pair
            # (the mesh's miss: its staging program on a column already
            # on the shards)
            if eng == "ladder":
                r["kernel_ms"], r["miss_ms"] = (
                    _median_call_s(*probe.latest(fn, b)) * 1e3
                    for fn in ("verify_batch_cached_a", "decompress_pubkeys"))
                blocked = (f"; blocked call {r['kernel_ms']:.3f} ms, an "
                           f"A-cache miss's decompress_pubkeys "
                           f"{r['miss_ms']:.3f} ms")
            else:
                column = jax.device_put(np.zeros((b, 32), np.uint8),
                                        mesh._sharding)
                r["kernel_ms"] = None
                r["miss_ms"] = _median_call_s(mesh._stage, (column,), {}) * 1e3
                blocked = (f"; an A-cache miss's sharded decompress_pubkeys "
                           f"{r['miss_ms']:.3f} ms as a blocked call")
            dev = (f"{r['device_ms']:.3f} ms in the profile "
                   f"{r.get('by_scope_ms')}" if r["device_ms"] is not None
                   else "not in a profile")
            log(f"     {eng:<6} {where}: {dev}{blocked}; submit() "
                f"{r['submit_ms']:.3f} ms, submit -> verdict "
                f"{r['submit_to_verdict_ms']:.3f} ms")

    n0, n1 = sizes
    for eng in engines:
        fixed, per_lane = E._DEV_LADDER_FIXED_MS, E._DEV_LADDER_US
        if eng == "mesh":  # the ladder's own line over the device count
            fixed += mesh.dispatch_terms()["collective_s"] * 1e3
            per_lane /= mesh.n_devices
        t0, t1 = (got[eng][n]["device_ms"] or got[eng][n]["kernel_ms"]
                  for n in sizes)
        if t0 is None or t1 is None:
            log(f"   {eng}: not measured; assumed {fixed:.2f} ms + n x "
                f"{per_lane:.3f} us")
            continue
        us = (t1 - t0) / (n1 - n0) * 1e3
        log(f"   {eng}, {where}: fixed {t0 - n0 * us * 1e-3:.2f} ms + n x "
            f"{us:.3f} us through ({n0}, {t0:.2f} ms) and ({n1}, {t1:.2f} "
            f"ms); assumed {fixed:.2f} ms + n x {per_lane:.3f} us")
    return got


def _make_store(path: str, n_blocks: int, n_vals: int, seed: int, **kw):
    from cometbft_tpu.abci.kvstore import KVStoreApp
    from cometbft_tpu.storage import BlockStore, open_kv
    from cometbft_tpu.utils import factories as fx

    store = BlockStore(open_kv(path))
    # nonces for 10 commits a fill: 10 x 1000 lanes is the 10240 bucket
    pool = fx.RPool(n_vals, blocks_per_fill=10, seed=seed + 11)
    _, final, genesis, _ = fx.make_chain(
        n_blocks, n_validators=n_vals, chain_id=CHAIN, app=KVStoreApp(),
        block_store=store, seed=seed, verify_last_commit=False,
        r_pool=pool, **kw)
    return store, final, genesis


def phase_catchup(workdir: str, n_blocks: int, n_vals: int, window: int,
                  seed: int):
    import re

    from cometbft_tpu.abci.client import AppConns
    from cometbft_tpu.abci.kvstore import KVStoreApp
    from cometbft_tpu.blocksync import ReplayEngine
    from cometbft_tpu.state.execution import BlockExecutor
    from cometbft_tpu.types.validation import ErrInvalidSignature

    def engine(store, app):
        # the engine's own default is window=64, which at 1000 signers
        # is a 65,000-lane batch in the 65536 bucket; passed explicitly
        # only so the rehearsal can shrink it
        return ReplayEngine(store, BlockExecutor(AppConns(app)),
                            verify_mode="batched", window=window)

    t0 = time.perf_counter()
    db = os.path.join(workdir, "blockstore.db")
    store, final, genesis = _make_store(db, n_blocks, n_vals, seed)
    log(f"   generated {n_blocks} blocks x {n_vals} validators into sqlite "
        f"({os.path.getsize(db) / 1e6:.1f} MB) in "
        f"{time.perf_counter() - t0:.1f}s; BASELINE config 4 is 50,000 "
        f"blocks: the block count is cut to fit the run")
    state, stats = engine(store, KVStoreApp()).run(genesis.copy())
    if state.app_hash != final.app_hash or stats.blocks != n_blocks:
        raise SystemExit("FAIL: replayed app hash != the generator's")
    windows = -(-n_blocks // window)
    # every block's commit is verified once as the next block's embedded
    # LastCommit (or as the chain tip's), and each window but the last
    # also checks its own tip's stored commit before its successor exists
    expect = (n_blocks + windows - 1) * n_vals
    if stats.sigs_verified != expect:
        raise SystemExit(f"FAIL: sigs_verified {stats.sigs_verified} != "
                         f"{expect}")
    log(f"   replayed {stats.blocks} blocks, window {window} "
        f"({window * n_vals + n_vals} lanes a full window), in "
        f"{stats.elapsed_s:.2f}s: app hash equals the generator's; "
        f"sigs_verified {stats.sigs_verified} = blocks x signers "
        f"({n_blocks * n_vals}) + {windows - 1} window-tip commits")

    # a second chain with one corrupted signature: refused at that height.
    # Two full windows, so its batches have the shapes already compiled
    n2 = 2 * window
    h_bad, idx_bad = window + window // 4, n_vals // 3
    db2 = os.path.join(workdir, "blockstore_bad.db")
    store2, _, genesis2 = _make_store(db2, n2, n_vals, seed,
                                      corrupt_sig=(h_bad, idx_bad))
    app2 = KVStoreApp()
    try:
        engine(store2, app2).run(genesis2.copy())
    except ErrInvalidSignature as e:
        lane = int(re.search(r"lane (\d+)", str(e)).group(1))
    else:
        raise SystemExit("FAIL: the corrupted chain replayed to its tip")
    # the window before the corrupted one was applied, and no block of
    # the corrupted window
    if app2.height != window:
        raise SystemExit(f"FAIL: app at height {app2.height}, expected the "
                         f"last good window's tip {window}")
    # window k holds blocks kW+1..(k+1)W, whose embedded LastCommits are
    # those of heights kW..(k+1)W-1 (none for height 0), n_vals lanes each
    first_commit = max((h_bad // window) * window, 1)
    got = (first_commit + lane // n_vals, lane % n_vals)
    if got != (h_bad, idx_bad):
        raise SystemExit(f"FAIL: blame on (height, index) {got}, corrupted "
                         f"{(h_bad, idx_bad)}")
    log(f"   corrupted chain ({n2} blocks): applied through height {window}, "
        f"the next window refused at height {h_bad} index {idx_bad} "
        f"(window lane {lane})")


def phase_node(workdir: str, heights: int, n_txs: int, seed: int):
    """One home from `cli init`, one Node as `cli start` builds it (here,
    in this process: the chip belongs to one process), crypto_backend=tpu,
    sqlite stores; signed txs over RPC, /status and /metrics."""
    from cometbft_tpu import cli
    from cometbft_tpu.abci.kvstore import KVStoreApp
    from cometbft_tpu.config import Config
    from cometbft_tpu.crypto.ed25519 import Ed25519PrivKey
    from cometbft_tpu.e2e.runner import _rpc  # JSON-RPC over HTTP, raises
    from cometbft_tpu.mempool.admission import wrap_signed_tx
    from cometbft_tpu.node import Node

    home = os.path.join(workdir, "node")
    if cli.main(["--home", home, "init", "--chain-id", CHAIN]) != 0:
        raise SystemExit("FAIL: cli init")
    cfg_file = os.path.join(home, "config", "config.toml")
    cfg = Config.load(cfg_file)
    assert cfg.base.crypto_backend == "tpu" and cfg.base.db_backend == "sqlite"
    cfg.p2p.laddr = "tcp://127.0.0.1:0"
    cfg.rpc.laddr = "tcp://127.0.0.1:0"
    cfg.instrumentation.prometheus = True
    cfg.instrumentation.prometheus_listen_addr = "127.0.0.1:0"
    cfg.mempool.admission_verify_sigs = True
    cfg.consensus.timeout_commit = 0.2
    cfg.save(cfg_file)
    cfg = Config.load(cfg_file)
    cfg.base.home = home
    node = Node(cfg, app=KVStoreApp(
        snapshot_interval=cfg.base.snapshot_interval))
    node.start()
    port = node.rpc_addr[1]
    try:
        priv = Ed25519PrivKey(bytes([seed % 251 + 1]) * 32)
        txs = [wrap_signed_tx(priv, b"smoke-%d-%d=v" % (seed, i))
               for i in range(n_txs)]
        for tx in txs:
            res = _rpc(port, "broadcast_tx_sync", {"tx": tx.hex()}, 10.0)
            if int(res.get("code", 0)) != 0:
                raise SystemExit(f"FAIL: tx refused: {res}")
        deadline = time.time() + 120
        committed: set[bytes] = set()
        seen_h = 0
        while time.time() < deadline:
            st = _rpc(port, "status")
            h = int(st["sync_info"]["latest_block_height"])
            for hh in range(seen_h + 1, h + 1):
                blk = _rpc(port, "block", {"height": str(hh)})
                for t in blk["block"]["data"]["txs"] or []:
                    committed.add(bytes.fromhex(t))
                seen_h = hh
            if h >= heights and committed >= set(txs):
                break
            time.sleep(0.2)
        else:
            raise SystemExit(f"FAIL: node at height {seen_h} with "
                             f"{len(committed)}/{n_txs} txs after 120 s")
        with urllib.request.urlopen(
                "http://%s:%d/metrics" % node.metrics_server.addr,
                timeout=5.0) as r:
            metrics = r.read()
        for series in (b"cometbft_consensus_height",
                       b"cometbft_crypto_path_selected_total"):
            if series not in metrics:
                raise SystemExit(f"FAIL: /metrics lacks {series.decode()}")
        log(f"   node: height {seen_h}, {len(committed)} signed txs sent over "
            f"RPC all committed; /status and /metrics "
            f"({len(metrics)} bytes) answer")
        log("   node: its commits have one signer and its admission windows "
            "a few txs, far under NATIVE_MAX: its verifies go to the host "
            "engine by design (ROADMAP S1's finding, not a fault)")
    finally:
        node.stop()


# ---------------------------------------------------------------------
# four chips


def phase_mesh(probe: Probe, n: int, seed: int):
    import jax

    from cometbft_tpu.crypto import ed25519 as E
    from cometbft_tpu.utils import trace
    from cometbft_tpu.utils.metrics import crypto_metrics

    eng = E._mesh_engine()
    if eng is None or eng.n_devices != 4:
        raise SystemExit(
            "FAIL: no 4-device mesh engine on this host "
            f"({'none' if eng is None else eng.n_devices}); off the chip "
            "rehearse with COMETBFT_TPU_MESH=on and 4 virtual devices")
    terms = eng.dispatch_terms()
    log(f"   mesh engine: {eng.n_devices} devices, put_fixed "
        f"{terms['put_fixed_s'] * 1e6:.1f} us, collective "
        f"{terms['collective_s'] * 1e6:.1f} us (never measured: the "
        f"written-down figure), calibrated={terms['calibrated']}")
    if not terms["calibrated"] or not E._host_terms()["calibrated"]:
        raise SystemExit("FAIL: a dispatch calibration fell back to its "
                         "written-down constants")

    vals, _bid, commit, weird = build_commit(n, seed)
    bad, why = corrupt_commit(commit, weird, seed)

    seen_shard_devices: set = set()
    for label, c, drop in (("honest", commit, 0), ("corrupted", bad, 0),
                           ("honest, n-1 lanes", commit, 1),
                           ("corrupted, n-1 lanes", bad, 1)):
        lanes = commit_lanes(vals, c)[: n - drop]
        pend = verifier(lanes)._launch_mesh(eng)
        seen_shard_devices |= set(pend._dev.sharding.device_set)
        ok_m, bits_m = pend.result()
        ok_1, bits_1 = verifier(lanes, force_perlane=True).submit().result()
        if (ok_m, bits_m) != (ok_1, bits_1):
            raise SystemExit(f"FAIL: mesh != single-chip ladder ({label})")
        expect = [i not in why or c is commit for i in range(len(lanes))]
        if bits_m != expect:
            raise SystemExit(f"FAIL: mesh bitmap wrong ({label})")
        log(f"   {label}: {len(lanes)} lanes over 4 devices == the "
            f"single-chip ladder, {bits_m.count(False)} lanes false")
    if len(seen_shard_devices) != 4:
        raise SystemExit(f"FAIL: shards sat on {seen_shard_devices}")
    # a column is decompressed once and kept on the shards: the corrupted
    # commit has the honest one's keys, the n-1 lanes are another column
    trace.flush()
    with open(probe.trace_path, encoding="utf-8") as f:
        a_cache = [r.get("a_cache") for r in map(json.loads, f)
                   if r.get("name") == "crypto.mesh_submit"]
    log(f"   crypto.mesh_submit a_cache: {a_cache}")
    if a_cache != ["miss", "hit", "miss", "hit"]:
        raise SystemExit("FAIL: the mesh did not keep its staged columns")

    # both sharded programs must hold their kernels too: the staging
    # program A's decompression and the 128 doublings, the verifier R's
    # decompression and the ladder
    b = E._bucket(n)
    rsk, live, pub_blob = verifier(
        commit_lanes(vals, commit))._pack_rsk_live(n, b)
    (ok_a, a_points), _ = eng.stage_pubkeys(pub_blob, b)
    for label, lowered in (
            ("sharded decompress_pubkeys", eng._stage.lower(jax.device_put(
                eng._column(pub_blob, b), eng._sharding))),
            ("sharded_verify_rsk", eng._fn(b).lower(
                ok_a, a_points,
                *jax.device_put((rsk, live), eng._sharding)))):
        k = lowered.as_text().count("@tpu_custom_call")
        if probe.on_chip and k < 2:
            raise SystemExit(f"FAIL: {label} lowered with {k} of its 2 "
                             "Pallas kernels")
        log(f"   {label}[{b}/4 = {b // 4} a shard]: {k} tpu_custom_call"
            + ("" if probe.on_chip else " (off the chip)"))

    # streamed commits: whole batches, round-robin over next_device()
    lanes = commit_lanes(vals, commit)
    pends = [verifier(lanes, force_perlane=True).submit() for _ in range(8)]
    placed = [next(iter(p._dev.devices())) for p in pends]
    res = E.collect_pending(pends)
    if not all(ok for ok, _ in res):
        raise SystemExit("FAIL: a streamed commit failed")
    if len(set(placed)) != 4:
        raise SystemExit(f"FAIL: streamed commits sat on {set(placed)}")
    log(f"   streamed: 8 commits placed on {[d.id for d in placed]}")

    per_dev = {}
    for (dev, mode), v in crypto_metrics().mesh_batches_total.values().items():
        per_dev.setdefault(dev, {})[mode] = v
    log("   crypto_mesh_batches_total = " + json.dumps(per_dev, sort_keys=True))
    for i in range(4):
        d = per_dev.get(str(i), {})
        if not d.get("shard") or not d.get("stream"):
            raise SystemExit(f"FAIL: device {i} took no work: {per_dev}")

    pend = verifier(lanes).submit()
    assert pend.result()[0]
    mdl = E.dispatch_model(n, b)
    log(f"   plain submit() of {n} lanes on this host chose: {pend._path} "
        f"(model ms: ladder {mdl['t_ladder'] * 1e3:.2f} mesh "
        f"{mdl['t_mesh'] * 1e3:.2f}; link {mdl['link_mbps']:.0f} MB/s)")


# ---------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes, no platform assertion")
    ap.add_argument("--terms", action="store_true",
                    help="only the device-terms phase: the ladder (with "
                         "--chips 4 the mesh too) at both of the cells' "
                         "buckets, beside the dispatch's terms")
    args = ap.parse_args(argv)
    t_start = time.perf_counter()

    import jax

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    on_chip = device["platform"] == "tpu"
    if not args.rehearse and (not on_chip or len(devs) != args.chips):
        print(f"chip_smoke.py needs {args.chips} TPU chip(s); jax found "
              f"{device}", file=sys.stderr)
        return 2
    log(f"device: {json.dumps(device)} jax {jax.__version__} seed {args.seed}"
        + (" REHEARSAL (sizes shrunk)" if args.rehearse else ""))

    from cometbft_tpu.crypto import ed25519 as E
    from cometbft_tpu.crypto import native
    from cometbft_tpu.ops import CACHE_DIR

    cache = jax.config.jax_compilation_cache_dir
    log(f"compile cache: {cache} "
        + ("(from JAX_COMPILATION_CACHE_DIR)"
           if os.environ.get("JAX_COMPILATION_CACHE_DIR")
           else "(the fixed in-checkout path)" if cache == CACHE_DIR
           else "(set by the caller)")
        + f", {len(os.listdir(cache)) if os.path.isdir(cache) else 0} entries")
    if not native.available():
        raise SystemExit(f"FAIL: the host C++ engine is not available: "
                         f"{native.build_state()}")
    bs = native.build_state()
    log(f"native engine: {native.engine()}, "
        f"{'built here' if bs['built'] else 'matched an existing build'} "
        f"({os.path.basename(bs['path'])})")
    if on_chip and not E._accel_backed():
        raise SystemExit("FAIL: dispatch does not see the accelerator")

    workdir = tempfile.mkdtemp(prefix="chip_smoke_")
    small = args.rehearse
    phases = []
    try:
        probe = Probe(workdir, on_chip)
        n_mega = 48 if small else 10_000
        # live lanes of the two buckets the cells use (10240, 65536); the
        # rehearsal's two sizes share one bucket, so its fit says nothing
        term_sizes = (24, 48) if small else (10_000, 65_000)
        if args.terms:
            vals, _bid, commit, _weird = build_commit(n_mega, args.seed)
            _, rec = run_phase(
                probe, "device-terms",
                lambda: phase_device_terms(
                    probe, commit_lanes(vals, commit), term_sizes, args.seed))
            phases.append(rec)
        elif args.chips == 4:
            _, rec = run_phase(probe, "mesh-4",
                               lambda: phase_mesh(probe, n_mega, args.seed))
            phases.append(rec)
        else:
            lanes, rec = run_phase(
                probe, "mega-commit",
                lambda: phase_megacommit(n_mega, args.seed))
            phases.append(rec)
            _, rec = run_phase(
                probe, "device-terms",
                lambda: phase_device_terms(probe, lanes, term_sizes,
                                           args.seed))
            phases.append(rec)
            blocks, n_vals, window = (12, 8, 4) if small else (256, 1000, 64)
            _, rec = run_phase(
                probe, "catch-up",
                lambda: phase_catchup(workdir, blocks, n_vals, window,
                                      args.seed))
            phases.append(rec)
            _, rec = run_phase(
                probe, "node",
                lambda: phase_node(workdir, 3 if small else 5,
                                   4 if small else 16, args.seed))
            phases.append(rec)
        verify = [c for c in probe.compiles if probe.is_verify(c["fn"])]
        log("summary: " + json.dumps({
            "phases": {p["name"]: round(p["s"], 1) for p in phases},
            "device_batches": sum(p["device_batches"] for p in phases),
            "verify_shapes_compiled": sum(not c["cache_hit"] for c in verify),
            "verify_shapes_cache_hits": sum(c["cache_hit"] for c in verify),
            "compile_s": round(sum(c["s"] for c in probe.compiles), 1),
            "wall_s": round(time.perf_counter() - t_start, 1),
        }))
        if on_chip and not any(p["device_batches"] for p in phases):
            raise SystemExit("FAIL: no batch ran on the device")
    finally:
        from cometbft_tpu.utils import trace

        trace.disable()
        shutil.rmtree(workdir, ignore_errors=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
