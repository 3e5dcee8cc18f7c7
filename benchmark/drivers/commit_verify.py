"""Traffic kind `commit_verify`: a closed loop of one caller validating block
commits through types/validation.verify_commit.

Parameters (configuration shapes + the cell's traffic block):
  validators        signers of every commit (two of them hold non-canonical
                    ZIP-215 keys, as in chip_smoke.py)
  commits           K distinct commits (heights 1..K, distinct block ids),
                    taken round-robin
  profile_calls     timed calls in the traced stretch
  device_from_lanes batches of this many lanes or more must take a device
                    path (null: the cell's batches are all smaller, and its
                    dispatch never reaches the chip)

The driver's contract refuses a traced run in which no operation ran on the
device. So where device_from_lanes is null the traced stretch also judges one
commit's lanes on the device's per-lane ladder and compares the verdicts: the
cell drives the device path once, outside the timed calls and outside set-up.

A node only ever sees commits off the wire or the store: each call gets a
fresh Commit.decode of the encoded bytes, made OUTSIDE the timed call. The
validator set is constant, so the pubkey column stays cached on the device.
"""

from __future__ import annotations

import time

from benchmark.harness import check as C
from benchmark.harness.env import log

CHAIN = "bench-commit"


class Driver:
    def __init__(self, ctx):
        self.ctx = ctx
        self.p = ctx.cell.params
        self.samples: list[float] = []
        self.failed = 0
        self.t0 = self.t1 = 0.0
        self.profile_units = 0
        # a cell that bypasses the chip judges one commit on the device
        # inside its traced stretch: (lanes, (ok, bits))
        self.bypass = self.p["device_from_lanes"] is None
        self.crosscheck = None

    # -- data ----------------------------------------------------------

    def _build(self):
        from cometbft_tpu.types import Commit
        from cometbft_tpu.utils import factories as fx

        n, k, seed = self.p["validators"], self.p["commits"], self.ctx.seed
        t0 = time.perf_counter()
        signers = fx.make_signers(n - 2, seed=seed)
        signers += [fx.ScalarSigner(0, enc)
                    for enc in C.noncanonical_identity_keys()]
        self.vals = fx.make_validator_set(signers)
        by_addr = {s.address(): s for s in signers}
        self.weird = [i for i, v in enumerate(self.vals.validators)
                      if by_addr[v.address].scalar == 0]
        self.bids, self.encoded = [], []
        for h in range(1, k + 1):
            bid = fx.make_block_id(b"bench-%d-%d" % (seed, h))
            commit = fx.make_commit(CHAIN, h, 0, bid, self.vals, by_addr,
                                    sign_seed=seed * 1000 + h)
            self.bids.append(bid)
            self.encoded.append(commit.encode())
        self.decode = Commit.decode
        self.ctx.objects_tracked("data built")
        log(f"   built {n} validators (non-canonical keys at {self.weird}) "
            f"and {k} commits of {len(self.encoded[0])} bytes in "
            f"{time.perf_counter() - t0:.1f}s")

    def _call(self, i: int) -> float | None:
        """One timed verify_commit of commit i on a freshly decoded object;
        seconds, or None when it raised."""
        from cometbft_tpu.types import validation

        k = i % len(self.encoded)
        commit = self.decode(self.encoded[k])  # not timed
        t0 = time.perf_counter()
        try:
            validation.verify_commit(CHAIN, self.vals, self.bids[k], k + 1,
                                     commit)
        except validation.CommitError as e:
            log(f"   call {i} REFUSED an honest commit: {e}")
            return None
        return time.perf_counter() - t0

    # -- phases --------------------------------------------------------

    def setup(self) -> None:
        self._build()
        t0 = time.perf_counter()
        # every shape the window can use: the dispatch's own choice (the
        # first call traces, lowers and compiles or loads), and where batches
        # reach the device the per-lane ladder that a declined RLC layout
        # falls back to
        took = [self._call(i) for i in range(self.p["warmup_calls"])]
        if not self.bypass:
            with self.ctx.perlane_forced():
                took.append(self._call(0))
            took.append(self._call(1))
        if None in took:
            raise SystemExit("FAIL: a warm-up call refused an honest commit")
        self.ctx.objects_tracked("warmed up")
        log(f"   warmed up in {time.perf_counter() - t0:.1f}s; calls took "
            f"{[round(s, 3) for s in took]}s")

    def window(self, seconds: float) -> None:
        self.t0 = time.perf_counter()
        self.ctx.window_opens(self.t0)
        i = 0
        while time.perf_counter() - self.t0 < seconds:
            dt = self._call(i)
            if dt is None:
                self.failed += 1
            else:
                self.samples.append(dt)
            i += 1
        self.t1 = time.perf_counter()

    def profile_stretch(self) -> None:
        """A short steady stretch under the profiler, after the window."""
        n, profiler = self.p["profile_calls"], self.ctx.profiler
        if self.bypass:
            self._device_bitmap(0)  # traces and lowers the ladder: not traced
        profiler.start()
        for i in range(n):
            self._call(i)
        if self.bypass:
            self.crosscheck = self._device_bitmap(0)
        profiler.stop()
        self.profile_units = n

    def _device_bitmap(self, k: int):
        commit = self.decode(self.encoded[k])
        lanes = C.commit_lanes(CHAIN, self.vals, commit)
        return lanes, C.program_bitmap(lanes, force_perlane=True)

    def attempted(self) -> int:
        return len(self.samples) + self.failed

    def expected_batches(self) -> int:
        return self.attempted()  # one ed25519 batch a commit

    def metrics(self) -> dict:
        from benchmark.harness.stats import percentile, samples_beyond

        ms = [s * 1e3 for s in self.samples]
        n = len(ms)
        log(f"   timed calls: {n} accepted, {self.failed} refused, in "
            f"{self.t1 - self.t0:.2f}s; beyond p95: {samples_beyond(n, 95)} "
            f"samples (the guide wants 10)")
        if not ms:
            return {}
        slow = sorted(range(n), key=lambda i: -ms[i])[:8]
        log(f"   slowest calls (index: ms): "
            f"{ {i: round(ms[i], 1) for i in slow} }; min {min(ms):.1f}")
        return {"commit_verify_ms.p50": percentile(ms, 50),
                "commit_verify_ms.p95": percentile(ms, 95)}

    def verify(self) -> list:
        """Outside the window, on a seeded commit of the K."""
        import numpy as np

        from cometbft_tpu.types import validation

        seed = self.ctx.seed
        k = int(np.random.default_rng([seed, 1]).integers(len(self.encoded)))
        out = [C.equal("timed_calls_refused", self.failed, 0),
               C.at_least("timed_calls", len(self.samples), 1)]
        commit = self.decode(self.encoded[k])
        lanes = C.commit_lanes(CHAIN, self.vals, commit)
        ok, bits = C.program_bitmap(lanes)
        out.append(C.equal("honest.batch_ok", ok, True))
        out += C.bitmap_checks("honest", bits, lanes, {}, self.weird, seed)

        bad, why = C.corrupt_commit(commit, self.weird, seed)
        first = min(why)
        try:
            validation.verify_commit(CHAIN, self.vals, self.bids[k], k + 1, bad)
            blamed = "accepted"
        except validation.ErrInvalidSignature as e:
            blamed = str(e)
        out.append(C.Check("corrupted.verify_commit", blamed,
                           f"refused with 'index {first}'",
                           blamed.endswith(f"index {first}")))
        bad_lanes = C.commit_lanes(CHAIN, self.vals, bad)
        ok, bits = C.program_bitmap(bad_lanes)
        out.append(C.equal("corrupted.batch_ok", ok, False))
        out += C.bitmap_checks("corrupted", bits, bad_lanes, why, self.weird,
                               seed)
        log(f"   corrupted commit {k + 1}: "
            f"{ {i: why[i] for i in sorted(why)} }")
        if self.crosscheck is not None:
            lanes0, (ok, bits) = self.crosscheck
            out.append(C.equal("honest.device_ladder_ok", ok, True))
            out += C.bitmap_checks("device_ladder", bits, lanes0, {},
                                   self.weird, seed)
        return out
