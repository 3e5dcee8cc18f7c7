"""Traffic kind `catchup_replay`: one node catching up from a block store
through blocksync.ReplayEngine(verify_mode="batched").

Parameters (configuration shapes + the cell's traffic block):
  validators, blocks, window   the chain: `blocks` heights signed by
                    `validators`, replayed `window` heights to a batch
  txs_per_block     transactions the generator puts in each block (about
                    ten bytes each); what the app applies
  warmup_windows    windows of the first pass that are not measured (the
                    first traces, lowers and loads the RLC program)
  profile_windows   windows in the traced stretch, from the boundary that
                    opens the measured window: one pass's worth, because the
                    pipeline queues a window's batch while the one before is
                    applied, and the last window of a pass queues nothing
  device_from_lanes batches of this many lanes or more must take a device path

Set-up generates the chain into a sqlite store from --seed and replays its
first window once, on the per-lane ladder (the engine a declined RLC layout
falls back to): that warms the ladder and leaves the app and state of height
`window`. A pass replays from a copy of those to the tip; when it reaches the
tip a new pass starts. So every measured window is a steady one, `window`
embedded commits and the tip's (65,000 lanes at 1000 validators), as in a long
catch-up; the one-commit-shorter first window from genesis is a second RLC
program (about a minute to trace and lower in every run, PR 23) that a node
meets once in 50,000 blocks, and the measured traffic leaves it out. The
engine applies blocks in bursts of one window, so the rate is (blocks of whole
windows) / (time between window boundaries), a boundary being the apply of a
window's last block: stats.whole_window_rate.

The rate spans pass restarts, and says what they cost: every run prints the
median seconds of a window by its place in its pass, and from them the rate
of a chain of mid-pass windows (which queue the next window, wait for their
own verdict and apply, as every window of a long catch-up does), of a pass,
and of all windows; and the time the harness itself spends between two passes
(a copy of the app and the state, a new engine). The mid-pass windows are
also the per-layer series `window_s.mid_pass`.
"""

from __future__ import annotations

import copy
import os
import re
import time

from benchmark.harness import check as C
from benchmark.harness.env import log
from benchmark.harness.stats import whole_window_rate

CHAIN = "bench-catchup"


class _Stop(Exception):
    pass


def make_store(path: str, n_blocks: int, n_vals: int, txs: int, seed: int):
    from cometbft_tpu.abci.kvstore import KVStoreApp
    from cometbft_tpu.storage import BlockStore, open_kv
    from cometbft_tpu.utils import factories as fx

    store = BlockStore(open_kv(path))
    # nonces for 10 commits a fill: 10 x 1000 lanes is the 10240 bucket
    pool = fx.RPool(n_vals, blocks_per_fill=10, seed=seed + 11)
    _, final, genesis, _ = fx.make_chain(
        n_blocks, n_validators=n_vals, chain_id=CHAIN, txs_per_block=txs,
        app=KVStoreApp(), block_store=store, seed=seed,
        verify_last_commit=False, r_pool=pool)
    return store, final, genesis


class Driver:
    def __init__(self, ctx):
        self.ctx = ctx
        self.p = ctx.cell.params
        self.boundaries: list[tuple[float, int]] = []  # (t, blocks applied)
        self.pass_of: list[int] = []  # the pass each boundary closed a window of
        self.between_passes: list[tuple[float, float]] = []  # (t, harness s)
        self.applied = 0
        self.mode = "run"  # "aside": a replay whose blocks are not the run's
        self.applied_aside = 0
        self.hash_mismatch = 0
        self.hash_checked = 0
        self.passes: list[dict] = []
        self.error: str | None = None
        self.t0 = self.t1 = 0.0
        self.deadline = None
        self.profile_units = 0
        self._profiler = None

    # -- the engine, with the harness's executor under it ---------------

    def _engine(self, store, app):
        from cometbft_tpu.abci.client import AppConns
        from cometbft_tpu.blocksync import ReplayEngine
        from cometbft_tpu.state.execution import BlockExecutor

        drv = self

        class Executor(BlockExecutor):
            """Counts applied blocks and stamps each window boundary."""

            def apply_block_preverified(self, state, block_id, block):
                state = super().apply_block_preverified(state, block_id, block)
                drv._applied(state, block.header.height)
                return state

        return ReplayEngine(store, Executor(AppConns(app)),
                            verify_mode="batched", window=self.p["window"])

    def _applied(self, state, height: int) -> None:
        if self.mode == "aside":
            self.applied_aside += 1
            return
        self.applied += 1
        want = self.boundary_hash.get(height)
        if want is None:
            return
        now = time.perf_counter()
        self.hash_checked += 1
        self.hash_mismatch += state.app_hash != want
        self.boundaries.append((now, self.applied))
        self.pass_of.append(len(self.passes))
        if self.deadline is None:
            if len(self.boundaries) == self.p["warmup_windows"]:
                self._open_window(now)
            elif len(self.boundaries) == 1:
                self.ctx.objects_tracked("first RLC window replayed")
        else:
            if self._profiler is not None and self._profiler.active:
                self.profile_units += 1
                if self.profile_units == self.p["profile_windows"]:
                    self._profiler.stop()
            if now >= self.deadline:
                raise _Stop

    def _open_window(self, now: float) -> None:
        self.t0 = now
        self.deadline = now + self.seconds
        self.ctx.window_opens(now)
        if self._profiler is not None:
            self._profiler.start()

    def _pass(self) -> None:
        t0 = time.perf_counter()
        b0 = self.applied
        engine = self._engine(self.store, copy.deepcopy(self.app_w))
        start = self.state_w.copy()
        self.between_passes.append((t0, time.perf_counter() - t0))
        state, stats = engine.run(start)
        n, w = self.p["blocks"] - self.p["window"], self.p["window"]
        self.passes.append({
            "blocks": self.applied - b0, "s": time.perf_counter() - t0,
            "app_hash_ok": state.app_hash == self.final_hash,
            "sigs_verified": stats.sigs_verified,
            # each block's embedded LastCommit, and each window's tip commit
            "sigs_expected": (n + -(-n // w)) * self.p["validators"],
        })

    # -- phases --------------------------------------------------------

    def setup(self) -> None:
        p, seed = self.p, self.ctx.seed
        t0 = time.perf_counter()
        db = os.path.join(self.ctx.workdir, "blockstore.db")
        self.store, final, self.genesis = make_store(
            db, p["blocks"], p["validators"], p["txs_per_block"], seed)
        self.final_hash = final.app_hash
        self.ctx.objects_tracked("data built")
        log(f"   generated {p['blocks']} blocks x {p['validators']} validators "
            f"into sqlite ({os.path.getsize(db) / 1e6:.1f} MB) in "
            f"{time.perf_counter() - t0:.1f}s")
        # the first window, on the per-lane ladder at the window's bucket,
        # which a declined RLC layout falls back to; every pass starts from
        # the app and state it leaves
        t0 = time.perf_counter()
        from cometbft_tpu.abci.kvstore import KVStoreApp

        self.app_w = KVStoreApp()
        self.mode = "aside"
        with self.ctx.perlane_forced():
            self.state_w, _ = self._engine(self.store, self.app_w).run(
                self.genesis.copy(), to_height=p["window"])
        self.mode = "run"
        # the app hash after height h is what block h+1's header carries
        tip, w = p["blocks"], p["window"]
        self.boundary_hash = {
            h: self.store.load_block(h + 1).header.app_hash
            for h in range(2 * w, tip, w)}
        self.boundary_hash[tip] = self.final_hash
        self.ctx.objects_tracked("first window replayed on the ladder")
        log(f"   replayed the first window on the per-lane ladder in "
            f"{time.perf_counter() - t0:.1f}s")

    def window(self, seconds: float) -> None:
        """Runs passes until the deadline. The first `warmup_windows`
        boundaries belong to set-up: the measured window opens at the last
        of them (ctx.window_opens is called there)."""
        self.seconds = seconds
        self._profiler = self.ctx.profiler  # None unless --trace 1
        try:
            while True:
                self._pass()
        except _Stop:
            pass
        except Exception as e:  # the engine refused its own honest chain
            self.error = f"{type(e).__name__}: {e}"
            log(f"   replay FAILED: {self.error}")
            if self.deadline is None:
                raise
        self.t1 = time.perf_counter()
        if self._profiler is not None and self._profiler.active:
            self._profiler.stop()

    def profile_stretch(self) -> None:
        pass  # the stretch is the first measured window, traced as it runs

    def _rate(self):
        return whole_window_rate(self.boundaries, self.t0, self.seconds)

    def _window_times(self) -> dict:
        """Seconds between consecutive boundaries inside the run, by the
        window's place in its pass: `first` starts with an empty pipeline
        (and holds the harness's work between passes), `last` queues no
        next window, `mid_pass` does what every window of a long catch-up
        does."""
        per_pass = (self.p["blocks"] - self.p["window"]) // self.p["window"]
        end = self.t0 + self.seconds
        out: dict = {"first": [], "mid_pass": [], "last": []}
        place = 0
        for i in range(1, len(self.boundaries)):
            place = place + 1 if self.pass_of[i] == self.pass_of[i - 1] else 0
            (t_a, _), (t_b, _) = self.boundaries[i - 1], self.boundaries[i]
            if t_a < self.t0 or t_b > end:
                continue
            kind = ("first" if place == 0 else
                    "last" if place == per_pass - 1 else "mid_pass")
            out[kind].append(t_b - t_a)
        return out

    def series(self) -> dict:
        return {f"window_s.{k}": v for k, v in self._window_times().items()}

    def attempted(self) -> int:
        return self._rate()[2]

    @property
    def failed(self) -> int:
        return 1 if self.error else 0

    def expected_batches(self) -> int:
        return self._rate()[1]

    def metrics(self) -> dict:
        rate, windows, blocks, span = self._rate()
        rel = [round(t - self.t0, 2) for t, _ in self.boundaries]
        log(f"   whole windows inside the run: {windows} = {blocks} blocks in "
            f"{span:.2f}s; boundaries at {rel[:10]} ... {rel[-3:]}; "
            f"completed passes: {len(self.passes)}")
        wt = self._window_times()
        if all(wt.values()):
            import statistics

            w = self.p["window"]
            med = {k: statistics.median(v) for k, v in wt.items()}
            per_pass = (self.p["blocks"] - w) // w
            a_pass = med["first"] + (per_pass - 2) * med["mid_pass"] + med["last"]
            log(f"   windows by place in their pass (count, median s): "
                f"{ {k: (len(v), round(med[k], 3)) for k, v in wt.items()} }: "
                f"a chain of median mid-pass windows replays "
                f"{w / med['mid_pass']:.1f} blocks/s, a pass of median windows "
                f"{w * per_pass / a_pass:.1f} (what restarts cost), all "
                f"windows {rate:.1f} (what pauses cost besides)")
        inside = [s for t, s in self.between_passes
                  if self.t0 <= t <= self.t0 + self.seconds]
        log(f"   harness work between passes (a copy of the app and the "
            f"state, a new engine): {sum(inside) * 1e3:.1f} ms in "
            f"{len(inside)} restarts = {100 * sum(inside) / max(span, 1e-9):.3f}% "
            f"of the span")
        return {} if rate is None else {"catchup_blocks_per_s": rate}

    def verify(self) -> list:
        from cometbft_tpu.abci.kvstore import KVStoreApp
        from cometbft_tpu.types.validation import ErrInvalidSignature

        import numpy as np

        p, seed = self.p, self.ctx.seed
        out = [C.equal("replay_error", self.error, None),
               C.at_least("whole_windows_in_run", self._rate()[1], 1),
               C.at_least("boundaries_app_hash_checked", self.hash_checked, 1),
               C.equal("boundaries_app_hash_differs", self.hash_mismatch, 0)]
        n = len(self.passes)
        out.append(C.equal(
            f"completed_passes_of_{n}_whose_app_hash_is_not_the_generators",
            sum(not ps["app_hash_ok"] for ps in self.passes), 0))
        want = (self.passes[0]["sigs_expected"] if n else None)
        out.append(C.equal(
            f"completed_passes_of_{n}_whose_sigs_verified_is_not_{want}",
            sum(ps["sigs_verified"] != ps["sigs_expected"]
                for ps in self.passes), 0))
        # one signature flipped at a seeded (height, index) of the window that
        # follows the first: the chain is continued from the set-up's app and
        # state for one window, so the batch has the measured windows' shape
        # and nothing compiles. Refused with blame there; no block applied.
        from cometbft_tpu.storage import BlockStore, open_kv
        from cometbft_tpu.utils import factories as fx

        w, n_vals = p["window"], p["validators"]
        rng = np.random.default_rng([seed, 4])
        # the continuation signs the commits of heights w+1..2w; those of
        # w+1..2w-1 ride in blocks w+2..2w with full VerifyCommit semantics
        h_bad = int(rng.integers(w + 1, 2 * w))
        idx_bad = int(rng.integers(n_vals))
        t0 = time.perf_counter()
        store2 = BlockStore(open_kv(
            os.path.join(self.ctx.workdir, "blockstore_bad.db")))
        fx.make_chain(
            w, n_validators=n_vals, chain_id=CHAIN,
            txs_per_block=p["txs_per_block"],
            app=copy.deepcopy(self.app_w), block_store=store2, seed=seed,
            verify_last_commit=False, corrupt_sig=(h_bad, idx_bad),
            r_pool=fx.RPool(n_vals, blocks_per_fill=10, seed=seed + 12),
            start_state=self.state_w.copy(),
            start_commit=self.store.load_block_commit(w), start_height=w + 1)
        app2 = copy.deepcopy(self.app_w)
        self.mode, self.applied_aside = "aside", 0
        try:
            self._engine(store2, app2).run(self.state_w.copy())
            got = "replayed to its tip"
        except ErrInvalidSignature as e:
            m = re.search(r"lane (\d+)", str(e))
            lane = int(m.group(1)) if m else -1
            # the window holds the commits of heights w..2w-1 (embedded in
            # blocks w+1..2w) and the tip's stored commit, n_vals lanes each
            got = (w + lane // n_vals, lane % n_vals)
        out.append(C.equal("corrupted_chain.blame_height_index", got,
                           (h_bad, idx_bad)))
        out.append(C.equal("corrupted_chain.blocks_applied",
                           (self.applied_aside, app2.height - w), (0, 0)))
        log(f"   corrupted chain ({w} blocks, bad signature at height {h_bad} "
            f"index {idx_bad}) judged in {time.perf_counter() - t0:.1f}s")
        return out
