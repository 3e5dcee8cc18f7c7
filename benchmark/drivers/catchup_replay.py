"""Traffic kind `catchup_replay`: one node catching up from a block store
through blocksync.ReplayEngine(verify_mode="batched").

Parameters (configuration shapes + the cell's traffic block):
  validators, blocks, window   the chain: `blocks` heights signed by
                    `validators`, replayed `window` heights to a batch
  txs_per_block     transactions the generator puts in each block (about
                    ten bytes each); what the app applies
  warmup_windows    windows of the first pass that are not measured (the
                    first traces, lowers and loads the ladder at the
                    window's bucket where set-up has not)
  profile_windows   windows of the traced stretch: a pass of its own, run
                    under the profiler once the measured window has closed
                    (one pass's worth, because the pipeline queues a
                    window's batch while the one before is applied, and the
                    last window of a pass queues nothing)
  device_from_lanes batches of this many lanes or more must take a device path

Set-up generates the chain into a sqlite store from --seed, then does what a
node that stops does (checkpoints the write-ahead log, closes the store,
drops the generator's objects) and opens the finished file anew: the replayer
reads a store that something else wrote, never through the connection and
the heap that just wrote it (PERF.md, Findings PR 31). It then replays the
first window once, on the forced per-lane ladder, which warms the one engine
the windows use and leaves the app and state of height `window`. A pass
replays from a copy of those to the tip; when it reaches the tip a new pass
starts. So every measured window is a steady one, `window` embedded commits
and the tip's (65,000 lanes at 1000 validators), as in a long catch-up; the
one-commit-shorter first window from genesis is a second shape that a node
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

A process draws one of two speeds (PERF.md, Findings PRs 25 and 31): glibc's
free() either keeps a pass's 117 MB of commit-decode buffers or gives the top
of the heap back to the system and faults it in again in every pass, at the
load and the fill of the pass's last window. The benchmark leaves the draw as
a node meets it. Every run prints what tells the speeds apart, never as
metrics: beside the windows by place, the medians of their CPU seconds
(process and main thread) and of the change in the bytes the C allocator holds
from the system, read at the boundaries the driver stamps anyway; a traced run
reads `window_load_tail_ms.catchup`.
"""

from __future__ import annotations

import copy
import ctypes
import gc
import os
import re
import sqlite3
import statistics
import time

from benchmark.harness import check as C
from benchmark.harness.env import log
from benchmark.harness.stats import whole_window_rate

CHAIN = "bench-catchup"


class _Stop(Exception):
    pass


def make_store(path: str, n_blocks: int, n_vals: int, txs: int, seed: int):
    """The generator: the chain of --seed written into a sqlite store at
    `path`. Returns the store as the generator leaves it (open, its last
    writes in the write-ahead log), the final state, the genesis state, and
    the store's key-value handle, which is what there is to close."""
    from cometbft_tpu.abci.kvstore import KVStoreApp
    from cometbft_tpu.storage import BlockStore, open_kv
    from cometbft_tpu.utils import factories as fx

    kv = open_kv(path)
    store = BlockStore(kv)
    # nonces for 10 commits a fill: 10 x 1000 lanes is the 10240 bucket
    pool = fx.RPool(n_vals, blocks_per_fill=10, seed=seed + 11)
    _, final, genesis, _ = fx.make_chain(
        n_blocks, n_validators=n_vals, chain_id=CHAIN, txs_per_block=txs,
        app=KVStoreApp(), block_store=store, seed=seed,
        verify_last_commit=False, r_pool=pool)
    return store, final, genesis, kv


class _Mallinfo2(ctypes.Structure):
    _fields_ = [(name, ctypes.c_size_t) for name in (
        "arena", "ordblks", "smblks", "hblks", "hblkhd", "usmblks", "fsmblks",
        "uordblks", "fordblks", "keepcost")]


try:
    _mallinfo2 = ctypes.CDLL(None).mallinfo2
    _mallinfo2.restype = _Mallinfo2
except (AttributeError, OSError):  # not glibc 2.33 or later
    _mallinfo2 = None


def _heap_mb() -> float:
    """MB the C allocator holds from the system: its heaps and the chunks it
    mapped one by one (under a microsecond a call). It falls when free()
    trims the top of the heap or unmaps a chunk, and every byte that comes
    back is a page fault and a zeroed page (the chip's machine counts no
    faults in ru_minflt, so this is the reading that shows it): a slow
    process swings by a window's 117 MB in every pass, a fast one holds
    steady within some MB."""
    if _mallinfo2 is None:
        return 0.0
    m = _mallinfo2()
    return (m.arena + m.hblkhd) / 1e6


def settle_store(path: str) -> None:
    """What a node that stops leaves of its store: every frame of the
    write-ahead log moved into the file and the log cut to nothing, through
    a connection of its own while the writer's is idle."""
    conn = sqlite3.connect(path)
    try:
        conn.execute("PRAGMA wal_checkpoint(TRUNCATE)").fetchone()
    finally:
        conn.close()


def build_store(path: str, p: dict, seed: int) -> None:
    """The store as a catching-up node finds it: written by the generator,
    settled, closed. Beside it, as a stopped node's state store would hold
    them, the genesis state in its own encoding (`path`.genesis) and the
    final app hash (`path`.apphash); no object the generator made outlives
    the call."""
    store, final, genesis, kv = make_store(
        path, p["blocks"], p["validators"], p["txs_per_block"], seed)
    with open(path + ".genesis", "wb") as f:
        f.write(genesis.encode())
    with open(path + ".apphash", "wb") as f:
        f.write(final.app_hash)
    settle_store(path)
    kv.close()


def open_store(path: str):
    """The finished file opened anew, as a restarted node opens it:
    (store, genesis state, final app hash)."""
    from cometbft_tpu.state.types import State
    from cometbft_tpu.storage import BlockStore, open_kv

    with open(path + ".genesis", "rb") as f:
        genesis = State.decode(f.read())
    with open(path + ".apphash", "rb") as f:
        final_hash = f.read()
    return BlockStore(open_kv(path)), genesis, final_hash


class Driver:
    def __init__(self, ctx):
        self.ctx = ctx
        self.p = ctx.cell.params
        self.boundaries: list[tuple[float, int]] = []  # (t, blocks applied)
        # beside each boundary: (process CPU s, main-thread CPU s, MB the
        # allocator holds from the system)
        self.usage: list[tuple[float, float, float]] = []
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
        self.usage.append((time.process_time(), time.thread_time(),
                           _heap_mb()))
        self.pass_of.append(len(self.passes))
        if self.deadline is None:
            if len(self.boundaries) == self.p["warmup_windows"]:
                self._open_window(now)
            elif len(self.boundaries) == 1:
                self.ctx.objects_tracked("first window of a pass replayed")
        elif now >= self.deadline:
            raise _Stop

    def _open_window(self, now: float) -> None:
        self.t0 = now
        self.deadline = now + self.seconds
        self.ctx.window_opens(now)

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
        from cometbft_tpu.abci.kvstore import KVStoreApp

        db = os.path.join(self.ctx.workdir, "blockstore.db")
        build_store(db, p, seed)
        self.ctx.objects_tracked("data built")
        t1 = time.perf_counter()
        freed = gc.collect()
        self.store, self.genesis, self.final_hash = open_store(db)
        self.ctx.objects_tracked("store reopened")
        log(f"   generated {p['blocks']} blocks x {p['validators']} validators "
            f"into sqlite ({os.path.getsize(db) / 1e6:.1f} MB) in "
            f"{t1 - t0:.1f}s; closed, collected ({freed} unreachable objects) "
            f"and opened anew in {time.perf_counter() - t1:.2f}s")
        # the first window, on the forced per-lane ladder at the window's
        # bucket: the one engine the windows use; every pass starts from the
        # app and state it leaves
        t0 = time.perf_counter()

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

    def profile_stretch(self) -> None:
        """One pass more under the profiler, once the measured window has
        closed: the profiler's start and its stop (seconds of work on the
        calling thread) lie outside every span of the window. Its blocks
        are not the run's."""
        w, tip = self.p["window"], self.p["blocks"]
        to = min(tip, w * (1 + self.p["profile_windows"]))
        engine = self._engine(self.store, copy.deepcopy(self.app_w))
        start = self.state_w.copy()
        self.mode = "aside"
        self.ctx.profiler.start()
        try:
            engine.run(start, to_height=to)
        finally:
            self.ctx.profiler.stop()
            self.mode = "run"
        self.profile_units = -(-(to - w) // w)

    def _rate(self):
        return whole_window_rate(self.boundaries, self.t0, self.seconds)

    def _windows_by_place(self) -> dict:
        """The windows between consecutive boundaries inside the run, by the
        window's place in its pass: `first` starts with an empty pipeline
        (and holds the harness's work between passes), `last` queues no
        next window, `mid_pass` does what every window of a long catch-up
        does. Each a row (seconds, process CPU s, main-thread CPU s, MB
        more held from the system)."""
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
            out[kind].append((t_b - t_a, *(
                b - a for a, b in zip(self.usage[i - 1], self.usage[i]))))
        return out

    def series(self) -> dict:
        return {f"window_s.{k}": [row[0] for row in v]
                for k, v in self._windows_by_place().items()}

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
        by_place = self._windows_by_place()
        wt = {k: [row[0] for row in v] for k, v in by_place.items()}
        if all(wt.values()):
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
            cols = {k: [round(statistics.median(c), 3) for c in zip(*rows)][1:]
                    for k, rows in by_place.items()}
            held = [u[2] for (t, _), u in zip(self.boundaries, self.usage)
                    if self.t0 <= t <= self.t0 + self.seconds]
            log(f"   the same windows' medians of (process CPU s, main-thread "
                f"CPU s, MB more held from the system): {cols}; the allocator "
                f"held {min(held):.0f} to {max(held):.0f} MB at the boundaries "
                f"inside the run")
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
