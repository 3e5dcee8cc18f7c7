"""Traffic kind `catchup_replay_indexed`: `catchup_replay_loaded`'s replayer
on a node that indexes, upstream's default `[tx_index] indexer = "kv"`.

Parameters (configuration shapes + the cell's traffic block): those of
`catchup_replay_loaded` (drivers/catchup_replay_loaded.py, whose driver this
one extends: the loaded block store, the state store on a file, the aside
replay of the first window, passes from copies of height `window`'s files,
the rate over whole windows), and
  indexer            "kv": the executor's event bus feeds the node's indexer
                     service, which writes a tx index and a block index on
                     sqlite files of the run's workdir

The indexing is built by the ONE function node/node.py calls,
storage/indexer.open_indexing(TxIndexConfig().indexer, <directory>): a
TxIndexer on tx_index.db, a BlockIndexer on block_index.db, the
IndexerService and the bus, which the replayer's BlockExecutor publishes
to. The index files are filled by the aside replay of heights 1..window,
settled and closed; every pass replays on COPIES of them beside its copy of
the state store, the app and the state. No query is sent inside the window.
A pass's last window closes only when the index holds the tip (the driver
waits for the service before it stamps that boundary; ReplayEngine.run
itself does not return before).

The driver's first act is to ask the program for what the deployment needs:
config.TxIndexConfig whose default is "kv", and storage/indexer's
open_indexing and TxIndexer.add_batch. A program without them (the tree
before the PR that brought this cell) ends the run there, at once, with
exit code 1 and no result line.

`correct`, every limit 0: the sibling's checks unchanged
(catchup_replay_loaded), and against the plain reference
reference/tx_index.py (handed each height's transactions as bytes; it knows
no bus, batch, thread or store), on the index of the last completed pass,
settled, closed and opened from a new connection (`index_checks`):
  - every hash the reference holds for heights 1..tip is found, with the
    reference's record: height, index in the block, bytes, the result's
    code and data (all of them, not a sample);
  - the count of records equals the reference's;
  - at 8 seeded heights `tx.height = h` gives the reference's hashes of
    that height, in block order;
  - 8 seeded hashes the reference does not hold are not found;
  - the block index holds exactly the heights 1..tip;
and of the program's own account:
  - it dropped 0 events (indexer_events_dropped_total over the process);
  - the most blocks any service held unwritten is at most
    storage/indexer.MAX_BLOCKS_HELD (K);
  - each of the two refused side chains leaves its copy of the index as
    height `window` left it (records, heights of the block index, the
    highest of them).
"""

from __future__ import annotations

import os
import shutil
import time

from benchmark.drivers import catchup_replay as base
from benchmark.drivers import catchup_replay_loaded as loaded
from benchmark.harness import check as C
from benchmark.harness.env import log
from benchmark.reference import tx_index as ref

SAMPLED = loaded.SAMPLED_HEIGHTS


def _program():
    """What the deployment needs of the program: (config.TxIndexConfig,
    storage.indexer). A program that lacks the `[tx_index]` section or the
    batch entry point ends the run here, at once, with exit code 1 and no
    result line."""
    from cometbft_tpu import config

    try:
        from cometbft_tpu.storage import indexer
    except ImportError:
        indexer = None
    if (not hasattr(config, "TxIndexConfig")
            or not hasattr(indexer, "open_indexing")
            or not hasattr(indexer.TxIndexer, "add_batch")):
        raise SystemExit(
            "FAIL: this program has no [tx_index] section "
            "(config.TxIndexConfig) or no batch entry point "
            "(storage/indexer.open_indexing, TxIndexer.add_batch): the "
            "configuration catchup-1000v-1ktx-kvindex cannot be run on it")
    return config.TxIndexConfig, indexer


def index_checks(index_dir: str, want: ref.Index, tip: int, seed: int,
                 sampled: int = SAMPLED) -> list:
    """The index files under `index_dir`, through a connection of their own,
    against the reference's index of heights 1..tip."""
    import numpy as np

    _, indexer = _program()
    out = []
    if not os.path.exists(os.path.join(index_dir, indexer.TX_INDEX_FILE)):
        return [C.equal("index.read_back_of_a_completed_pass", None, "found")]
    txi, bli, dbs = indexer.open_indexers(index_dir)
    try:
        missing = differs = 0
        for h, (height, index, tx, code, data) in want.records.items():
            rec = txi.get(h)
            if rec is None:
                missing += 1
            elif (rec["height"], rec["index"], rec["tx"], rec["code"],
                  rec["data"]) != (height, index, tx, code, data):
                differs += 1
        n = len(want.records)
        out.append(C.equal(
            f"index.hashes_of_{n}_not_found_by_get", missing, 0))
        out.append(C.equal(
            f"index.records_of_{n}_that_differ_from_the_references",
            differs, 0))
        out.append(C.equal("index.records_held", txi.count(), n))
        rng = np.random.default_rng([seed, 8])
        heights = sorted(rng.choice(
            np.arange(1, tip + 1), size=min(sampled, tip),
            replace=False).tolist())
        wrong = []
        for h in heights:
            want_h = want.by_height[h]
            got = txi.search(f"tx.height = {h}", limit=len(want_h) + 1)
            if [ref.tx_hash(r["tx"]) for r in got] != want_h:
                wrong.append(h)
        out.append(C.equal(
            f"index.heights_of_{len(heights)}_whose_tx_height_search_is_"
            f"not_the_references_hashes_in_order", wrong, []))
        absent = [ref.tx_hash(rng.bytes(32)) for _ in range(sampled)]
        out.append(C.equal(
            f"index.hashes_of_{sampled}_the_reference_does_not_hold_found",
            sum(h in want.records or txi.get(h) is not None
                for h in absent), 0))
        held = bli.search("block.height >= 1", limit=tip + 2)
        out.append(C.equal(
            "index.block_index_heights_are_1_to_tip",
            held == list(range(1, tip + 1)) or held[:8], True))
    finally:
        for db in dbs:
            db.close()
    return out


class Driver(loaded.Driver):
    def __init__(self, ctx):
        self.TxIndexConfig, self.indexer = _program()
        # the sibling's driver holds its own configuration to indexer
        # "null"; this one is that driver with the indexer on
        shapes = ctx.cell.params
        if shapes["indexer"] != self.TxIndexConfig().indexer:
            raise SystemExit(
                f"FAIL: this driver runs the program's default indexer "
                f"{self.TxIndexConfig().indexer!r}, the configuration says "
                f"{shapes['indexer']!r}")
        ctx.cell.params = dict(shapes, indexer="null")
        try:
            super().__init__(ctx)
        finally:
            ctx.cell.params = shapes
        self.p = shapes
        self.index_w_dir = os.path.join(ctx.workdir, "index_w")
        self.index_done_dir = os.path.join(ctx.workdir, "index_done")
        self._ix = None  # the indexing of the engine last built
        self._ix_dir = None
        self.max_held = 0
        self.side_index: list[tuple] = []
        self.tip_wait_s: list[float] = []

    # -- the engine: the sibling's, its executor publishing to the bus ----

    def _retire_state_store(self) -> None:
        completed = self._run_kv is not None and self._run_completed
        super()._retire_state_store()
        if self._ix is None:
            return
        self._ix.stop()  # writes what was published, closes both files
        self.max_held = max(self.max_held, self._ix.service.max_held)
        if completed:
            shutil.rmtree(self.index_done_dir, ignore_errors=True)
            os.replace(self._ix_dir, self.index_done_dir)
        elif self._ix_dir != self.index_w_dir:
            shutil.rmtree(self._ix_dir)
        self._ix = None

    def _engine(self, store, app):
        engine = super()._engine(store, app)  # retires the run before
        if os.path.exists(self.index_w_dir):
            self._ix_dir = os.path.join(self.ctx.workdir,
                                        f"index_run{self._runs}")
            shutil.copytree(self.index_w_dir, self._ix_dir)
        else:
            # set-up: a fresh node's empty index
            self._ix_dir = self.index_w_dir
            os.makedirs(self._ix_dir)
        self._ix = self.indexer.open_indexing(
            self.TxIndexConfig().indexer, self._ix_dir)
        engine.executor.event_bus = self._ix.event_bus
        return engine

    def _applied(self, state, height: int) -> None:
        if self.mode == "run" and height == self.p["blocks"]:
            # a pass's last window closes when the index holds the tip
            t0 = time.perf_counter()
            self._ix.service.wait(height)
            self.tip_wait_s.append(time.perf_counter() - t0)
        super()._applied(state, height)

    # -- phases ----------------------------------------------------------

    def setup(self) -> None:
        super().setup()  # ends with the aside run retired: files closed
        size = 0
        for name in sorted(os.listdir(self.index_w_dir)):
            path = os.path.join(self.index_w_dir, name)
            if name.endswith(".db"):
                base.settle_store(path)
            size += os.path.getsize(path)
        log(f"   the index of heights 1-{self.p['window']} "
            f"({self.indexer.TX_INDEX_FILE}, {self.indexer.BLOCK_INDEX_FILE}"
            f") is {size / 1e6:.1f} MB, settled and closed")

    def metrics(self) -> dict:
        out = super().metrics()
        done = self.index_done_dir
        size = (sum(os.path.getsize(os.path.join(done, f))
                    for f in os.listdir(done)) if os.path.exists(done) else 0)
        log(f"   a completed pass's index: {size / 1e6:.1f} MB; the waits "
            f"for the index at the tip, one a pass reached: "
            f"{[round(s * 1e3, 1) for s in self.tip_wait_s[:12]]} ms")
        return out

    # -- checks ----------------------------------------------------------

    def _refused(self, store):
        raised, applied = super()._refused(store)
        # the side chain's copy of the index, as its refused replay left it
        heights = self._ix.block_indexer.search(
            "block.height >= 1", limit=self.p["blocks"] + 2)
        self.side_index.append((self._ix.tx_indexer.count(), len(heights),
                                max(heights, default=0)))
        return raised, applied

    def verify(self) -> list:
        from cometbft_tpu.utils.metrics import indexer_metrics

        p, seed = self.p, self.ctx.seed
        w, tip, per = p["window"], p["blocks"], p["txs_per_block"]
        out = super().verify()  # retires the cut pass and the side chains'
        t0 = time.perf_counter()
        want = ref.Index()
        for h in range(1, tip + 1):
            want.block(h, self.store.load_block(h).data.txs)
        for name in sorted(os.listdir(self.index_done_dir)
                           if os.path.exists(self.index_done_dir) else ()):
            if name.endswith(".db"):
                base.settle_store(os.path.join(self.index_done_dir, name))
        out += index_checks(self.index_done_dir, want, tip, seed)
        m = indexer_metrics()
        out.append(C.equal(
            "index.events_dropped",
            int(sum(m.events_dropped_total.values().values())), 0))
        k = self.indexer.MAX_BLOCKS_HELD
        out.append(C.equal(
            f"index.blocks_held_unwritten_beyond_K_{k}_the_most_being_"
            f"{self.max_held}_over_"
            f"{int(sum(m.blocks_indexed_total.values().values()))}_indexed",
            max(0, self.max_held - k), 0))
        out.append(C.equal(
            "refused_side_chains.index_records_and_heights_left",
            self.side_index, 2 * [(per * w, w, w)]))
        log(f"   the reference indexed {tip} heights "
            f"({len(want.records)} records) and the last completed pass's "
            f"index was read back in {time.perf_counter() - t0:.1f}s")
        return out
