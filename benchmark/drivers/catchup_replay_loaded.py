"""Traffic kind `catchup_replay_loaded`: `catchup_replay`'s one replayer over
blocks that carry upstream's QA load, with the node's state store on disk.

Parameters (configuration shapes + the cell's traffic block): those of
`catchup_replay` (drivers/catchup_replay.py, whose driver this one extends:
the block store written, settled, closed and opened anew; the first window
replayed aside on the forced per-lane ladder; passes from a copy of the state
and app of height `window` to the tip; a new pass at the tip; the rate over
whole windows), and
  txs_per_block, tx_bytes   every block carries this many transactions of
                     this many bytes and no other
  tx_format          "loadtime": `a=` + the hex of test/loadtime's Payload
                     (utils/factories.LoadtimeTxs, drawn from --seed)
  load_connections, load_rate   the `connections` and `rate` the payloads name
  state_store        "sqlite": the replayer's executor holds a StateStore on
                     a file of the run's workdir, as node/node.py builds it
  indexer            "null": no event bus, so nothing is indexed

The replayer is ReplayEngine(store, BlockExecutor(AppConns(KVStoreApp()),
state_store=StateStore(open_kv(<file>))), verify_mode="batched"). The state
store's file is bootstrapped as a fresh node's is (the genesis state) and
filled by the aside replay of heights 1..window; it is then settled and
closed, and every pass replays on a COPY of it beside its copy of the app
and the state (the copy, the closing of the pass before and the new engine
are the harness's time between passes, printed as such by every run).

`correct`, every limit 0 (the plain reference is reference/kvstore_replay.py:
from each height's transactions and the two hashes its header carries it
recomputes data_hash, the application's dict and the next last_results_hash;
it knows no window, no app hash and no store):
  - the sibling's checks: at every window boundary the app hash equals the
    next header's; every completed pass verified (blocks + windows) x
    validators signatures; the path checks;
  - at every boundary inside the run the app's store equals the reference's
    dict of that height; at every height the header's data_hash and
    last_results_hash equal the reference's roots (all heights: the
    reference replays them all for the dict anyway);
  - every completed pass applied exactly txs_per_block x its blocks
    transactions (the program's blocksync_txs_applied_total; traced, also
    the `txs` of its blocksync.window_apply spans);
  - read back: once the window has closed, the state store of the last
    completed pass, closed and opened from a new connection: load() is the
    tip's state with the chain's app hash; at 8 seeded heights of the pass
    load_validators hashes to that header's validators_hash,
    load_abci_responses decodes to txs_per_block results of code 0 whose
    root is the reference's, and load_finalize_response is that root;
  - outside the run, a side chain of one window continued from the state of
    height `window`, replayed batched twice: first with one signature
    flipped at a seeded (height, index): refused with blame there; then
    with one byte of one stored transaction flipped as well, at a seeded
    height: refused because the block's id is no longer the one its
    successor's commit signs, and the reference's data_hash differs from
    the header's at that height. Neither applies a block.
"""

from __future__ import annotations

import copy
import gc
import json
import os
import re
import resource
import shutil
import statistics
import time

from benchmark.drivers import catchup_replay as base
from benchmark.harness import check as C
from benchmark.harness.env import log
from benchmark.reference import kvstore_replay as ref

CHAIN = base.CHAIN
SAMPLED_HEIGHTS = 8


def _fixtures():
    """The program's fixture kit, if it can make blocks that carry the QA
    load. A program that cannot (the tree before the PR that brought this
    cell) ends the run here, at once, with exit code 1 and no result line."""
    from cometbft_tpu.utils import factories as fx

    if not hasattr(fx, "LoadtimeTxs"):
        raise SystemExit(
            "FAIL: this program's utils/factories has no LoadtimeTxs: the "
            "configuration catchup-1000v-1ktx cannot be run on it")
    return fx


def _load(fx, p: dict, seed: int):
    if p["tx_format"] != "loadtime":
        raise SystemExit(f"FAIL: unknown tx_format {p['tx_format']!r}")
    return fx.LoadtimeTxs(seed, per_block=p["txs_per_block"],
                          size=p["tx_bytes"],
                          connections=p["load_connections"],
                          rate=p["load_rate"])


def build_store(path: str, p: dict, seed: int) -> None:
    """catchup_replay.build_store over blocks that carry the load."""
    from cometbft_tpu.abci.kvstore import KVStoreApp
    from cometbft_tpu.storage import BlockStore, open_kv

    fx = _fixtures()
    n = p["validators"]
    kv = open_kv(path)
    _, final, genesis, _ = fx.make_chain(
        p["blocks"], n_validators=n, chain_id=CHAIN, txs_per_block=0,
        extra_txs=_load(fx, p, seed), app=KVStoreApp(),
        block_store=BlockStore(kv), seed=seed, verify_last_commit=False,
        r_pool=fx.RPool(n, blocks_per_fill=10, seed=seed + 11))
    for ext, blob in ((".genesis", genesis.encode()),
                      (".apphash", final.app_hash)):
        with open(path + ext, "wb") as f:
            f.write(blob)
    base.settle_store(path)
    kv.close()


class Driver(base.Driver):
    def __init__(self, ctx):
        super().__init__(ctx)
        _fixtures()
        p = self.p
        if (p["state_store"], p["indexer"]) != ("sqlite", "null"):
            raise SystemExit("FAIL: this driver runs a state store on sqlite "
                             "and no indexer")
        self.state_w_path = os.path.join(ctx.workdir, "state_w.db")
        self.state_done_path = os.path.join(ctx.workdir, "state_done.db")
        self._run_kv = None  # the state store of the engine last built
        self._run_path = None
        self._run_completed = False
        self._runs = 0
        self.mark_stores: list[tuple[int, dict]] = []
        self.txs_by_pass: list[int] = []
        self.pass_ns: list[tuple[int, int]] = []

    # -- the engine: the node's executor, its state store on a file -------

    def _retire_state_store(self) -> None:
        """Closes the state store of the engine before; a completed pass's
        file is kept for the read-back, any other is removed."""
        if self._run_kv is None:
            return
        self._run_kv.close()
        if self._run_completed:
            os.replace(self._run_path, self.state_done_path)
        elif self._run_path != self.state_w_path:
            os.remove(self._run_path)
        self._run_kv, self._run_completed = None, False

    def _engine(self, store, app):
        from cometbft_tpu.abci.client import AppConns
        from cometbft_tpu.blocksync import ReplayEngine
        from cometbft_tpu.state.execution import BlockExecutor
        from cometbft_tpu.storage import StateStore, open_kv

        drv = self
        self._retire_state_store()
        if os.path.exists(self.state_w_path):
            self._runs += 1
            self._run_path = os.path.join(self.ctx.workdir,
                                          f"state_run{self._runs}.db")
            shutil.copyfile(self.state_w_path, self._run_path)
            self._run_kv = open_kv(self._run_path)
        else:
            # set-up: what a fresh node does before it syncs (state/
            # handshake.py: the genesis state, the sets of heights 1 and 2)
            self._run_path = self.state_w_path
            self._run_kv = open_kv(self._run_path)
            StateStore(self._run_kv).save(self.genesis)

        class Executor(BlockExecutor):
            """Counts applied blocks and stamps each window boundary."""

            def apply_block_preverified(self, state, block_id, block):
                state = super().apply_block_preverified(state, block_id, block)
                h = block.header.height
                if drv.mode == "run" and h in drv.boundary_hash:
                    drv.mark_stores.append((h, dict(app.store)))
                drv._applied(state, h)
                return state

        return ReplayEngine(
            store, Executor(AppConns(app),
                            state_store=StateStore(self._run_kv)),
            verify_mode="batched", window=self.p["window"])

    def _pass(self) -> None:
        from cometbft_tpu.utils.metrics import blocksync_metrics

        counter = blocksync_metrics().txs_applied_total
        before = sum(counter.values().values())
        t0 = time.perf_counter_ns()
        super()._pass()  # an interrupted pass leaves here by _Stop
        self.pass_ns.append((t0, time.perf_counter_ns()))
        self.txs_by_pass.append(int(sum(counter.values().values()) - before))
        self._run_completed = True

    # -- phases ----------------------------------------------------------

    def setup(self) -> None:
        from cometbft_tpu.abci.kvstore import KVStoreApp

        p, seed = self.p, self.ctx.seed
        t0 = time.perf_counter()
        db = os.path.join(self.ctx.workdir, "blockstore.db")
        build_store(db, p, seed)
        self.ctx.objects_tracked("data built")
        t1 = time.perf_counter()
        freed = gc.collect()
        self.store, self.genesis, self.final_hash = base.open_store(db)
        self.ctx.objects_tracked("store reopened")
        log(f"   generated {p['blocks']} blocks x {p['validators']} validators "
            f"x {p['txs_per_block']} transactions of {p['tx_bytes']} bytes "
            f"into sqlite ({os.path.getsize(db) / 1e6:.1f} MB) in "
            f"{t1 - t0:.1f}s; closed, collected ({freed} unreachable objects) "
            f"and opened anew in {time.perf_counter() - t1:.2f}s")

        # heights 1..window on the forced per-lane ladder, into a state
        # store bootstrapped as a fresh node's; every pass starts from the
        # app, the state and a copy of the state store this leaves
        t0 = time.perf_counter()
        tip, w = p["blocks"], p["window"]
        self.boundary_hash = {}
        self.app_w = KVStoreApp()
        self.mode = "aside"
        with self.ctx.perlane_forced():
            self.state_w, _ = self._engine(self.store, self.app_w).run(
                self.genesis.copy(), to_height=w)
        self.mode = "run"
        self._retire_state_store()
        base.settle_store(self.state_w_path)
        self.boundary_hash = {
            h: self.store.load_block(h + 1).header.app_hash
            for h in range(2 * w, tip, w)}
        self.boundary_hash[tip] = self.final_hash
        self.ctx.objects_tracked("first window replayed on the ladder")
        log(f"   replayed heights 1-{w} on the per-lane ladder in "
            f"{time.perf_counter() - t0:.1f}s; the state store of height {w} "
            f"is {os.path.getsize(self.state_w_path) / 1e6:.1f} MB, settled "
            f"and closed")

    def metrics(self) -> dict:
        out = super().metrics()
        log(f"   peak resident memory of the process: "
            f"{resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1e3:.0f} "
            f"MB; a completed pass's state store: "
            + (f"{os.path.getsize(self.state_done_path) / 1e6:.1f} MB"
               if os.path.exists(self.state_done_path) else "none completed"))
        return out

    # -- checks ----------------------------------------------------------

    def _spans(self, names: set) -> list:
        from cometbft_tpu.utils import trace

        if not self.ctx.trace_path:
            return []
        trace.flush()
        with open(self.ctx.trace_path, encoding="utf-8") as f:
            recs = (json.loads(line) for line in f)
            return [r for r in recs if r.get("name") in names]

    def _per_block_table(self, spans: list) -> None:
        """Medians of the run's spans by stage, in ms a block (a window's
        spans over its blocks): what PERF.md's table is copied from."""
        t_a, t_b = (int(t * 1e9) for t in (self.t0, self.t1))
        w = self.p["window"]
        rows: dict = {}
        for r in spans:
            if not t_a <= r["t0_ns"] <= t_b:
                continue
            stem = r["name"].split(".")[1]
            if stem == "apply_block":
                fields, per = ("dur_ms", "validate_ms", "data_hash_ms",
                               "finalize_ms", "update_state_ms", "commit_ms",
                               "save_events_ms", "state_save_ms"), 1
            else:
                fields, per = ("dur_ms", "read_ms", "decode_ms"), w
            for f in fields:
                if f in r:
                    rows.setdefault(f"{stem}.{f}", []).append(r[f] / per)
        log(f"   medians of the run's spans, ms a block (a window's over its "
            f"{w} blocks): " + json.dumps(
                {k: round(statistics.median(v), 4) for k, v in rows.items()}))

    def _side_chain(self, **kw):
        """A chain of one window continued from the state of height
        `window`, its blocks carrying the load: (store, its key-value
        handle)."""
        from cometbft_tpu.storage import BlockStore, open_kv

        fx = _fixtures()
        p, seed = self.p, self.ctx.seed
        w, n = p["window"], p["validators"]
        kv = open_kv(os.path.join(self.ctx.workdir, "blockstore_bad.db"))
        store = BlockStore(kv)
        fx.make_chain(
            w, n_validators=n, chain_id=CHAIN, txs_per_block=0,
            extra_txs=_load(fx, p, seed), app=copy.deepcopy(self.app_w),
            block_store=store, seed=seed, verify_last_commit=False,
            r_pool=fx.RPool(n, blocks_per_fill=10, seed=seed + 12),
            start_state=self.state_w.copy(),
            start_commit=self.store.load_block_commit(w), start_height=w + 1,
            **kw)
        return store, kv

    def _refused(self, store):
        """(what a batched replay of the side chain raised, blocks applied)."""
        from cometbft_tpu.types.validation import CommitError

        w = self.p["window"]
        app = copy.deepcopy(self.app_w)
        self.mode, self.applied_aside = "aside", 0
        try:
            self._engine(store, app).run(self.state_w.copy())
            raised = None
        except CommitError as e:
            raised = e
        finally:
            self.mode = "run"
        return raised, (self.applied_aside, app.height - w)

    def verify(self) -> list:
        import numpy as np

        from cometbft_tpu.abci import wire
        from cometbft_tpu.state.execution import results_hash
        from cometbft_tpu.storage import StateStore, open_kv
        from cometbft_tpu.storage.blockstore import _key_block
        from cometbft_tpu.types.validation import (
            ErrInvalidBlockID, ErrInvalidSignature)

        p, seed = self.p, self.ctx.seed
        w, n, tip, per = (p["window"], p["validators"], p["blocks"],
                          p["txs_per_block"])
        self._retire_state_store()  # the pass the deadline cut, if any
        out = [C.equal("replay_error", self.error, None),
               C.at_least("whole_windows_in_run", self._rate()[1], 1),
               C.at_least("boundaries_app_hash_checked", self.hash_checked, 1),
               C.equal("boundaries_app_hash_differs", self.hash_mismatch, 0)]
        k = len(self.passes)
        out.append(C.at_least("completed_passes", k, 1))
        out.append(C.equal(
            f"completed_passes_of_{k}_whose_app_hash_is_not_the_generators",
            sum(not ps["app_hash_ok"] for ps in self.passes), 0))
        want = self.passes[0]["sigs_expected"] if k else None
        out.append(C.equal(
            f"completed_passes_of_{k}_whose_sigs_verified_is_not_{want}",
            sum(ps["sigs_verified"] != ps["sigs_expected"]
                for ps in self.passes), 0))
        want = per * (tip - w)
        out.append(C.equal(
            f"completed_passes_of_{k}_whose_transactions_applied_is_not_{want}",
            sum(t != want for t in self.txs_by_pass), 0))
        spans = self._spans({"blocksync.window_apply", "blocksync.window_load",
                             "blocksync.window_fill", "state.apply_block",
                             "blocksync.window_resolve"})
        if self.ctx.trace_path:
            sums = [0] * k
            for r in spans:
                if r["name"] != "blocksync.window_apply":
                    continue
                for i, (a, b) in enumerate(self.pass_ns):
                    if a <= r["t0_ns"] <= b:
                        sums[i] += int(r["txs"])
            out.append(C.equal(
                f"completed_passes_of_{k}_whose_window_apply_txs_is_not_{want}",
                sum(s != want for s in sums), 0))
            self._per_block_table(spans)

        # the reference replays the whole chain from its transactions
        t0 = time.perf_counter()
        r = ref.Replay(keep=self.boundary_hash)
        for h in range(1, tip + 1):
            blk = self.store.load_block(h)
            r.block(h, blk.data.txs, blk.header.data_hash,
                    blk.header.last_results_hash)
        out.append(C.equal("reference.transactions_and_bytes",
                           (r.txs, r.tx_bytes),
                           (per * tip, per * tip * p["tx_bytes"])))
        out.append(C.equal(
            f"heights_of_{tip}_whose_data_hash_or_last_results_hash_is_not_"
            f"the_references", r.differs, []))
        out.append(C.at_least("boundaries_app_store_checked",
                              len(self.mark_stores), 1))
        out.append(C.equal(
            "boundaries_app_store_differs_from_the_references_dict",
            sum(got != r.snapshots[h] for h, got in self.mark_stores), 0))
        log(f"   the reference replayed {tip} heights ({r.txs} transactions, "
            f"{r.tx_bytes / 1e6:.1f} MB) in {time.perf_counter() - t0:.1f}s")

        # read back: the last completed pass's state store, which its
        # connection has closed, through a new one
        rng = np.random.default_rng([seed, 6])
        sample = sorted(rng.choice(np.arange(w + 1, tip + 1),
                                   size=min(SAMPLED_HEIGHTS, tip - w),
                                   replace=False).tolist())
        got = {"state": None, "validators": [], "responses": [], "roots": []}
        if os.path.exists(self.state_done_path):
            skv = open_kv(self.state_done_path)
            try:
                ss = StateStore(skv)
                st = ss.load()
                got["state"] = (st.last_block_height, st.app_hash.hex())
                for h in sample:
                    hdr = self.store.load_block(h).header
                    vals = ss.load_validators(h)
                    if vals is None or vals.hash() != hdr.validators_hash:
                        got["validators"].append(h)
                    raw = ss.load_abci_responses(h)
                    resp = wire.dec_finalize_resp(raw) if raw else None
                    if (resp is None or len(resp.tx_results) != per
                            or any(t.code for t in resp.tx_results)):
                        got["responses"].append(h)
                    elif not (results_hash(resp.tx_results)
                              == r.results_root[h]
                              == ss.load_finalize_response(h)):
                        got["roots"].append(h)
            finally:
                skv.close()
        out.append(C.equal("read_back.state_height_and_app_hash",
                           got["state"], (tip, self.final_hash.hex())))
        out.append(C.equal(
            f"read_back.heights_of_{len(sample)}_whose_validators_hash_is_"
            f"not_the_headers", got["validators"], []))
        out.append(C.equal(
            f"read_back.heights_of_{len(sample)}_whose_responses_are_not_"
            f"{per}_results_of_code_0", got["responses"], []))
        out.append(C.equal(
            f"read_back.heights_of_{len(sample)}_whose_results_root_is_not_"
            f"the_references", got["roots"], []))

        # the side chain: one signature flipped at a seeded (height, index);
        # the commits of w+1..2w-1 ride in blocks w+2..2w
        rng = np.random.default_rng([seed, 4])
        h_bad = int(rng.integers(w + 1, 2 * w))
        idx_bad = int(rng.integers(n))
        h_byte = int(rng.integers(w + 1, 2 * w + 1))
        t0 = time.perf_counter()
        store2, kv2 = self._side_chain(corrupt_sig=(h_bad, idx_bad))
        raised, applied = self._refused(store2)
        blame = type(raised).__name__
        if isinstance(raised, ErrInvalidSignature):
            m = re.search(r"lane (\d+)", str(raised))
            lane = int(m.group(1)) if m else -n
            # the window holds the commits of heights w..2w-1 (embedded in
            # blocks w+1..2w) and the tip's stored commit, n lanes each
            blame = (w + lane // n, lane % n)
        out.append(C.equal("flipped_signature.blame_height_index", blame,
                           (h_bad, idx_bad)))
        out.append(C.equal("flipped_signature.blocks_applied", applied,
                           (0, 0)))
        # and one byte of one transaction of a stored block
        blk = store2.load_block(h_byte)
        tx = blk.data.txs[int(rng.integers(per))]
        raw = bytearray(kv2.get(_key_block(h_byte)))
        raw[bytes(raw).index(tx) + int(rng.integers(2, len(tx)))] ^= 0x01
        kv2.set(_key_block(h_byte), bytes(raw))
        raised, applied = self._refused(store2)
        out.append(C.equal("flipped_transaction_byte.refused_with",
                           type(raised).__name__, ErrInvalidBlockID.__name__))
        out.append(C.equal("flipped_transaction_byte.blocks_applied", applied,
                           (0, 0)))
        bad = store2.load_block(h_byte)
        out.append(C.equal(
            "flipped_transaction_byte.reference_data_hash_differs_from_"
            "headers_only_there",
            [ref.data_hash(b.data.txs) != b.header.data_hash
             for b in (bad, store2.load_block(
                 h_byte - 1 if h_byte > w + 1 else h_byte + 1))],
            [True, False]))
        self._retire_state_store()
        kv2.close()
        log(f"   side chain of {w} blocks (bad signature at height {h_bad} "
            f"index {idx_bad}; then a flipped transaction byte at height "
            f"{h_byte}) built and judged twice in "
            f"{time.perf_counter() - t0:.1f}s")
        return out
