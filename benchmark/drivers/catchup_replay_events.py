"""Traffic kind `catchup_replay_events`: `catchup_replay_indexed`'s indexing
replayer on a chain whose application answers as upstream's kvstore does:
two `app` events of four attributes a transaction.

Parameters (configuration shapes + the cell's traffic block): those of
`catchup_replay_indexed` (drivers/catchup_replay_indexed.py, whose driver
this one extends as that one extends `catchup_replay_loaded`: the loaded
block store, the state store and both indexes on files, the aside replay of
the first window, passes on copies of height `window`'s files, a pass's last
window closing when the index holds the tip), and
  app_events         "upstream": the replayer's application is
                     KVStoreApp(events=True)

The block store is the siblings' to the byte: loaded.build_store writes it
with the application that emits nothing, and events change no header. What
replays it emits: the parents build every application as `KVStoreApp()` and
hand each to `_engine`, so `_engine` is where this driver turns the
argument's attribute on (`app.events = True`), on the aside replay's
application, on every pass's copy of it and on the side chains'. From there
the events take the node's path: the executor encodes them into the stored
FinalizeBlockResponse (abci/wire.py), the bus hands them to the indexer
service, TxIndexer.add_batch writes a key an attribute marked for indexing
and keeps the marked attributes in the record.

The driver's first act is to ask the program for abci.types.Event and
KVStoreApp(events=True). A program without them (the tree before the PR that
brought this cell) ends the run there, at once, with exit code 1 and no
result line.

`correct`, every limit 0: the siblings' checks unchanged, and against the
two plain references (reference/kvstore_events.py: the events of a
transaction from its bytes; reference/tx_index.py fed those events), on the
files of the last completed pass, settled, closed and opened from a new
connection (`event_checks`):
  - every attribute key of the reference is in the index and points at the
    reference's hash (all of them), the count of keys under each of
    app.creator/, app.key/, app.index_key/ equals the reference's, and
    there is no key under app.noindex_key/ or another attribute's name;
  - `app.key = '<value>'` for 8 seeded transactions finds exactly that
    transaction; `app.key = 'a' AND tx.height = h` at 8 seeded heights
    gives block h's hashes in block order; 8 seeded values no transaction
    carried find nothing;
  - every record's stored attributes equal the reference's indexed
    attributes of that transaction (all records);
  - at 8 seeded heights load_abci_responses decodes to txs_per_block
    results whose events equal kvstore_events.events(tx) attribute for
    attribute, flags included, and whose root over the deterministic
    fields is the reference's and the next header's last_results_hash;
and of the run itself:
  - the generator's application emits nothing, and at every height of the
    last completed pass the state the eventful replay reached carries the
    stored block's id, and the last_results_hash and app hash the NEXT
    stored header carries (the tip's app hash: the generator's final one);
  - traced: over each completed pass the `attr_keys` of its index.block
    spans sum to 5 x txs_per_block x its blocks and the `events` of its
    state.apply_block spans to 2 x txs_per_block x its blocks.
"""

from __future__ import annotations

import os
import time

from benchmark.drivers import catchup_replay as base
from benchmark.drivers import catchup_replay_indexed as indexed
from benchmark.harness import check as C
from benchmark.harness.env import log
from benchmark.reference import kvstore_events as ref_events
from benchmark.reference import kvstore_replay as ref_replay
from benchmark.reference import tx_index as ref

SAMPLED = indexed.SAMPLED
PREFIXES = ("app.creator/", "app.key/", "app.index_key/")
# attribute keys and events a transaction: kvstore_events.events(tx) has two
# events whose six marked attributes make five distinct keys
KEYS_PER_TX, EVENTS_PER_TX = 5, 2


def _program():
    """What the deployment needs of the program beyond its sibling's:
    abci.types.Event and an application that emits upstream's events when
    asked to. A program without them ends the run here, at once, with exit
    code 1 and no result line."""
    from cometbft_tpu.abci import types as T
    from cometbft_tpu.abci.kvstore import KVStoreApp

    try:
        if not hasattr(T, "Event"):
            raise TypeError("abci/types.py has no Event")
        KVStoreApp(events=True)
    except TypeError as e:
        raise SystemExit(
            f"FAIL: this program's kvstore application emits no events "
            f"({e}): the configuration catchup-1000v-1ktx-kvevents cannot "
            f"be run on it")
    return KVStoreApp


def reference_index(blocks) -> ref.Index:
    """reference/tx_index.py fed reference/kvstore_events.py: `blocks`
    yields (height, transactions) from height 1."""
    want = ref.Index()
    for h, txs in blocks:
        want.block(h, txs, [ref_events.events(tx) for tx in txs])
    return want


def _grouped(pairs) -> dict:
    out: dict = {}
    for composite, value in pairs:
        out.setdefault(composite, []).append(value)
    return out


def event_checks(index_dir: str, state_path: str, want: ref.Index,
                 carried: dict, first: int, tip: int, seed: int,
                 sampled: int = SAMPLED) -> list:
    """The index files under `index_dir` and the state store at
    `state_path`, each through a connection of its own, against the
    reference's index of heights 1..tip and its events. `carried[h]` is
    the last_results_hash the stored header of height h carries; the state
    store holds the responses of heights first..tip."""
    import numpy as np

    from cometbft_tpu.abci import wire
    from cometbft_tpu.state.execution import results_hash
    from cometbft_tpu.storage import StateStore, indexer, open_kv

    if not (os.path.exists(os.path.join(index_dir, indexer.TX_INDEX_FILE))
            and os.path.exists(state_path)):
        return [C.equal("events.read_back_of_a_completed_pass", None,
                        "found")]
    out = []
    rng = np.random.default_rng([seed, 9])
    txi, _, dbs = indexer.open_indexers(index_dir)
    try:
        # every key the application's events wrote (their type is `app`)
        held = {k.decode(): v for k, v in dbs[0].iterate_prefix(b"app.")}
        theirs = {k: v for k, v in want.keys.items()
                  if not k.startswith(indexer.TX_HEIGHT)}
        out.append(C.equal(
            f"events.attribute_keys_of_{len(theirs)}_missing_or_pointing_"
            f"at_another_hash",
            sum(held.get(k) != v for k, v in theirs.items()), 0))
        out.append(C.equal(
            "events.attribute_keys_held_under_creator_key_index_key",
            [sum(k.startswith(p) for k in held) for p in PREFIXES],
            [sum(k.startswith(p) for k in theirs) for p in PREFIXES]))
        out.append(C.equal(
            "events.attribute_keys_held_under_noindex_key_or_another",
            sum(not k.startswith(PREFIXES) for k in held), 0))
        del held, theirs

        hashes = list(want.records)
        picked = [hashes[int(i)] for i in rng.choice(
            len(hashes), size=min(sampled, len(hashes)), replace=False)]
        wrong = 0
        for h in picked:
            value = want.records[h][2].partition(b"=")[2].decode()
            got = txi.search(f"app.key = '{value}'", limit=4)
            wrong += [ref.tx_hash(r["tx"]) for r in got] != want.find(
                "app.key", value)
        out.append(C.equal(
            f"events.values_of_{len(picked)}_whose_app_key_search_is_not_"
            f"that_one_transaction", wrong, 0))
        heights = sorted(rng.choice(
            np.arange(1, tip + 1), size=min(sampled, tip),
            replace=False).tolist())
        wrong = []
        for h in heights:
            want_h = [x for x in want.by_height[h]
                      if want.records[x][2].startswith(b"a=")]
            got = txi.search(f"app.key = 'a' AND tx.height = {h}",
                             limit=len(want_h) + 1)
            if [ref.tx_hash(r["tx"]) for r in got] != want_h:
                wrong.append(h)
        out.append(C.equal(
            f"events.heights_of_{len(heights)}_whose_app_key_and_height_"
            f"search_is_not_the_references_hashes_in_order", wrong, []))
        absent = [rng.bytes(24).hex() for _ in range(sampled)]
        out.append(C.equal(
            f"events.values_of_{sampled}_no_transaction_carried_found",
            sum(bool(want.find("app.key", v))
                or bool(txi.search(f"app.key = '{v}'", limit=1))
                for v in absent), 0))

        differs = 0
        for h, (_, _, tx, code, _) in want.records.items():
            rec = txi.get(h)
            differs += (rec is None or rec["events"]
                        != _grouped(() if code else ref_events.indexed(tx)))
        out.append(C.equal(
            f"events.records_of_{len(hashes)}_whose_stored_attributes_are_"
            f"not_the_references", differs, 0))
    finally:
        for db in dbs:
            db.close()

    sample = sorted(rng.choice(
        np.arange(first, tip + 1), size=min(sampled, tip - first + 1),
        replace=False).tolist())
    events_differ, roots_differ = [], []
    skv = open_kv(state_path)
    try:
        ss = StateStore(skv)
        for h in sample:
            raw = ss.load_abci_responses(h)
            resp = wire.dec_finalize_resp(raw) if raw else None
            emitted = [[] if code else ref_events.events(tx)
                       for _, _, tx, code, _ in
                       (want.records[x] for x in want.by_height[h])]
            if (resp is None or resp.events
                    or [tr.events for tr in resp.tx_results] != emitted):
                events_differ.append(h)
                continue
            root = ref_replay.merkle_root([
                ref_replay.result_bytes(*want.records[x][3:5])
                for x in want.by_height[h]])
            if not results_hash(resp.tx_results) == root == carried.get(
                    h + 1, root):
                roots_differ.append(h)
    finally:
        skv.close()
    out.append(C.equal(
        f"events.heights_of_{len(sample)}_whose_stored_events_are_not_the_"
        f"references", events_differ, []))
    out.append(C.equal(
        f"events.heights_of_{len(sample)}_whose_results_root_is_not_the_"
        f"references_and_the_next_headers", roots_differ, []))
    return out


class Driver(indexed.Driver):
    def __init__(self, ctx):
        self.KVStoreApp = _program()
        if ctx.cell.params["app_events"] != "upstream":
            raise SystemExit(
                f"FAIL: this driver runs upstream's kvstore events, the "
                f"configuration says {ctx.cell.params['app_events']!r}")
        super().__init__(ctx)
        # what the state the replay reached says of each height of the pass
        # under way: (block id, last_results_hash, app hash)
        self.reached: dict[int, tuple] = {}
        self.reached_done: dict[int, tuple] = {}

    def _engine(self, store, app):
        app.events = True  # the parents' `KVStoreApp()`, made to emit
        return super()._engine(store, app)

    def _applied(self, state, height: int) -> None:
        if self.mode == "run":
            self.reached[height] = (state.last_block_id.hash,
                                    state.last_results_hash, state.app_hash)
        super()._applied(state, height)

    def _pass(self) -> None:
        self.reached = {}
        super()._pass()  # an interrupted pass leaves here by _Stop
        self.reached_done = self.reached

    def _header_checks(self, stored: dict) -> list:
        """`stored[h]`: (block id, last_results_hash, app hash) of the
        stored block of height h."""
        w, tip = self.p["window"], self.p["blocks"]
        wrong = []
        for h in range(w + 1, tip + 1):
            block_id, results, app_hash = self.reached_done.get(h, 3 * (None,))
            nxt = stored.get(h + 1, (None, results, self.final_hash))
            if (block_id, results, app_hash) != (stored[h][0], *nxt[1:]):
                wrong.append(h)
        return [
            C.equal("events.generators_application_emits",
                    self.KVStoreApp().events, False),
            C.equal(
                f"events.heights_of_{tip - w}_at_which_the_replays_state_"
                f"is_not_the_stored_chains", wrong, [])]

    def _span_checks(self) -> list:
        per = self.p["txs_per_block"]
        blocks = self.p["blocks"] - self.p["window"]
        k = len(self.pass_ns)
        sums = [[0, 0] for _ in range(k)]
        for r in self._spans({"index.block", "state.apply_block"}):
            col, field = ((0, "attr_keys") if r["name"] == "index.block"
                          else (1, "events"))
            for i, (a, b) in enumerate(self.pass_ns):
                if a <= r["t0_ns"] <= b:
                    sums[i][col] += int(r.get(field, 0))
        want = [KEYS_PER_TX * per * blocks, EVENTS_PER_TX * per * blocks]
        return [C.equal(
            f"events.completed_passes_of_{k}_whose_spans_attr_keys_and_"
            f"events_are_not_{want[0]}_and_{want[1]}",
            sum(s != want for s in sums), 0)]

    def verify(self) -> list:
        seed = self.ctx.seed
        w, tip = self.p["window"], self.p["blocks"]
        out = super().verify()  # retires the cut pass; settles the index
        t0 = time.perf_counter()
        stored = {}

        def blocks():
            for h in range(1, tip + 1):
                blk = self.store.load_block(h)
                stored[h] = (blk.hash(), blk.header.last_results_hash,
                             blk.header.app_hash)
                yield h, blk.data.txs

        want = reference_index(blocks())
        out += self._header_checks(stored)
        if self.ctx.trace_path:
            out += self._span_checks()
        if os.path.exists(self.state_done_path):
            base.settle_store(self.state_done_path)
        out += event_checks(
            self.index_done_dir, self.state_done_path, want,
            {h: results for h, (_, results, _) in stored.items()},
            w + 1, tip, seed)
        attr = sum(not k.startswith("tx.height") for k in want.keys)
        log(f"   the references gave {tip} heights' events ({attr} "
            f"attribute keys) and the last completed pass's index and "
            f"state store were held to them in "
            f"{time.perf_counter() - t0:.1f}s; that pass's "
            f"{self.indexer.TX_INDEX_FILE}: {self._file_use()}")
        return out

    def _file_use(self) -> str:
        """The tx index file of the last completed pass: its size, and the
        share of its pages' bytes that hold nothing (sqlite's dbstat, where
        the build has it)."""
        import sqlite3

        path = os.path.join(self.index_done_dir, self.indexer.TX_INDEX_FILE)
        if not os.path.exists(path):
            return "no pass completed"
        size = f"{os.path.getsize(path) / 1e6:.1f} MB"
        conn = sqlite3.connect(path)
        try:
            unused, total = conn.execute(
                "SELECT sum(unused), sum(pgsize) FROM dbstat").fetchone()
            return f"{size}, {100 * unused / total:.1f}% of its pages unused"
        except sqlite3.OperationalError:
            return f"{size} (this sqlite has no dbstat)"
        finally:
            conn.close()
