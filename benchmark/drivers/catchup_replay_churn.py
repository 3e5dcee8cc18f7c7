"""Traffic kind `catchup_replay_churn`: `catchup_replay`'s one replayer over a
chain whose validator set changes every few heights.

Parameters (configuration shapes + the cell's traffic block): those of
`catchup_replay` (drivers/catchup_replay.py, whose driver this one extends:
the store written, settled, closed and opened anew; passes from a copy of the
state and app at height `window` to the tip; a new pass at the tip), and
  update_every       every so many heights a block carries validator updates
                     (`val:<hex pubkey>=<power>` transactions), by turns a
                     join (a key never seen before joins and the member
                     lowest in the order leaves with power 0) and a
                     re-powering
  repowered_members  members a re-powering gives a new power
  power_min/_max     every power (genesis, joiner, re-powering) is drawn
                     uniformly from this range
  spare_keys         keys held back for the members that join
The generator is the program's fixture kit (utils/factories.ValsetChurn under
make_chain), drawn from --seed; the kvstore app turns each transaction into a
validator update, in force two heights on. The schedule is fixed and the
seed draws only who and how much, so every seed's chain has the same number
of changes.

The engine ends a window wherever the set changes, so its own windows are the
program's business and are not pinned: the driver MARKS every `window`
applied heights (heights 2 x window, 3 x window, ... and the tip of each
pass), and the rate is blocks applied over the time between the first and
the last mark inside the run (stats.whole_window_rate over the marks);
`warmup_windows` marks belong to set-up. Set-up replays heights 1..window
aside on the forced per-lane ladder and then warms every bucket a window of
THIS chain falls in (a run of k heights under one set, cut at `window`, is
a batch of k + 1 commits; the reference's sets say how long the runs are),
each with a pubkey column the device has not seen and again with one it has,
so that nothing compiles in the run. The side chains of the check run
outside it and load what they need then.

`correct`, every limit 0 (the plain reference is reference/valset_replay.py:
it evolves the set itself from the genesis members and the blocks'
transactions, and judges one commit lane by lane; it knows no window):
  - at every mark the app hash equals the next header's and the program's
    state.validators.hash() equals both that header's validators_hash and
    the reference's own set for that height; at the tip (no next header) the
    generator's final state stands in, and last_height_validators_changed
    equals the reference's;
  - every completed pass verified at least the non-absent signatures of the
    commits it replayed across plus one tip, and, traced, exactly the lanes
    its own blocksync.window_fill spans report;
  - a seeded sample of 32 heights, every one next to a change of the set,
    judged by the reference against ITS set of that height: all accepted;
  - outside the run three side chains of one `window` each, continued from
    the state of height `window` and replayed batched: (a) one signature
    flipped in the commit of the first height of a re-powered set: refused
    with blame at that (height, index); (b) after a rotation, the commit of
    the first height of the new set signed by the old set: refused at that
    height; (c) a member raised to 6/10 of the set's whole power at height
    a, and at a + 2 that member and the 13.4% lowest in the order vote nil
    (over 2/3 of the old power, under 2/3 of the new): refused for power at
    a + 2. In each, no block of the refused window is applied and the
    reference refuses the same commit
    for the same reason.
"""

from __future__ import annotations

import copy
import json
import os
import re
import time

from benchmark.drivers import catchup_replay as base
from benchmark.harness import check as C
from benchmark.harness.env import log
from benchmark.reference import valset_replay as ref

CHAIN = base.CHAIN
SAMPLED_HEIGHTS = 32


def _fixtures():
    """The program's fixture kit, if it can make a chain whose set changes.
    A program that cannot (the tree before the PR that brought this cell)
    ends the run here, at once, with exit code 1 and no result line."""
    from cometbft_tpu.utils import factories as fx

    if not hasattr(fx, "ValsetChurn"):
        raise SystemExit(
            "FAIL: this program's utils/factories.make_chain cannot make a "
            "chain whose validator set changes (no ValsetChurn): the "
            "configuration catchup-1000v-churn cannot be run on it")
    return fx


def _spare(fx, p: dict, seed: int) -> list:
    """The keys held back for members that join, from --seed."""
    return fx.make_signers(p["spare_keys"], seed=seed + 7)


def make_store(path: str, p: dict, seed: int):
    """catchup_replay.make_store with the churn: (store as the generator
    leaves it, final state, genesis state, the handle to close)."""
    from cometbft_tpu.abci.kvstore import KVStoreApp
    from cometbft_tpu.storage import BlockStore, open_kv

    fx = _fixtures()
    n = p["validators"]
    spare = _spare(fx, p, seed)
    churn = fx.ValsetChurn(
        spare, seed=seed, every=p["update_every"],
        repowered=p["repowered_members"], power_lo=p["power_min"],
        power_hi=p["power_max"])
    kv = open_kv(path)
    store = BlockStore(kv)
    _, final, genesis, _ = fx.make_chain(
        p["blocks"], n_validators=n, chain_id=CHAIN,
        txs_per_block=p["txs_per_block"], app=KVStoreApp(),
        block_store=store, seed=seed, verify_last_commit=False,
        r_pool=fx.RPool(n, blocks_per_fill=10, seed=seed + 11),
        powers=churn.genesis_powers(n), extra_txs=churn,
        spare_signers=spare)
    log(f"   the generator wrote {churn.joins} joins and "
        f"{churn.repowerings} re-powerings into {p['blocks']} blocks")
    return store, final, genesis, kv


def build_store(path: str, p: dict, seed: int) -> None:
    """catchup_replay.build_store, and the generator's final state beside the
    store (`path`.final), as a stopped node's state store would hold it."""
    store, final, genesis, kv = make_store(path, p, seed)
    for ext, blob in ((".genesis", genesis.encode()),
                      (".apphash", final.app_hash),
                      (".final", final.encode())):
        with open(path + ext, "wb") as f:
            f.write(blob)
    base.settle_store(path)
    kv.close()


def slots_of(commit) -> list:
    """A decoded commit as the reference takes it: (flag, address, sign
    bytes, signature) a slot. The sign bytes are the program's canonical
    vote encoding, which the generator signed too."""
    return [(int(cs.block_id_flag), cs.validator_address,
             b"" if cs.is_absent() else commit.vote_sign_bytes(CHAIN, i),
             cs.signature)
            for i, cs in enumerate(commit.signatures)]


def members_of(vals) -> list:
    return [(v.pub_key.bytes(), v.voting_power) for v in vals.validators]


class Driver(base.Driver):
    def __init__(self, ctx):
        super().__init__(ctx)
        _fixtures()
        self.valhash_differs_from_header = 0
        self.valhash_differs_from_reference = 0
        self.valhash_checked = 0
        self.tip_changed: list[int] = []  # last_height_validators_changed
        self.pass_ns: list[tuple[int, int]] = []  # (t0, t1) of engine.run

    # -- marks -----------------------------------------------------------

    def _applied(self, state, height: int) -> None:
        if self.mode == "run" and height in self.boundary_hash:
            # state.validators judges height + 1; its hash is memoised by
            # the program's own validation of the block just applied
            got = state.validators.hash()
            self.valhash_checked += 1
            self.valhash_differs_from_header += got != self.mark_valhash[height]
            self.valhash_differs_from_reference += (
                got != self.ref_hash[height + 1])
            if height == self.p["blocks"]:
                self.tip_changed.append(state.last_height_validators_changed)
        super()._applied(state, height)

    def _pass(self) -> None:
        t0 = time.perf_counter_ns()
        super()._pass()
        self.pass_ns.append((t0, time.perf_counter_ns()))
        self.passes[-1]["sigs_expected"] = self.sigs_floor

    # -- phases ----------------------------------------------------------

    def setup(self) -> None:
        import gc

        from cometbft_tpu.abci.kvstore import KVStoreApp
        from cometbft_tpu.state.types import State

        p, seed = self.p, self.ctx.seed
        t0 = time.perf_counter()
        db = os.path.join(self.ctx.workdir, "blockstore.db")
        build_store(db, p, seed)
        self.ctx.objects_tracked("data built")
        t1 = time.perf_counter()
        freed = gc.collect()
        self.store, self.genesis, self.final_hash = base.open_store(db)
        with open(db + ".final", "rb") as f:
            final = State.decode(f.read())
        self.ctx.objects_tracked("store reopened")
        log(f"   generated {p['blocks']} blocks x {p['validators']} validators "
            f"into sqlite ({os.path.getsize(db) / 1e6:.1f} MB) in "
            f"{t1 - t0:.1f}s; closed, collected ({freed} unreachable objects) "
            f"and opened anew in {time.perf_counter() - t1:.2f}s")

        # the reference's own sets, from the genesis members and the
        # transactions the store's blocks carry
        t0 = time.perf_counter()
        tip, w = p["blocks"], p["window"]
        self.updates_at = {}
        # what a pass verifies at the least: the LastCommit of every block
        # it replays (heights w..tip-1, every non-absent signature) and the
        # COMMIT signatures of one tip
        flags = [int(cs.block_id_flag)
                 for cs in self.store.load_seen_commit(tip).signatures]
        self.sigs_floor = flags.count(ref.COMMIT)
        for h in range(1, tip + 1):
            blk = self.store.load_block(h)
            self.updates_at[h] = ref.val_updates(blk.data.txs)
            if h > w:
                self.sigs_floor += ref.signed(
                    int(cs.block_id_flag) for cs in blk.last_commit.signatures)
        g = members_of(self.genesis.validators)
        self.ref_sets = ref.evolve(1, g, g, self.updates_at, tip)
        marks = list(range(2 * w, tip, w)) + [tip]
        self.ref_hash = {h + 1: ref.set_hash(self.ref_sets[h + 1])
                         for h in marks}
        self.ref_changed = ref.last_changed(1, self.updates_at, tip)
        runs = [h for h in range(w + 1, tip + 1)
                if self.ref_sets[h] != self.ref_sets[h - 1]]
        log(f"   the reference evolved {tip + 2} sets in "
            f"{time.perf_counter() - t0:.1f}s: the set changes at "
            f"{len(runs)} of the heights {w + 1}-{tip} a pass replays; a "
            f"pass verifies at least {self.sigs_floor} signatures")

        # heights 1..window, on the forced per-lane ladder; every pass
        # starts from the app and state this leaves
        t0 = time.perf_counter()
        self.app_w = KVStoreApp()
        self.mode = "aside"
        with self.ctx.perlane_forced():
            self.state_w, _ = self._engine(self.store, self.app_w).run(
                self.genesis.copy(), to_height=w)
        self.mode = "run"
        self.boundary_hash = {
            h: self.store.load_block(h + 1).header.app_hash
            for h in marks[:-1]}
        self.boundary_hash[tip] = self.final_hash
        self.mark_valhash = {
            h: self.store.load_block(h + 1).header.validators_hash
            for h in marks[:-1]}
        self.mark_valhash[tip] = final.validators.hash()
        self.final_changed = final.last_height_validators_changed
        self.ctx.objects_tracked("first window replayed on the ladder")
        log(f"   replayed heights 1-{w} on the per-lane ladder in "
            f"{time.perf_counter() - t0:.1f}s")
        self._warm_buckets()

    def _warm_buckets(self) -> None:
        """Both device programs (the pubkey column's decompression and the
        ladder) at every bucket a window of this chain falls in: the commit
        of height `window` repeated to the largest batch of the bucket,
        first from its second lane on (a column the device has not seen:
        miss), then twice from its first (miss, hit)."""
        from cometbft_tpu.crypto import ed25519 as E

        p = self.p
        w, n, tip = p["window"], p["validators"], p["blocks"]
        # the engine's windows: runs of heights under one set, cut at
        # `window`; a window of k heights is a batch of k + 1 commits
        runs = [0]
        for h in range(w + 1, tip + 1):
            if runs[-1] == w or (
                    runs[-1] and self.ref_sets[h] != self.ref_sets[h - 1]):
                runs.append(0)
            runs[-1] += 1
        fill = {}
        for k in runs:
            b = E._bucket((k + 1) * n)
            fill[b] = max(fill.get(b, 0), (k + 1) * n)
        commit = self.store.load_seen_commit(w)
        lanes = C.commit_lanes(CHAIN, self.state_w.last_validators, commit)
        t0 = time.perf_counter()
        for b in sorted(fill):
            for shift in (1, 0, 0):
                ok, bits = C.program_bitmap(
                    [lanes[(i + shift) % n] for i in range(fill[b])],
                    force_perlane=True)
                if not ok or len(bits) != fill[b]:
                    raise SystemExit(f"FAIL: the warm-up batch of {fill[b]} "
                                     f"honest lanes was refused")
        log(f"   warmed (bucket, lanes) {sorted(fill.items())}, column unseen "
            f"and seen, in {time.perf_counter() - t0:.1f}s")

    # -- checks ----------------------------------------------------------

    def _fill_lanes_by_pass(self):
        """Lanes the program's own blocksync.window_fill spans report, one
        sum a completed pass (None untraced)."""
        from cometbft_tpu.utils import trace

        if not self.ctx.trace_path:
            return None
        trace.flush()
        sums = [0] * len(self.passes)
        with open(self.ctx.trace_path, encoding="utf-8") as f:
            for line in f:
                r = json.loads(line)
                if r.get("name") != "blocksync.window_fill":
                    continue
                for i, (a, b) in enumerate(self.pass_ns[:len(sums)]):
                    if a <= r["t0_ns"] <= b:
                        sums[i] += int(r["lanes"])
        return sums

    def _side_chain(self, tag: str, spare: list, extra_txs, **kw):
        """A chain of one window continued from the state of height
        `window`, replayed batched: (what the engine raised, blocks it
        applied, the side chain's store)."""
        from cometbft_tpu.storage import BlockStore, open_kv
        from cometbft_tpu.types.validation import CommitError

        fx = _fixtures()
        p, seed = self.p, self.ctx.seed
        w, n = p["window"], p["validators"]
        store = BlockStore(open_kv(
            os.path.join(self.ctx.workdir, f"blockstore_{tag}.db")))
        fx.make_chain(
            w, n_validators=n, chain_id=CHAIN,
            txs_per_block=p["txs_per_block"], app=copy.deepcopy(self.app_w),
            block_store=store, seed=seed, verify_last_commit=False,
            r_pool=fx.RPool(n, blocks_per_fill=10, seed=seed + 12),
            start_state=self.state_w.copy(),
            start_commit=self.store.load_block_commit(w), start_height=w + 1,
            extra_txs=extra_txs, spare_signers=spare, **kw)
        app = copy.deepcopy(self.app_w)
        self.mode, self.applied_aside = "aside", 0
        try:
            self._engine(store, app).run(self.state_w.copy())
            raised = None
        except CommitError as e:
            raised = e
        finally:
            self.mode = "run"
        return raised, (self.applied_aside, app.height - w), store

    def _side_sets(self, store) -> dict:
        """The reference's sets along a side chain."""
        w = self.p["window"]
        ups = {h: ref.val_updates(store.load_block(h).data.txs)
               for h in range(w + 1, 2 * w + 1)}
        return ref.evolve(w + 1, self.ref_sets[w + 1], self.ref_sets[w + 2],
                          ups, 2 * w)

    def verify(self) -> list:
        import numpy as np

        from cometbft_tpu.types.validation import (
            ErrInvalidSignature, ErrNotEnoughVotingPower)

        p, seed = self.p, self.ctx.seed
        w, n, tip = p["window"], p["validators"], p["blocks"]
        fx = _fixtures()
        out = [C.equal("replay_error", self.error, None),
               C.at_least("marks_in_run", self._rate()[1], 1),
               C.at_least("marks_app_hash_checked", self.hash_checked, 1),
               C.equal("marks_app_hash_differs", self.hash_mismatch, 0),
               C.equal("marks_valset_hash_checked_less_app_hash_checked",
                       self.valhash_checked - self.hash_checked, 0),
               C.equal("marks_valset_hash_differs_from_next_header",
                       self.valhash_differs_from_header, 0),
               C.equal("marks_valset_hash_differs_from_reference",
                       self.valhash_differs_from_reference, 0),
               C.equal("generator_last_height_validators_changed_less_"
                       "references", self.final_changed - self.ref_changed, 0),
               C.equal(f"tips_of_{len(self.tip_changed)}_whose_last_height_"
                       f"validators_changed_is_not_{self.ref_changed}",
                       sum(c != self.ref_changed for c in self.tip_changed), 0)]
        k = len(self.passes)
        out.append(C.equal(
            f"completed_passes_of_{k}_whose_app_hash_is_not_the_generators",
            sum(not ps["app_hash_ok"] for ps in self.passes), 0))
        out.append(C.equal(
            f"completed_passes_of_{k}_whose_sigs_verified_is_under_"
            f"{self.sigs_floor}",
            sum(ps["sigs_verified"] < self.sigs_floor for ps in self.passes),
            0))
        fills = self._fill_lanes_by_pass()
        if fills is not None:
            out.append(C.equal(
                f"completed_passes_of_{k}_whose_sigs_verified_is_not_its_"
                f"window_fill_lanes",
                sum(ps["sigs_verified"] != f
                    for ps, f in zip(self.passes, fills)), 0))
        if self.passes:
            log(f"   a pass verified {self.passes[0]['sigs_verified']} "
                f"signatures for {tip - w} blocks of {n}: "
                f"{self.passes[0]['sigs_verified'] / ((tip - w) * n):.3f} a "
                f"signature in the chain")

        # a seeded sample of heights next to a change of the set: the commit
        # of each, as stored in the next block, against the reference's set
        t0 = time.perf_counter()
        rng = np.random.default_rng([seed, 5])
        near = [h for h in range(w + 1, tip)
                if self.ref_sets[h] != self.ref_sets[h - 1]
                or self.ref_sets[h + 1] != self.ref_sets[h]]
        want = min(SAMPLED_HEIGHTS, len(near))
        sample = sorted(rng.choice(near, size=want, replace=False).tolist())
        refused = [
            (h, v) for h in sample
            if (v := ref.judge(
                self.ref_sets[h],
                slots_of(self.store.load_block(h + 1).last_commit)))
            != ("accepted",)]
        out.append(C.at_least("sampled_heights_next_to_a_set_change",
                              len(sample),
                              min(SAMPLED_HEIGHTS, max(len(near), 1))))
        out.append(C.equal(
            f"sampled_commits_of_{len(sample)}_the_reference_refuses",
            refused, []))
        log(f"   the reference judged the commits of {len(sample)} heights "
            f"next to a set change in {time.perf_counter() - t0:.1f}s")

        # three side chains of one window, continued from height `window`
        spare = _spare(fx, p, seed)
        seen = {pub for s in self.ref_sets.values() for pub, _ in s}
        joiner = next(s for s in spare if s.pub_bytes not in seen)
        # the change is carried by block a and in force at a + 2, whose
        # commit is embedded in block a + 3 <= 2 x window
        a = int(rng.integers(w + 1, 2 * w - 2))
        idx_bad = int(rng.integers(n))
        top = p["power_max"]

        def at(height, make):
            return lambda h, state: make(state) if h == height else []

        def mid(state):  # a member in the middle of the order
            return state.next_validators.validators[n // 2]

        # (a) a re-powering at a: the set of a + 2 is new, and its first
        # commit carries one flipped signature
        t0 = time.perf_counter()
        raised, applied, store = self._side_chain(
            "a", spare, at(a, lambda st: [fx.val_tx(
                mid(st).pub_key.bytes(), mid(st).voting_power + top)]),
            corrupt_sig=(a + 2, idx_bad))
        got = type(raised).__name__
        if isinstance(raised, ErrInvalidSignature):
            m = re.search(r"lane (\d+)", str(raised))
            lane = int(m.group(1)) if m else -n
            # the refused window starts at a + 2: block a + 2's LastCommit
            # (height a + 1) first, then one commit a height, n lanes each
            got = (a + 1 + lane // n, lane % n)
        out.append(C.equal("flipped_signature_after_set_change.blame_height_"
                           "index", got, (a + 2, idx_bad)))
        out.append(C.equal("flipped_signature_after_set_change.blocks_applied",
                           applied, (a + 1 - w, a + 1 - w)))
        sets = self._side_sets(store)
        out.append(C.equal(
            "flipped_signature_after_set_change.reference",
            (sets[a + 2] != sets[a + 1],
             ref.judge(sets[a + 2],
                       slots_of(store.load_block(a + 3).last_commit))),
            (True, ("signature", idx_bad))))

        # (b) a rotation at a: the first commit of the new set is signed by
        # the old one
        raised, applied, store = self._side_chain(
            "b", spare, at(a, lambda st: [
                fx.val_tx(st.next_validators.validators[-1].pub_key.bytes(), 0),
                fx.val_tx(joiner.pub_bytes, top)]),
            stale_set_at=a + 2)
        m = re.search(r"height (\d+)", str(raised))
        out.append(C.equal(
            "old_set_signs_after_rotation.refused_at_height",
            (type(raised).__name__, int(m.group(1)) if m else None),
            ("ErrInvalidSignature", a + 2)))
        out.append(C.equal("old_set_signs_after_rotation.blocks_applied",
                           applied, (a + 1 - w, a + 1 - w)))
        sets = self._side_sets(store)
        verdict = ref.judge(sets[a + 2],
                            slots_of(store.load_block(a + 3).last_commit))
        out.append(C.equal(
            "old_set_signs_after_rotation.reference",
            ({pub for pub, _ in sets[a + 2]} != {pub for pub, _ in sets[a + 1]},
             verdict[0]), (True, "address")))

        # (c) one member raised to 6/10 of the set's whole power at a; at
        # a + 2 it is first in the order, and it and the 13.4% lowest in the
        # order vote nil
        nil = {0} | set(range(n - 134 * n // 1000, n))
        raised, applied, store = self._side_chain(
            "c", spare, at(a, lambda st: [fx.val_tx(
                mid(st).pub_key.bytes(),
                6 * st.next_validators.total_voting_power() // 10)]),
            nil_votes={a + 2: nil})
        m = re.search(r"height (\d+)", str(raised))
        out.append(C.equal(
            "nil_votes_under_new_powers.refused_at_height",
            (type(raised).__name__, int(m.group(1)) if m else None),
            ("ErrNotEnoughVotingPower", a + 2)))
        out.append(C.equal("nil_votes_under_new_powers.blocks_applied",
                           applied, (a + 1 - w, a + 1 - w)))
        sets = self._side_sets(store)
        slots = slots_of(store.load_block(a + 3).last_commit)
        old = dict(sets[a + 1])
        by_old = sum(old[pub] for (pub, _), s in zip(sets[a + 2], slots)
                     if s[0] == ref.COMMIT)
        out.append(C.equal(
            "nil_votes_under_new_powers.reference",
            (ref.judge(sets[a + 2], slots)[0],
             by_old * 3 > 2 * sum(old.values())), ("power", True)))
        log(f"   three side chains of {w} blocks (set change at height "
            f"{a + 2}) built and judged in {time.perf_counter() - t0:.1f}s")
        return out
