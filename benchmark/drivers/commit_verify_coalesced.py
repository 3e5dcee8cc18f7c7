"""Traffic kind `commit_verify_coalesced`: several chains validated by ONE
validator set on one host, each chain's node a caller thread of its own, all
of them through the process-wide VerifyScheduler.

Each caller does what a node does (node/node.py, consensus/state.py,
blocksync/reactor.py): it acquires the shared scheduler with
crypto.sched.acquire_shared under config.SchedConfig's defaults, and calls
types/validation.verify_commit inside verify_context(sched, tenant=<its chain
id>, source="consensus"). The scheduler's drainer thread merges what is queued
into one batch, dispatches it, and hands each request its slice of the bitmap.

Parameters (configuration shapes + the cell's traffic block):
  validators        signers of every commit (two of them hold non-canonical
                    ZIP-215 keys); one set, shared by all chains
  chains, chain_ids one caller thread a chain; the chain id is in the sign
                    bytes, so no two chains' commits are alike
  scheduler         the configuration's statement of the scheduler's settings;
                    set-up refuses to run where config.SchedConfig() differs
  commits           K distinct commits a chain (heights 1..K), round-robin
  warmup_seconds    the closed loop run in set-up, through the live scheduler
  profile_calls     timed calls a caller in the traced stretch
  caller_grace_s    a caller that has not returned this long after its phase
                    should have ended fails the run (it never waits longer)
  mixed_round_tries how often the mixed round (below) may be repeated until
                    bad and honest requests rode in one dispatch
  coalesced_device_from_lanes
                    every batch of more lanes than this took a device path
                    (null off the chip, where the dispatch keeps every batch
                    on the host engine); the harness's own device_from_lanes
                    stays null, because a lone request (150 lanes) rightly
                    takes the host engine
  device_from_lanes null: see above

Closed loop, no think time: a caller's next call starts when its last one
returned; every call gets a fresh Commit.decode made OUTSIDE the timed call.
All callers are released together when the window opens. The end-to-end
numbers are over the calls of all callers pooled.

Callers are daemon threads and the driver never waits on one without a limit.
"""

from __future__ import annotations

import copy
import json
import os
import threading
import time

from benchmark.harness import check as C
from benchmark.harness.env import DEVICE_PATHS, dispatch_counts, log
from benchmark.reference import commit_alone

SOURCE = "consensus"
# a phase bounded by calls, not seconds, should be over well inside this
CALLS_PHASE_S = 30.0


class Driver:
    def __init__(self, ctx):
        self.ctx = ctx
        self.p = ctx.cell.params
        self.chains = list(self.p["chain_ids"])[: self.p["chains"]]
        if len(set(self.chains)) != self.p["chains"]:
            raise SystemExit("FAIL: the configuration must name one distinct "
                             "chain id a chain")
        self.grace = float(self.p["caller_grace_s"])
        self.calls: list[list[tuple[float, str | None]]] = []
        self.failed = 0
        self.t0 = self.t1 = 0.0
        self.profile_units = 0
        self.scheds: list = []
        self.next_k = [0] * len(self.chains)

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
        # bids[c][h-1], encoded[c][h-1]: chain c's commit of height h
        self.bids, self.encoded = [], []
        for c, chain in enumerate(self.chains):
            nonces = fx.RPool(n, blocks_per_fill=k, seed=seed * 1000 + c)
            bids, encoded = [], []
            for h in range(1, k + 1):
                bid = fx.make_block_id(b"ics-%d-%d-%d" % (seed, c, h))
                commit = fx.make_commit(chain, h, 0, bid, self.vals, by_addr,
                                        r_pool=nonces)
                bids.append(bid)
                encoded.append(commit.encode())
            self.bids.append(bids)
            self.encoded.append(encoded)
        self.decode = Commit.decode
        self.ctx.objects_tracked("data built")
        log(f"   built {n} validators (non-canonical keys at {self.weird}), "
            f"{len(self.chains)} chains and {k} commits a chain of "
            f"{len(self.encoded[0][0])} bytes in "
            f"{time.perf_counter() - t0:.1f}s")

    def _acquire(self):
        """One acquire a chain, as one Node a chain would make it."""
        from cometbft_tpu.config import SchedConfig
        from cometbft_tpu.crypto.sched import acquire_shared

        cfg = SchedConfig()
        stated = self.p["scheduler"]
        ours = {"max_coalesce_sigs": cfg.max_coalesce_sigs,
                "max_coalesce_delay_ms": cfg.max_coalesce_delay_ms,
                "tenant_weight": cfg.tenant_weight}
        differ = {key: (stated.get(key), v) for key, v in ours.items()
                  if stated.get(key) != v}
        if differ or not cfg.enabled:
            raise SystemExit(f"FAIL: the configuration states scheduler "
                             f"settings the program does not default to "
                             f"(stated, default): {differ}; enabled "
                             f"{cfg.enabled}")
        for chain in self.chains:
            sched = acquire_shared(
                stated["backend"],
                max_coalesce_sigs=cfg.max_coalesce_sigs,
                max_coalesce_delay_ms=cfg.max_coalesce_delay_ms,
                stop_timeout_s=cfg.stop_timeout_s)
            sched.set_tenant_weight(chain, cfg.tenant_weight)
            self.scheds.append(sched)
        self.sched = self.scheds[0]
        log(f"   {len(self.scheds)} chains acquired the shared scheduler "
            f"({json.dumps(ours)}): "
            f"{'one object' if all(s is self.sched for s in self.scheds) else 'SEVERAL OBJECTS'}")

    # -- callers -------------------------------------------------------

    def _call(self, c: int, k: int, commit) -> tuple[float, str | None]:
        """One timed verify_commit of chain c's commit k, inside the caller's
        verify_context: (seconds, None) or (seconds, the refusal)."""
        from cometbft_tpu.types import validation

        t0 = time.perf_counter()
        try:
            validation.verify_commit(self.chains[c], self.vals,
                                     self.bids[c][k], k + 1, commit)
        except validation.CommitError as e:
            return time.perf_counter() - t0, str(e)
        return time.perf_counter() - t0, None

    def _drive(self, seconds=None, calls=None, on_open=None, special=None):
        """Every chain's caller at once, each a closed loop over its own
        chain's commits: for `seconds` from the instant all are released, or
        `calls` calls each. `special` {chain index: commit} replaces a
        caller's first commit. Returns (calls of each chain, t released,
        t all returned). A caller still out `caller_grace_s` after the phase
        should have ended fails the run: nothing here waits without a limit."""
        from cometbft_tpu.crypto.sched import verify_context

        n = len(self.chains)
        ready = threading.Barrier(n + 1)
        go = threading.Event()
        out: list[list] = [[] for _ in range(n)]
        errors: list = []
        deadline = [0.0]

        def caller(c: int) -> None:
            try:
                with verify_context(self.scheds[c], self.chains[c], SOURCE):
                    ready.wait(timeout=self.grace)
                    if not go.wait(timeout=self.grace):
                        raise TimeoutError("never released")
                    k = self.next_k[c]
                    while (len(out[c]) < calls if calls is not None
                           else time.perf_counter() < deadline[0]):
                        kk = k % len(self.encoded[c])
                        commit = special.pop(c, None) if special else None
                        if commit is None:  # not timed
                            commit = self.decode(self.encoded[c][kk])
                        out[c].append(self._call(c, kk, commit))
                        k += 1
                    self.next_k[c] = k
            except Exception as e:  # noqa: BLE001 - told to the run
                errors.append(f"{self.chains[c]}: {e!r}")

        threads = [threading.Thread(target=caller, args=(c,), daemon=True,
                                    name=f"caller-{self.chains[c]}")
                   for c in range(n)]
        for t in threads:
            t.start()
        try:
            ready.wait(timeout=self.grace)
        except threading.BrokenBarrierError:
            raise SystemExit(f"FAIL: callers did not reach the line in "
                             f"{self.grace:g}s: {errors}") from None
        t_open = time.perf_counter()
        deadline[0] = t_open + (seconds or 0.0)
        if on_open is not None:
            on_open(t_open)
        go.set()
        t_limit = (t_open + (seconds if seconds is not None else CALLS_PHASE_S)
                   + self.grace)
        for t in threads:
            t.join(timeout=max(0.0, t_limit - time.perf_counter()))
        t_close = time.perf_counter()
        hung = [t.name for t in threads if t.is_alive()]
        if hung:
            raise SystemExit(
                f"FAIL: {len(hung)} caller(s) had not returned "
                f"{self.grace:g}s after their phase should have ended "
                f"(a handle that never resolved?): {hung}")
        if errors:
            raise SystemExit(f"FAIL: a caller raised: {errors}")
        return out, t_open, t_close

    # -- phases --------------------------------------------------------

    def setup(self) -> None:
        self._build()
        self._acquire()
        t0 = time.perf_counter()
        # every shape the window can use, on the dispatch's own choice: the
        # host engine at one commit's lanes, and the ladder at the bucket
        # that 7 and 16 commits' lanes fall in (the first call traces, lowers
        # and compiles or loads both device programs)
        snap = self.ctx.counters.snap()
        for k in self.p["warmup_batches_of"]:
            lanes = []
            for c in range(k):
                commit = self.decode(self.encoded[c % len(self.chains)][0])
                lanes += C.commit_lanes(self.chains[c % len(self.chains)],
                                        self.vals, commit)
            t1 = time.perf_counter()
            ok, _ = C.program_bitmap(lanes)
            if not ok:
                raise SystemExit(f"FAIL: the warm-up batch of {k} honest "
                                 f"commits was refused")
            log(f"   warm-up batch of {k} commits, {len(lanes)} lanes: "
                f"{time.perf_counter() - t1:.2f}s")
        moved = self.ctx.counters.delta(snap, self.ctx.counters.snap())
        log("   warm-up batches took: " + json.dumps(
            {"/".join(k): v for k, v in
             sorted(moved["path_selected_total"].items())}))
        # the closed loop itself, through the live scheduler: the drainer
        # thread, the callers' entries, the tracer
        calls, t_open, t_close = self._drive(seconds=self.p["warmup_seconds"])
        refused = [r for chain in calls for _, r in chain if r is not None]
        if refused:
            raise SystemExit(f"FAIL: a warm-up call refused an honest "
                             f"commit: {refused[:3]}")
        self.ctx.objects_tracked("warmed up")
        log(f"   warmed up in {time.perf_counter() - t0:.1f}s; the live loop "
            f"made {sum(len(c) for c in calls)} calls in "
            f"{t_close - t_open:.2f}s; scheduler so far "
            f"{json.dumps(self.sched.stats)}")

    def window(self, seconds: float) -> None:
        def opens(t: float) -> None:
            self.tenants_open = self.sched.tenant_stats()
            self.stats_open = dict(self.sched.stats)
            self.big_open = batches_over_1024()
            self.ctx.window_opens(t)

        self.calls, self.t0, self.t1 = self._drive(seconds=seconds,
                                                   on_open=opens)
        self.snap_close = self.ctx.counters.snap()
        self.big_close = batches_over_1024()
        self.tenants_close = self.sched.tenant_stats()
        self.stats_close = dict(self.sched.stats)
        self.failed = sum(r is not None for chain in self.calls
                          for _, r in chain)

    def profile_stretch(self) -> None:
        """A short steady stretch under the profiler, after the window."""
        n, profiler = self.p["profile_calls"], self.ctx.profiler
        profiler.start()
        calls, _, _ = self._drive(calls=n)
        profiler.stop()
        self.profile_units = sum(len(c) for c in calls)

    def attempted(self) -> int:
        return sum(len(c) for c in self.calls)

    def expected_batches(self):
        return None  # the scheduler decides how many batches the calls make

    def metrics(self) -> dict:
        from benchmark.harness.stats import percentile, samples_beyond

        ms = [s * 1e3 for chain in self.calls for s, r in chain if r is None]
        n = len(ms)
        log(f"   timed calls: {n} accepted, {self.failed} refused, by "
            f"{len(self.chains)} callers in {self.t1 - self.t0:.2f}s; beyond "
            f"p95: {samples_beyond(n, 95)} samples (the guide wants 10)")
        if not ms:
            return {}
        per = [percentile([s * 1e3 for s, _ in chain], 50)
               for chain in self.calls if chain]
        log(f"   calls a caller {min(len(c) for c in self.calls)}-"
            f"{max(len(c) for c in self.calls)}; a caller's own p50 "
            f"{min(per):.2f}-{max(per):.2f} ms; slowest call {max(ms):.1f}, "
            f"fastest {min(ms):.1f} ms")
        d = {k: self.stats_close[k] - self.stats_open[k]
             for k in self.stats_close}
        if d["dispatches"]:
            log(f"   scheduler inside the window: {json.dumps(d)}: "
                f"{d['requests'] / d['dispatches']:.2f} requests a dispatch, "
                f"{d['passthrough']} of {d['dispatches']} dispatches a lone "
                f"request")
        return {"commit_verify_ms.p50": percentile(ms, 50),
                "commit_verify_ms.p95": percentile(ms, 95)}

    # -- checks --------------------------------------------------------

    def verify(self) -> list:
        out = [C.equal("timed_calls_refused", self.failed, 0),
               C.at_least("timed_calls", self.attempted(), 1)]
        out += self._answered_checks()
        out += self._path_checks()
        out += self._mixed_round_checks()
        out += self._sliced_bitmap_checks()
        self.release()
        return out

    def release(self) -> None:
        """One release a chain, as each Node's stop() would make it; the
        last one closes the scheduler and joins its drainer."""
        from cometbft_tpu.crypto.sched import release_shared

        while self.scheds:
            release_shared(self.scheds.pop())

    def _answered_checks(self) -> list:
        """Requests answered = requests submitted, chain by chain: what the
        scheduler took from each tenant inside the window against the calls
        that returned to that chain's caller."""
        n = self.p["validators"]
        off = {}
        for chain, calls in zip(self.chains, self.calls):
            took = (self.tenants_close.get(chain, 0)
                    - self.tenants_open.get(chain, 0))
            if took != len(calls) * n:
                off[chain] = (took, len(calls) * n)
        if off:
            log(f"   lanes the scheduler took against lanes answered: {off}")
        sent = self.stats_close["requests"] - self.stats_open["requests"]
        return [C.equal("chains_whose_requests_answered_differ_from_submitted",
                        len(off), 0),
                C.equal("requests_submitted_less_calls_returned",
                        sent - self.attempted(), 0)]

    def _path_checks(self) -> list:
        """Every batch of over `coalesced_device_from_lanes` lanes took a
        device path: by the program's counters always, by its spans when
        traced. The batch-size histogram's edge is 1,024 lanes and the
        dispatch's line is "1,024 or more"; the two differ only for a batch
        of exactly 1,024 lanes, which whole commits of this set never make."""
        line = self.p["coalesced_device_from_lanes"]
        if line is None:
            return []
        if line != 1024:
            raise SystemExit("FAIL: the batch-size counter has its edge at "
                             "1024 lanes and no other")
        delta = self.ctx.counters.delta(self.ctx.snap_open, self.snap_close)
        dev, host = dispatch_counts(delta)
        big = self.big_close - self.big_open
        log(f"   batches inside the window: {int(dev)} on a device path, "
            f"{int(host)} on the host engine; {big} of over {line} lanes")
        gave = {k[0]: v for k, v in delta["gave_way_total"].items()}
        out = [C.equal("batches_over_1024_lanes_less_batches_on_a_device_path",
                       big - int(dev), 0),
               C.at_least("batches_on_a_device_path", int(dev), 1),
               C.equal("lanes_sent_to_the_host_at_result",
                       int(gave.get("oversize", 0)), 0)]
        if self.ctx.trace_path:
            lo, hi = self.t0 * 1e9, self.t1 * 1e9
            hidden = [(int(r["n"]), r["path"])
                      for r in read_records(self.ctx.trace_path,
                                            self.ctx.trace_off)
                      if r.get("name") == "crypto.batch_verify"
                      and "path" in r and lo <= r.get("t0_ns", 0) <= hi
                      and int(r["n"]) >= line
                      and r["path"] not in DEVICE_PATHS]
            out.append(C.equal("spans_of_big_batches_on_a_host_path",
                               len(hidden), 0))
        return out

    def _mixed_commits(self) -> None:
        """The commits of the mixed round and of the sliced bitmaps, on one
        seeded height: chain `four` gets check.corrupt_commit's four bad
        lanes, the chains `ones` one bad lane each (two chains at the cell's
        size; a rehearsal of four chains has room for one), two `honest`
        chains are compared beside them. bad[c] is the corrupted commit,
        why[c] its bad lanes, lanes[c] the compared commits' lanes."""
        import numpy as np

        seed, n_chains = self.ctx.seed, len(self.chains)
        rng = np.random.default_rng([seed, 5])
        self.order = order = rng.permutation(n_chains).tolist()
        n_ones = min(2, n_chains - 3)
        four, ones = order[0], order[1:1 + n_ones]
        self.honest = order[1 + n_ones:3 + n_ones]
        self.k = k = int(rng.integers(len(self.encoded[0])))
        self.bad, self.why = {}, {}
        commit = self.decode(self.encoded[four][k])
        self.bad[four], self.why[four] = C.corrupt_commit(
            commit, self.weird, seed)
        free = [i for i in range(self.p["validators"]) if i not in self.weird]
        for c in ones:
            commit = copy.deepcopy(self.decode(self.encoded[c][k]))
            idx = free[int(rng.integers(len(free)))]
            sig = bytearray(commit.signatures[idx].signature)
            sig[40] ^= 0x01
            commit.signatures[idx].signature = bytes(sig)
            commit.invalidate_memos()
            self.bad[c], self.why[c] = commit, {idx: "flipped bit in S"}
        self.lanes = {
            c: C.commit_lanes(self.chains[c], self.vals, self.bad.get(c)
                              or self.decode(self.encoded[c][k]))
            for c in [four, *ones, *self.honest]}
        log(f"   mixed round on commit {k + 1}: bad lanes "
            f"{ {self.chains[c]: sorted(w) for c, w in self.why.items()} }")

    def _mixed_round_checks(self) -> list:
        """Outside the window, every caller at once through the live
        scheduler: the chains of _mixed_commits send their corrupted copies,
        the others honest commits. Each call's answer must be what the plain
        reference gives for that commit ALONE, and the program's own record
        of the round (crypto.sched_coalesce: which tenants rode in which
        dispatch) must show bad and honest requests in ONE dispatch, so that
        the check cannot pass by an accident of timing; the round is
        repeated, at most mixed_round_tries times, until it does."""
        self._mixed_commits()
        bad, why, chains = self.bad, self.why, self.chains
        want = {c: commit_alone.judge(rows) for c, rows in self.lanes.items()}
        out = [C.equal("mixed_round.reference_blames_the_lowest_bad_lane",
                       {chains[c]: want[c] for c in bad},
                       {chains[c]: min(why[c]) for c in bad}),
               C.equal("mixed_round.reference_accepts_the_honest_commits",
                       [want[c] for c in self.honest],
                       [None] * len(self.honest))]
        together, tries = False, 0
        while not together and tries < self.p["mixed_round_tries"]:
            tries += 1
            self.next_k = [self.k] * len(chains)
            records, (calls, _, _) = self._recorded(
                lambda: self._drive(calls=1, special=dict(bad)))
            for r in records:
                if (r.get("name") == "crypto.sched_coalesce"
                        and int(r.get("n_requests", 0)) >= 2):
                    rode = set(r.get("per_tenant_sigs") or {})
                    n_bad = sum(chains[c] in rode for c in bad)
                    together = together or 0 < n_bad < len(rode)
        got = {chains[c]: calls[c][0][1] for c in range(len(chains))}
        expect = {chains[c]: f"invalid signature at index {min(why[c])}"
                  for c in bad}
        wrong = {ch: r for ch, r in got.items() if r != expect.get(ch)}
        if wrong:
            log(f"   mixed round: answers that differ from the commit judged "
                f"alone: {wrong}")
        return out + [
            C.Check("mixed_round.bad_and_honest_in_one_dispatch",
                    f"{together} after {tries} round(s)",
                    f"True within {self.p['mixed_round_tries']} rounds",
                    together),
            C.equal("mixed_round.calls_refused",
                    sorted(ch for ch, r in got.items() if r is not None),
                    sorted(expect)),
            C.equal("mixed_round.answers_differing_from_the_commit_alone",
                    len(wrong), 0)]

    def _sliced_bitmap_checks(self) -> list:
        """The lane bitmaps of the three bad and of two honest commits, as
        the scheduler slices them out of a merged batch, against the
        generator, OpenSSL, the host engine and the plain reference
        (check.bitmap_checks, as the other commit cells). All chains'
        verifiers are handed to the scheduler at once, an honest one that is
        not compared first: the first arrival at an idle scheduler rides
        alone."""
        from cometbft_tpu.crypto.ed25519 import (
            Ed25519BatchVerifier,
            Ed25519PubKey,
        )

        rest = [c for c in self.order if c not in self.lanes]
        filled = []
        for c in rest[:1] + list(self.lanes) + rest[1:]:
            rows = self.lanes.get(c) or C.commit_lanes(
                self.chains[c], self.vals,
                self.decode(self.encoded[c][self.k]))
            bv = Ed25519BatchVerifier(backend=self.p["scheduler"]["backend"])
            for pub, msg, sig in rows:
                bv.add(Ed25519PubKey(pub), msg, sig)
            filled.append((c, bv))
        handles = {c: self.sched.submit(bv, tenant=self.chains[c],
                                        source=SOURCE) for c, bv in filled}
        out, lost = [], 0
        for c, handle in handles.items():
            try:
                ok, bits = handle.result(timeout=self.grace)
            except Exception as e:  # noqa: BLE001 - a check that fails
                log(f"   sliced bitmaps: {self.chains[c]} got no verdict: "
                    f"{e!r}")
                lost += 1
                continue
            if c in self.lanes:
                is_bad = c in self.bad
                tag = (f"sliced.{'bad' if is_bad else 'honest'}."
                       f"{self.chains[c]}")
                out.append(C.equal(f"{tag}.batch_ok", ok, not is_bad))
                out += C.bitmap_checks(tag, bits, self.lanes[c],
                                       self.why.get(c, {}), self.weird,
                                       self.ctx.seed)
        # answered, every one: none is dropped
        out.append(C.equal("sliced.requests_unanswered", lost, 0))
        return out

    def _recorded(self, fn):
        """fn() with the program's tracer on; (the records it wrote, fn's
        result). A traced run's sink is read from where it stood; an untraced
        run's tracer is on for the call alone, outside the window."""
        from cometbft_tpu.utils import trace

        path = self.ctx.trace_path
        mine = path is None
        if mine:
            path = os.path.join(self.ctx.workdir, "mixed_round.jsonl")
            trace.configure(path)
        try:
            trace.flush()
            start = os.path.getsize(path)
            result = fn()
            return read_records(path, start), result
        finally:
            if mine:
                trace.disable()


def read_records(path: str, start: int) -> list[dict]:
    from cometbft_tpu.utils import trace

    trace.flush()
    with open(path, encoding="utf-8") as f:
        f.seek(start)
        return [json.loads(line) for line in f]


def batches_over_1024() -> int:
    """Dispatches of more than 1,024 lanes so far, from the program's
    crypto_batch_size histogram as a scrape of /metrics reads it."""
    from cometbft_tpu.utils.metrics import crypto_metrics

    le_1024 = total = 0
    for line in crypto_metrics().batch_size.expose():
        if '_bucket{le="1024"}' in line:
            le_1024 = int(float(line.rsplit(" ", 1)[1]))
        elif '_bucket{le="+Inf"}' in line:
            total = int(float(line.rsplit(" ", 1)[1]))
    return total - le_1024
