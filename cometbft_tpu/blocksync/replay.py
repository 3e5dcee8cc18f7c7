"""Chain replay: the block-sync apply loop (north-star workload #4).

Behavior parity with reference internal/blocksync/reactor.go:425-517: each
block is verified with the *next* block's LastCommit via VerifyCommitLight,
then applied through ABCI. Per-block that is one sig-verify-bound batch +
one FinalizeBlock round trip — the loop the TPU data plane must cut >=5x.

TPU-first design: instead of one device dispatch per height (the
reference's per-block CGo batch call), `window` heights of commit
signatures are packed into ONE mega-batch (10k+ lanes) and verified in a
single kernel launch while the host applies previously-verified blocks —
commit size no longer bounds device utilization (SURVEY §5.7's "sequence
length" analogue: batch across heights, not just within a commit).
"""

from __future__ import annotations

import time
from dataclasses import dataclass

from ..crypto import ed25519
from ..state.execution import BlockExecutor, BlockValidationError, validate_block
from ..storage import BlockStore
from ..types import Block, Commit
from ..types.block import block_id_for
from ..types.validation import (
    CertCommitVerifier,
    CommitError,
    ErrInvalidSignature,
    ErrNotEnoughVotingPower,
)
from ..utils import trace as _trace
from ..utils.metrics import blocksync_metrics


class _WindowPending:
    """Joined handle for one window's verification work: the ed25519
    mega-batch plus the certificate-native commits' one-pairing checks
    (ISSUE 17). Certificates never enter the signature mega-batch — each
    is a single pairing regardless of signer count."""

    def __init__(self, ed_pending, cert_checks):
        self.ed = ed_pending  # ed25519 pending | None (all-cert window)
        self.certs = cert_checks  # [(height, CertCommitVerifier, pending)]

    def prefetch(self):
        if self.ed is not None:
            self.ed.prefetch()

    def result(self):
        """(ok, bits) of the ed25519 lanes, raising first on any failed
        certificate with the same error classes the column path uses."""
        from ..types.validation import _raise_cert_error

        m = blocksync_metrics()
        for h, bv, pend in self.certs:
            t0 = time.perf_counter()
            ok, _ = pend.result()
            m.cert_verify_seconds.observe(time.perf_counter() - t0)
            if not ok:
                try:
                    _raise_cert_error(bv.error)
                except CommitError as e:
                    raise type(e)(f"height {h}: {e}") from e
        if self.ed is None:
            return True, []
        return self.ed.result()


@dataclass
class ReplayStats:
    blocks: int = 0
    sigs_verified: int = 0
    elapsed_s: float = 0.0

    @property
    def blocks_per_sec(self) -> float:
        return self.blocks / self.elapsed_s if self.elapsed_s else 0.0


class ReplayEngine:
    """Replays a stored chain into an application.

    verify_mode:
      - "full": reference-faithful — VerifyCommitLight per height plus the
        full LastCommit verification inside block validation.
      - "batched": commit signatures for `window` consecutive heights are
        verified in one device mega-batch (per-sig bitmap checked, +2/3
        tallied per height), then blocks are applied with the in-validation
        re-verification elided (it would re-check the same signatures).
    """

    def __init__(
        self,
        block_store: BlockStore,
        executor: BlockExecutor,
        verify_mode: str = "batched",
        window: int = 64,
        backend: str = "tpu",
        depth: int | None = None,
        sched=None,
        tenant: str = "",
    ):
        # window=64 default: each window resolve pays one device->host
        # round trip (its cost is unmeasured on today's machine), so
        # fewer, larger windows amortize it; 64 heights x 150 validators
        # still fits the 16384-lane bucket (x 1000 validators it is a
        # 65,000-lane batch in the 65536 bucket)
        if verify_mode not in ("full", "batched"):
            raise ValueError(f"unknown verify_mode {verify_mode}")
        self.store = block_store
        self.executor = executor
        self.verify_mode = verify_mode
        self.window = window
        self.backend = backend
        # in-flight window count: None = auto (see _pipeline_depth)
        self.depth = depth
        # optional crypto.sched.VerifyScheduler: window mega-batches
        # coalesce with other consumers' work at blocksync priority
        self.sched = sched
        self.tenant = tenant

    def _pipeline_depth(self) -> int:
        """Windows in flight at once. Single device: 2 (device verifies
        w+1 while the host applies w — deeper queues just park work
        behind one chip). Mesh: 1 + n_devices, so round-robin streaming
        keeps EVERY chip holding a window while the host applies."""
        if self.depth:
            return max(1, int(self.depth))
        eng = ed25519._mesh_engine()
        if eng is not None and eng.n_devices > 1:
            return 1 + eng.n_devices
        return 2

    def _commit_for(self, height: int) -> Commit | None:
        c = self.store.load_block_commit(height)
        if c is None:
            c = self.store.load_seen_commit(height)
        return c

    def _queue_window(self, chain_id, validators, lc_vals, prev_bid,
                      initial_height, blocks: list):
        """Submit (without blocking) every signature check a window of
        blocks needs; returns an opaque handle for _resolve_window.

        Split from the old synchronous check so run() can keep the
        device verifying window w+1 while the host applies window w —
        the replay loop is control-plane-bound (ABCI + stores + proto),
        and serializing host and device work wastes whichever is
        cheaper (round 3 measured: verification was ~2 ms of a ~10 ms block
        budget)."""
        with _trace.span("blocksync.window_queue",
                         window=blocks[0].header.height,
                         blocks=len(blocks)):
            return self._window_batch(
                chain_id, validators, lc_vals, prev_bid, initial_height,
                blocks
            )

    def _resolve_window(self, handle) -> int:
        """Block on the device verdict; raise on any invalid signature
        or insufficient tally. Returns signatures verified."""
        pending, per_commit, nsigs, window = handle
        with _trace.span("blocksync.window_resolve", window=window,
                         sigs=nsigs):
            ok, bits = pending.result()
            if not ok:
                for i, b in enumerate(bits):
                    if not b:
                        raise ErrInvalidSignature(
                            f"invalid signature in window lane {i}"
                        )
            for h, threshold, entries in per_commit:
                tally = sum(entries)
                if tally <= threshold:
                    raise ErrNotEnoughVotingPower(
                        f"height {h}: tallied {tally} <= {threshold}"
                    )
        return nsigs

    def _window_batch(self, chain_id, validators, lc_vals_first, prev_bid,
                      initial_height, blocks: list):
        """Batch every signature check the per-block path would do across a
        window of blocks, submitted (not resolved) in one device call.

        Two families of commits go into the mega-batch:

        1. Each block's EMBEDDED LastCommit, with full VerifyCommit
           semantics (reference types/validation.go:21-34: every non-absent
           signature — COMMIT and NIL votes alike — verified; COMMIT votes
           tallied to +2/3; commit bound to the predecessor's computed
           BlockID). This is exactly the check apply_block_preverified
           elides, so eliding it is sound.
        2. The STORED commit for the window's last block, VerifyCommitLight
           semantics (reference internal/blocksync/reactor.go:462: the tip
           needs an external +2/3 endorsement since no successor block in
           this window embeds one).

        The window only spans heights whose header.validators_hash equals
        validators.hash() (caller enforces), so every embedded LastCommit
        except the first block's was signed by `validators`; the first
        block's was signed by `lc_vals_first`.
        """
        from ..types.validation import (
            ErrInvalidCommitSize, _check_commit_basics, commit_lanes)

        bv = ed25519.Ed25519BatchVerifier(backend=self.backend)
        per_commit: list[tuple[int, int, list[int]]] = []
        cert_bvs: list[tuple[int, CertCommitVerifier]] = []
        lane = 0
        singles = 0
        cert_sigs = 0
        columnar = 0  # commits that took the columnar path

        def queue_commit_cert(commit, vals, height):
            """Certificate-native commit: ONE pairing check replaces the
            whole signature column. Power tally and bitmap consistency
            are enforced inside AggregateCommit.verify, so no per_commit
            entry is needed — a shortfall surfaces as
            ErrNotEnoughVotingPower through the verifier's error."""
            nonlocal cert_sigs
            cert_bvs.append((height, CertCommitVerifier(chain_id, vals, commit)))
            cert_sigs += commit.signer_count()

        def queue_commit_columnar(commit, vals, height, all_sigs):
            """Whole-commit queueing without per-CommitSig Python:
            validation.commit_lanes (the one copy of the columnar gates)
            turns the decode columns and the frozen set's key columns
            into lanes, and one add_batch queues them. Returns False
            (caller takes the per-slot path) when it declines or the set
            holds non-ed25519 keys — so behavior is byte-identical where
            it matters and merely slower where it is rare."""
            nonlocal lane
            if vals.ed25519_columns() is None:
                return False
            lanes = commit_lanes(chain_id, vals, commit, all_sigs)
            if isinstance(lanes, str):
                return False  # per-slot path localizes what is off
            lanes.add_ed25519(bv)
            lane += lanes.n
            per_commit.append(
                (height, vals.total_voting_power() * 2 // 3,
                 (lanes.power,))
            )
            return True

        def queue_commit(commit, vals, expect_bid, height, all_sigs):
            nonlocal lane, singles, columnar
            _check_commit_basics(vals, commit, height, expect_bid)
            if commit.size() != len(vals):
                raise ErrInvalidCommitSize(
                    f"commit size {commit.size()} != validator set {len(vals)}"
                )
            if getattr(commit, "cert", None) is not None:
                queue_commit_cert(commit, vals, height)
                return
            if queue_commit_columnar(commit, vals, height, all_sigs):
                columnar += 1
                return
            entries = []
            msgs = commit.vote_sign_bytes_all(chain_id)
            for idx, cs in enumerate(commit.signatures):
                if cs.is_absent() or (not all_sigs and not cs.is_commit()):
                    continue
                val = vals.get_by_index(idx)
                if val is None or val.address != cs.validator_address:
                    raise ErrInvalidSignature(
                        f"address mismatch at height {height} index {idx}"
                    )
                msg = msgs[idx]
                before = bv.count()
                bv.add(val.pub_key, msg, cs.signature)
                if bv.count() == before:
                    # batch verifier refused the key type (no lane was
                    # consumed): verify singly, like _verify_items' fallback
                    if not val.pub_key.verify_signature(msg, cs.signature):
                        raise ErrInvalidSignature(
                            f"invalid signature at height {height} index {idx}"
                        )
                    singles += 1
                else:
                    lane += 1
                if cs.is_commit():
                    entries.append(val.voting_power)
            per_commit.append(
                (height, vals.total_voting_power() * 2 // 3, entries)
            )

        window = blocks[0].header.height
        lc_vals = lc_vals_first
        with _trace.span("blocksync.window_fill", window=window) as sp:
            for blk in blocks:
                h = blk.header.height
                if h != initial_height:
                    if lc_vals is None:
                        raise BlockValidationError(
                            f"no validator set for last commit of height {h}"
                        )
                    queue_commit(blk.last_commit, lc_vals, prev_bid, h - 1,
                                 all_sigs=True)
                prev_bid = block_id_for(blk)
                lc_vals = validators
            tip = blocks[-1].header.height
            commit = self._commit_for(tip)
            if commit is None:
                raise BlockValidationError(
                    f"missing commit at height {tip}")
            queue_commit(commit, validators, prev_bid, tip, all_sigs=False)
            sp.add(commits=len(per_commit) + len(cert_bvs), lanes=lane,
                   columnar=columnar)
        cert_checks = []
        if self.sched is not None:
            for ch, cbv in cert_bvs:
                cert_checks.append(
                    (ch, cbv, self.sched.submit(
                        cbv, tenant=self.tenant, source="blocksync"))
                )
            ed_pending = (
                self.sched.submit(bv, tenant=self.tenant, source="blocksync")
                if bv.count() else None
            )
        else:
            for ch, cbv in cert_bvs:
                cert_checks.append((ch, cbv, cbv.submit()))
            ed_pending = bv.submit() if bv.count() else None
        pending = _WindowPending(ed_pending, cert_checks)
        return pending, per_commit, lane + singles + cert_sigs, window

    def _light_check_window(self, state, blocks: list) -> int:
        """Synchronous window check (submit + resolve); kept for callers
        outside the pipelined run loop."""
        handle = self._queue_window(
            state.chain_id, state.validators, state.last_validators,
            state.last_block_id, state.initial_height, blocks,
        )
        return self._resolve_window(handle)

    def _load_window(self, h: int, tip: int, vals_hash: bytes) -> list:
        """Blocks [h .. h+window-1] bounded by tip and by the first
        validator-set change (empty list when block h is stored but
        belongs to a different set; raises when block h is missing)."""
        w_end = min(h + self.window - 1, tip)
        blocks = []
        # what ended the window: the window size, the chain's tip, a block
        # of another validator set, a block the store lacks
        end = "tip" if w_end == tip else "full"
        # stored bytes read; traced, the seconds in the key-value gets and
        # in Block.decode (two clock reads a block: a get runs from the
        # end of the decode before it)
        nbytes, read_s, decode_s = 0, 0.0, 0.0
        timed = _trace.enabled
        with _trace.span("blocksync.window_load", window=h) as sp:
            t_a = time.perf_counter() if timed else 0.0
            for hh in range(h, w_end + 1):
                raw = self.store.load_block_bytes(hh)
                if timed:
                    t_b = time.perf_counter()
                    read_s += t_b - t_a
                # the store's own bytes are canonical: load_block's decode
                blk = Block.decode(raw, trusted_bytes=True) if raw else None
                if timed:
                    t_a = time.perf_counter()
                    decode_s += t_a - t_b
                if blk is None:
                    if hh == h:
                        raise BlockValidationError(
                            f"missing block at height {h}")
                    end = "missing"
                    break
                if blk.header.validators_hash != vals_hash:
                    end = "set_change"
                    break
                blocks.append(blk)
                nbytes += len(raw)
            sp.add(blocks=len(blocks), end=end, bytes=nbytes)
            if timed:
                sp.add(read_ms=round(read_s * 1e3, 3),
                       decode_ms=round(decode_s * 1e3, 3))
        if blocks:
            m = blocksync_metrics()
            m.window_blocks.observe(len(blocks))
            m.window_bytes.observe(nbytes)
        return blocks

    def run(self, state, to_height: int | None = None) -> tuple[object, ReplayStats]:
        """Replay from state.last_block_height+1 to `to_height` (or tip).

        Batched mode pipelines depth-N (_pipeline_depth: 2 on a single
        device, 1 + n_devices on a mesh so round-robin streaming keeps
        every chip holding a window): windows w+1..w+N-1's signature
        batches are in flight while the host applies window w's blocks
        (sound within a constant-validator-set span: each window's
        verification inputs — validator set and predecessor block id —
        are known before w is applied; across a set change the pipeline
        drains and re-queues with the post-apply state).

        Where the executor's event bus feeds an indexer, run() returns
        (or raises) only once the index holds every block it applied."""
        tip = to_height or self.store.height()
        h = state.last_block_height + 1
        with _trace.span("blocksync.replay", to=tip, mode=self.verify_mode,
                         **{"from": h}) as span:
            try:
                return self._run(state, tip, h, span)
            finally:
                # the tip is indexed when the replay says it is: what the
                # applied blocks published is written before run() returns
                # (or raises), and a failed index write fails the replay
                bus = getattr(self.executor, "event_bus", None)
                if bus is not None:
                    bus.join()

    def _run(self, state, tip: int, h: int,
             span) -> tuple[object, ReplayStats]:
        stats = ReplayStats()
        t0 = time.perf_counter()
        if self.verify_mode == "batched" and h <= tip:
            from collections import deque

            depth = self._pipeline_depth()
            span.add(depth=depth)
            cur_hash = state.validators.hash()
            blocks = self._load_window(h, tip, cur_hash)
            if not blocks:
                raise BlockValidationError(f"cannot form window at height {h}")
            handle = self._queue_window(
                state.chain_id, state.validators, state.last_validators,
                state.last_block_id, state.initial_height, blocks,
            )
            q: deque = deque([(blocks, handle)])
            last_qed = blocks  # last window queued (speculation anchor)
            spec_dead = False  # stop speculating until the serial requeue

            def fill():
                # top the in-flight queue up to `depth` windows,
                # speculatively: problems in a later window's data must
                # not abort before the already-verified earlier windows
                # apply (they resurface in the serial re-queue below,
                # after that progress is durable)
                nonlocal last_qed, spec_dead
                while not spec_dead and len(q) < depth:
                    nh = last_qed[-1].header.height + 1
                    if nh > tip:
                        return
                    try:
                        nxt = self._load_window(nh, tip, cur_hash)
                        if not nxt:
                            spec_dead = True
                            return
                        # same-set continuation: every window in the
                        # span was signed by the CURRENT validator set
                        nxt_handle = self._queue_window(
                            state.chain_id, state.validators,
                            state.validators, block_id_for(last_qed[-1]),
                            state.initial_height, nxt,
                        )
                    except (CommitError, BlockValidationError):
                        spec_dead = True
                        return
                    # start the device->host fetch (a fixed cost,
                    # unmeasured on today's machine) early so it rides
                    # under later queueing/apply work instead of
                    # blocking resolve
                    nxt_handle[0].prefetch()
                    q.append((nxt, nxt_handle))
                    last_qed = nxt

            while q:
                fill()  # keep every device busy before blocking
                blocks, handle = q.popleft()
                handle[0].prefetch()
                stats.sigs_verified += self._resolve_window(handle)
                with _trace.span("blocksync.window_apply",
                                 window=blocks[0].header.height,
                                 blocks=len(blocks)) as sp:
                    txs = 0
                    for block in blocks:
                        bid = block_id_for(block)
                        state = self.executor.apply_block_preverified(
                            state, bid, block)
                        stats.blocks += 1
                        txs += len(block.data.txs)
                    sp.add(txs=txs)
                    if _trace.enabled:
                        sp.add(tx_bytes=sum(
                            len(tx) for b in blocks for tx in b.data.txs))
                blocksync_metrics().txs_applied_total.inc(txs)
                nh = blocks[-1].header.height + 1
                if q or nh > tip:
                    continue
                # pipeline drained mid-chain: validator set changed at
                # the boundary (or speculation failed) — reload and
                # queue against the post-apply state. Nothing is in
                # flight from here to the return of the re-queued
                # window's submit(): blocksync.set_change spans that
                # stretch (a depth-1 engine comes here after every
                # window, which is no boundary)
                sc = _trace.open_span("blocksync.set_change", height=nh)
                new_hash = state.validators.hash()
                boundary = new_hash != cur_hash or spec_dead
                if boundary:
                    reason = ("set_change" if new_hash != cur_hash
                              else "speculation_failed")
                    blocksync_metrics().set_change_total.inc(1.0, reason)
                    sc.add(reason=reason)
                cur_hash = new_hash
                spec_dead = False
                try:
                    nxt = self._load_window(nh, tip, cur_hash)
                    if not nxt:
                        raise BlockValidationError(
                            f"cannot form window at height {nh}"
                        )
                    nxt_handle = self._queue_window(
                        state.chain_id, state.validators,
                        state.last_validators, state.last_block_id,
                        state.initial_height, nxt,
                    )
                finally:
                    if boundary:
                        sc.close()
                q.append((nxt, nxt_handle))
                last_qed = nxt
            stats.elapsed_s = time.perf_counter() - t0
            return state, stats
        # "full" mode: reference-faithful per-height verify + apply
        from ..crypto.sched import verify_context
        from ..types.validation import verify_commit_light

        while h <= tip:
            block = self.store.load_block(h)
            commit = self._commit_for(h)
            if block is None or commit is None:
                raise BlockValidationError(f"missing block/commit at {h}")
            bid = block_id_for(block)
            with verify_context(self.sched, self.tenant, "blocksync"):
                verify_commit_light(
                    state.chain_id, state.validators, bid, h, commit,
                    backend=self.backend,
                )
            stats.sigs_verified += sum(
                1 for cs in commit.signatures if cs.is_commit()
            )
            state = self.executor.apply_block(state, bid, block)
            stats.blocks += 1
            h += 1
        stats.elapsed_s = time.perf_counter() - t0
        return state, stats
