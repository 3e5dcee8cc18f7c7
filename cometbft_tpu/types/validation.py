"""Commit verification — the consensus hot path feeding the TPU data plane.

Behavior parity with reference types/validation.go:
- VerifyCommit (:26): checks EVERY non-absent signature (LastCommit reward
  accuracy), tallying only BlockIDFlag.COMMIT votes toward the +2/3 check.
- VerifyCommitLight (:61): verifies only COMMIT votes, succeeds on +2/3.
- VerifyCommitLightTrusting (:125): validator lookup by address against a
  *different* (trusted) set, threshold = trust_level fraction of its power.
- Batch path (:214): any commit with >= 2 signatures goes through the
  BatchVerifier (the TPU kernel); on batch failure the per-signature
  validity bitmap pinpoints the first bad signature — the reference has to
  re-scan singly (:304-311), we get the bitmap for free from the per-lane
  kernel.
"""

from __future__ import annotations

import time as _time

from ..crypto.keys import PubKey
from ..utils import trace as _trace
from ..utils.metrics import crypto_metrics
from .basic import BlockID
from .block import BlockIDFlag, Commit
from .validator_set import ValidatorSet

BATCH_VERIFY_THRESHOLD = 2

_SECP_TAG = "tendermint/PubKeySecp256k1"
_BLS_TAG = "tendermint/PubKeyBls12_381"
_ED_TAG = "tendermint/PubKeyEd25519"
_SR_TAG = "tendermint/PubKeySr25519"
# key types whose lanes the columnar path fills: 64-byte signatures and
# a verifier that takes rows. A set with any other (BLS: 96 bytes, one
# aggregate) is left to the per-slot loop.
_COLUMNAR_TAGS = (_ED_TAG, _SR_TAG, _SECP_TAG)


def _curve_of(tag: str) -> str:
    """Metric/span curve label from a key type tag:
    "tendermint/PubKeyEd25519" -> "ed25519"."""
    if tag == _BLS_TAG:
        return "bls"
    return tag.rsplit("PubKey", 1)[-1].lower() or tag


def _observe_partition(tag: str, path: str, dt: float) -> None:
    """Per-curve observability for one commit partition: the mixed
    mega-commit's breakdown (which curve burns the wall) shows up in
    /metrics (crypto_verify_seconds{path=...,curve=...}) without
    re-profiling. `dt` runs from the partition's launch to its verdict."""
    curve = _curve_of(tag)
    m = crypto_metrics()
    m.path_selected_total.inc(1.0, path, curve)
    m.verify_seconds.observe(dt, path, curve)


class _Leg:
    """One curve's share of a commit from its launch to its verdict:
    the verifier or the in-flight handle, and the crypto.commit_partition
    span, which its sibling legs overlap."""

    __slots__ = ("tag", "path", "idxs", "bv", "pending", "verdict",
                 "t0", "launch_s", "span")

    def __init__(self, tag: str, path: str, idxs: list[int], bv=None):
        self.tag = tag
        self.path = path
        self.idxs = idxs
        self.bv = bv
        self.pending = self.verdict = None
        self.span = _trace.open_span(
            "crypto.commit_partition", curve=_curve_of(tag), path=path,
            n=len(idxs))
        self.t0 = _time.perf_counter()
        self.launch_s = 0.0

    def launched(self) -> "_Leg":
        self.launch_s = _time.perf_counter() - self.t0
        return self

    def resolve(self) -> tuple[bool, list[bool]]:
        """(all ok, verdict per index of idxs), blocking as long as the
        leg still needs; closes the span."""
        t0 = _time.perf_counter()
        if self.verdict is None:
            self.verdict = (self.pending.result() if self.pending is not None
                            else self.bv.verify())
        now = _time.perf_counter()
        waited = now - t0
        # a leg on a worker thread knows its own time; one that ran on
        # this thread took its launch and what its verdict blocked for
        own = getattr(self.pending, "own_s", self.launch_s + waited)
        self.span.add(own_ms=round(own * 1e3, 3),
                      waited_ms=round(waited * 1e3, 3))
        self.span.close()
        _observe_partition(self.tag, self.path, now - self.t0)
        return self.verdict


class CommitError(Exception):
    pass


class ErrInvalidCommitHeight(CommitError):
    pass


class ErrInvalidCommitSize(CommitError):
    pass


class ErrInvalidBlockID(CommitError):
    pass


class ErrInvalidSignature(CommitError):
    pass


class ErrNotEnoughVotingPower(CommitError):
    pass


def _verify_items(items, backend: str):
    """items: list of (pubkey, msg, sig, power_if_counted). Returns tally.

    Mixed-curve commits are partitioned by key type and each group goes
    to its own batch verifier (ed25519 → TPU kernel, sr25519 → host
    batch); key types without batch support (secp256k1) verify singly —
    matching the reference's batchSigIdxs dispatch
    (types/validation.go:274-311, crypto/batch/batch.go:11-35).
    Raises ErrInvalidSignature naming the lowest invalid index, on
    whichever curve it lies.
    """
    if len(items) >= BATCH_VERIFY_THRESHOLD:
        groups, singles, secp_rows = _fill_items(items, backend)
        _judge(groups, singles, secp_rows, items.__getitem__, backend)
    else:
        for i, (pub, msg, sig, _) in enumerate(items):
            if not pub.verify_signature(msg, sig):
                raise ErrInvalidSignature(f"invalid signature at index {i}")
    return sum(p for _, _, _, p in items)


def _fill_items(items, backend: str):
    """The per-slot fill: each item grouped by key type and add()ed to
    its curve's verifier. Returns (groups: tag -> (verifier, lanes it
    took), singles: tag -> lanes judged without a batch verifier, the
    secp256k1 singles as (pub, msg, sig) rows); a lane is its position
    in `items`."""
    from ..crypto.batch import create_batch_verifier

    groups: dict[str, tuple[object, list[int]]] = {}
    singles: dict[str, list[int]] = {}
    with _trace.span("types.verify_items_fill", n=len(items)) as sp:
        for i, (pub, msg, sig, _) in enumerate(items):
            tag = pub.type_tag()
            if tag not in groups:
                groups[tag] = (
                    create_batch_verifier(pub, backend=backend), [])
            bv, idxs = groups[tag]
            if bv is None:
                singles.setdefault(tag, []).append(i)
                continue
            before = bv.count()
            added = bv.add(pub, msg, sig)
            if bv.count() > before:
                # verifier took the item (possibly pre-marked
                # invalid): its bitmap stays index-aligned
                idxs.append(i)
            elif not added:
                # rejected outright: decide singly
                singles.setdefault(tag, []).append(i)
        secp_rows = [(items[i][0].bytes(), items[i][1], items[i][2])
                     for i in singles.get(_SECP_TAG, ())]
        sp.add(groups=len(groups),
               singles=sum(len(v) for v in singles.values()))
    return groups, singles, secp_rows


def _fill_lanes(lanes: "CommitLanes", backend: str):
    """The columnar fill, to _fill_items' contract: one add_batch for
    the ed25519 lanes, rows for the two minority curves; a lane is its
    position among the commit's judged slots."""
    from ..crypto.ed25519 import Ed25519BatchVerifier
    from ..crypto.sr25519 import Sr25519BatchVerifier

    groups: dict[str, tuple[object, list[int]]] = {}
    singles: dict[str, list[int]] = {}
    secp_rows: list = []
    with _trace.span("types.verify_items_fill", n=lanes.n) as sp:
        for tag, (pos, _, _) in lanes.curves.items():
            if not len(pos):
                continue
            if tag == _ED_TAG:
                bv = Ed25519BatchVerifier(backend=backend)
                lanes.add_ed25519(bv)
            elif tag == _SR_TAG:
                bv = Sr25519BatchVerifier(backend=backend)
                bv.add_rows(lanes.rows(tag))
            else:
                singles[tag] = pos.tolist()
                secp_rows = lanes.rows(tag)
                continue
            groups[tag] = (bv, pos.tolist())
        sp.add(groups=len(groups),
               singles=sum(len(v) for v in singles.values()))
    return groups, singles, secp_rows


def _judge(groups, singles, secp_rows, item, backend: str) -> None:
    """Launch every filled leg, await every verdict, and raise
    ErrInvalidSignature for the LOWEST bad lane over all curves.
    `item(lane)` gives (pubkey, msg, sig, ...) for the legs that must
    judge a lane singly."""
    # Launch every leg first, await the verdicts after. The host
    # legs go first: each is a hand-off to a worker thread (one
    # ctypes call into the C++ engine, which releases the GIL), so
    # they run under the ed25519 group's submit(), which packs on
    # this thread, under its device program, and beside each other.
    # The verdicts are awaited in reverse: the device's first, so a
    # host leg's waited_ms is what that overlap did not hide. With
    # backend "cpu" (the reference path) nothing is launched: each
    # leg is judged on this thread, in the same order.
    from ..crypto.sched import current_context

    sched_ctx = current_context()
    threaded = backend != "cpu"
    legs: list[_Leg] = []
    for tag, idxs in singles.items():
        if tag == _SECP_TAG:
            # no batch equation for secp256k1 (matching the
            # reference's "no batch support"), but the whole
            # partition still verifies in ONE native call across
            # the worker pool; per-item verdicts are exact, so
            # blame needs no rescan
            from ..crypto import native as _native
            from ..crypto import secp256k1 as _secp

            leg = _Leg(tag, "native-multi"
                       if _native.secp256k1_available() else "single",
                       idxs)
            if threaded:
                leg.pending = _secp.submit_many(secp_rows)
            else:
                bits = _secp.verify_many(secp_rows)
                leg.verdict = (all(bits), bits)
        else:
            leg = _Leg(tag, "single", idxs)
            bits = []
            for i in idxs:
                pub, msg, sig = item(i)[:3]
                bits.append(pub.verify_signature(msg, sig))
            leg.verdict = (all(bits), bits)
        legs.append(leg.launched())
    # ed25519 last: the one submit() that works on this thread
    for tag in sorted(groups, key=lambda t: t == _ED_TAG):
        bv, idxs = groups[tag]
        if bv is None or not idxs:
            continue
        leg = _Leg(tag, "aggregate" if tag == _BLS_TAG else "batch",
                   idxs, bv)
        if sched_ctx is not None and tag == _ED_TAG:
            # shared-scheduler seam (crypto/sched.py): the filled
            # verifier coalesces with other tenants'/sources' work
            # into one mega-dispatch; the handle is
            # pending-compatible and the bitmap slice is bit-exact
            leg.pending = sched_ctx.submit(bv)
        elif threaded and hasattr(bv, "submit"):
            leg.pending = bv.submit()
            leg.pending.prefetch()
        legs.append(leg.launched())
    # every leg is judged whatever the others found, and blame goes
    # to the LOWEST bad index of the commit, as the reference's
    # per-signature loop over a mixed set gives it
    bad: list[int] = []
    for leg in reversed(legs):
        ok, bits = leg.resolve()
        if ok:
            continue
        if bits:
            # the bitmap pinpoints failures directly — no rescan
            bad.extend(j for j, b in zip(leg.idxs, bits) if not b)
            continue
        # batch could not localize: fall back to single verification
        # like the reference (:327). If every signature passes singly,
        # the commit is valid — accept.
        for j in leg.idxs:
            pub, msg, sig = item(j)[:3]
            if not pub.verify_signature(msg, sig):
                bad.append(j)
    if bad:
        raise ErrInvalidSignature(
            f"invalid signature at index {min(bad)}")


def _check_commit_basics(vals: ValidatorSet, commit: Commit, height: int, block_id: BlockID):
    if commit.height != height:
        raise ErrInvalidCommitHeight(f"expected height {height}, got {commit.height}")
    if commit.block_id != block_id:
        raise ErrInvalidBlockID("commit is for a different block")


# ----------------------------------------------------------------------
# certificate-native verification (ISSUE 17): a CertCommit is ONE
# pairing check regardless of signer count, routed through the shared
# VerifyScheduler when a verify_context is active (non-coalescable: the
# scheduler dispatches it individually inside the same drain cycle).
# ----------------------------------------------------------------------
class CertCommitVerifier:
    """Scheduler-compatible verifier wrapping one certificate check.

    Duck-types the BatchVerifier surface the scheduler consumes
    (count()/verify()); coalescable=False keeps it out of the ed25519
    mega-batch. The AggCommitError that failed verification is kept on
    .error so callers can raise the precise CommitError subclass."""

    coalescable = False

    def __init__(self, chain_id: str, vals: ValidatorSet, cert_commit):
        self.chain_id = chain_id
        self.vals = vals
        self.cc = cert_commit
        self.error = None

    def count(self) -> int:
        return max(1, self.cc.signer_count())

    def verify(self):
        try:
            self.cc.verify(self.chain_id, self.vals)
            return True, [True]
        except Exception as e:  # AggCommitError
            self.error = e
            return False, [False]

    def submit(self):
        """Pending-compatible inline handle (no-scheduler path)."""
        outer = self

        class _P:
            def prefetch(self):
                pass

            def result(self):
                return outer.verify()

        return _P()


def _raise_cert_error(err) -> None:
    from .agg_commit import AggCommitPowerError

    if isinstance(err, AggCommitPowerError):
        raise ErrNotEnoughVotingPower(str(err))
    raise ErrInvalidSignature(str(err))


def _verify_cert_commit(
    chain_id: str,
    vals: ValidatorSet,
    block_id: BlockID,
    height: int,
    commit,
    backend: str = "tpu",
) -> None:
    """Shared core for verify_commit/verify_commit_light on a
    CertCommit: structural checks, then one pairing (scheduler-routed
    when a verify_context is active)."""
    from ..crypto.sched import current_context

    _check_commit_basics(vals, commit, height, block_id)
    if len(vals) != commit.size():
        raise ErrInvalidCommitSize(
            f"validator set size {len(vals)} != commit size {commit.size()}"
        )
    bv = CertCommitVerifier(chain_id, vals, commit)
    ctx = current_context()
    t0 = _time.perf_counter()
    if ctx is not None:
        ok, _bits = ctx.submit(bv).result()
    else:
        ok, _bits = bv.verify()
    _observe_partition(_BLS_TAG, "aggregate", _time.perf_counter() - t0)
    if not ok:
        _raise_cert_error(bv.error)


def verify_cert_trusting(
    chain_id: str,
    trusted_vals: ValidatorSet,
    signing_vals: ValidatorSet,
    commit,
    trust_level: tuple[int, int] = (1, 3),
    backend: str = "tpu",
) -> None:
    """Certificate analogue of verify_commit_light_trusting for light
    skipping sync: the bitmap indexes `signing_vals` (the untrusted
    header's set); signers that are ALSO members of `trusted_vals` must
    carry more than trust_level of the trusted power. The aggregate
    itself is then checked with ONE pairing against signing_vals."""
    num, den = trust_level
    if den <= 0 or num < 0 or num > den:
        raise ValueError("invalid trust level")
    cert = commit.cert
    n = len(signing_vals)
    if commit.size() != n or len(cert.bitmap) != (n + 7) // 8:
        raise ErrInvalidCommitSize(
            f"certificate size {commit.size()} != signing set {n}")
    threshold = trusted_vals.total_voting_power() * num // den
    seen: set[bytes] = set()
    tally = 0
    for i in range(n):
        if not cert.has_signer(i):
            continue
        sv = signing_vals.get_by_index(i)
        _, tv = trusted_vals.get_by_address(sv.address)
        if tv is None or tv.address in seen:
            continue
        seen.add(tv.address)
        tally += tv.voting_power
    if tally <= threshold:
        raise ErrNotEnoughVotingPower(
            f"trusted tally {tally} <= threshold {threshold}")
    _verify_cert_commit(chain_id, signing_vals, cert.block_id,
                        cert.height, commit, backend=backend)


class CommitLanes:
    """The lanes of one commit that verification judges, as columns:
    what commit_lanes() builds where the per-slot loop would build one
    (pubkey, msg, sig, power) tuple a lane.

    n       lanes taken; lane k is slot slots[k] of the commit
    power   voting power of the taken COMMIT lanes
    curves  key type tag -> (lanes of that curve ascending, their slots,
            their pubkey rows)
    sign_s  seconds inside the native sign-bytes build
    """

    __slots__ = ("n", "power", "slots", "curves", "sign_s",
                 "_sigs", "_blob", "_lens", "_offs")

    def __init__(self, slots, power, curves, sign_s, sigs, blob, lens):
        self.n = len(slots)
        self.slots = slots
        self.power = power
        self.curves = curves
        self.sign_s = sign_s
        self._sigs = sigs
        self._blob = blob
        self._lens = lens
        self._offs = None

    def _msg_offsets(self):
        if self._offs is None:
            import numpy as np

            self._offs = np.zeros(len(self._lens) + 1, np.int64)
            np.cumsum(self._lens, out=self._offs[1:])
        return self._offs

    def add_ed25519(self, bv) -> None:
        """The ed25519 lanes into `bv` by one add_batch."""
        _, slots, pubs = self.curves[_ED_TAG]
        if len(slots) == len(self._lens):  # every slot of the commit
            bv.add_batch(pubs, self._sigs, self._blob, self._lens)
            return
        import numpy as np

        # the taken slots' sign bytes, one slice a run of neighbours
        offs = self._msg_offsets()
        cut = np.nonzero(np.diff(slots) != 1)[0] + 1
        first = slots[np.concatenate(([0], cut))]
        last = slots[np.concatenate((cut - 1, [len(slots) - 1]))]
        blob = self._blob
        msgs = b"".join([blob[a:b] for a, b in zip(
            offs[first].tolist(), offs[last + 1].tolist())])
        bv.add_batch(pubs, self._sigs[slots], msgs, self._lens[slots])

    def rows(self, tag: str) -> list[tuple[bytes, bytes, bytes]]:
        """One curve's lanes as the (pub, msg, sig) rows the host
        verifiers take."""
        _, slots, pubs = self.curves[tag]
        offs = self._msg_offsets()
        width = pubs.shape[1]
        pub_blob = pubs.tobytes()
        sig_blob = self._sigs[slots].tobytes()
        blob = self._blob
        return [
            (pub_blob[i * width:(i + 1) * width], blob[a:b],
             sig_blob[i * 64:(i + 1) * 64])
            for i, (a, b) in enumerate(zip(offs[slots].tolist(),
                                           offs[slots + 1].tolist()))
        ]


def commit_lanes(chain_id: str, vals: ValidatorSet, commit: Commit,
                 all_sigs: bool, cut_at: int | None = None):
    """The columnar entry: a commit that carries its decode columns,
    against a set whose key columns match them, becomes CommitLanes in
    a handful of numpy / native calls, with no CommitSig built. The ONE
    copy of these gates (verify_commit, verify_commit_light and the
    replay window all come here).

    all_sigs: full semantics, every non-absent lane is judged and COMMIT
    power counted; otherwise light semantics, COMMIT lanes only, in slot
    order up to and including the first whose running power exceeds
    `cut_at` (None: all of them).

    Returns the reason (str) where a gate fails: the caller's per-slot
    loop then decides, so every error is that loop's. The caller has
    checked commit.size() == len(vals)."""
    cols = commit.verify_columns()
    if cols is None:
        return "no_columns"  # hand-built, or after invalidate_memos()
    kc = vals.key_columns()
    if any(tag not in _COLUMNAR_TAGS or rows is None
           for tag, (_, rows) in kc.curves.items()):
        return "key_type"
    import numpy as np

    flags, addrs, addr_lens, sig_lens, sigs, _, _ = cols
    n = len(flags)
    absent = flags == 1
    counted = flags == 2
    live = ~absent if all_sigs else counted
    # structural gates: only ABSENT/COMMIT/NIL flags, 20-byte addresses
    # and 64-byte signatures on judged lanes, empty addresses on absent
    if not (
        kc.addr_rows is not None
        and n == len(kc.powers)
        and (absent | counted | (flags == 3)).all()
        and (addr_lens[live] == 20).all()
        and (sig_lens[live] == 64).all()
        and (addr_lens[absent] == 0).all()
    ):
        return "shape"
    if not (addrs[live] == kc.addr_rows[live]).all():
        return "address"  # the per-slot loop names the index
    t0 = _time.perf_counter()
    sb = commit.vote_sign_bytes_blob(chain_id)
    sign_s = _time.perf_counter() - t0
    if sb is None:
        return "no_native"
    blob, lens = sb
    slots = np.nonzero(live)[0]
    if all_sigs:
        power = int(kc.powers[counted].sum())
    else:
        running = np.cumsum(kc.powers[slots])
        if cut_at is not None:
            # the per-slot loop breaks after the first lane at which
            # the running power exceeds cut_at
            slots = slots[:int(np.searchsorted(running, cut_at,
                                               side="right")) + 1]
        power = int(running[len(slots) - 1]) if len(slots) else 0
    curves = {}
    of_curve = np.zeros(n, bool)
    for tag, (idx, rows) in kc.curves.items():
        of_curve[:] = False
        of_curve[idx] = True
        pos = np.nonzero(of_curve[slots])[0]
        mine = slots[pos]
        curves[tag] = (
            pos, mine,
            rows if len(mine) == len(idx)
            else rows[np.searchsorted(idx, mine)])
    return CommitLanes(slots, power, curves, sign_s, sigs, blob, lens)


def _slot_items(chain_id: str, vals: ValidatorSet, commit: Commit,
                light: bool, cut_at: int | None):
    """The per-slot loop: (items for _verify_items, seconds inside
    vote_sign_bytes). Full semantics take every non-absent slot and
    count COMMIT power; light semantics take COMMIT slots until the
    running power exceeds cut_at (None: all)."""
    items = []
    running = 0
    # per-lane time is never a span: accumulated under a local flag
    timed = _trace.enabled
    sign_s = 0.0
    for idx, cs in enumerate(commit.signatures):
        if cs.is_absent() or (light and not cs.is_commit()):
            continue
        val = vals.get_by_index(idx)
        if val.address != cs.validator_address:
            raise ErrInvalidSignature(f"address mismatch at index {idx}")
        counted = val.voting_power if cs.is_commit() else 0
        if timed:
            t0 = _time.perf_counter()
            msg = commit.vote_sign_bytes(chain_id, idx)
            sign_s += _time.perf_counter() - t0
        else:
            msg = commit.vote_sign_bytes(chain_id, idx)
        items.append((val.pub_key, msg, cs.signature, counted))
        running += counted
        if cut_at is not None and running > cut_at:
            break
    return items, sign_s


def _verify_commit(chain_id, vals, block_id, height, commit, backend,
                   light: bool, verify_all: bool) -> None:
    """verify_commit and verify_commit_light past the certificate case:
    basics, the commit's lanes (from its columns where commit_lanes()
    takes them, else slot by slot), every leg judged, the +2/3 tally."""
    with _trace.span("types.verify_commit", height=height,
                     **({"light": True} if light else {})) as root:
        _check_commit_basics(vals, commit, height, block_id)
        if len(vals) != commit.size():
            raise ErrInvalidCommitSize(
                f"validator set size {len(vals)} != commit size {commit.size()}"
            )
        threshold = vals.total_voting_power() * 2 // 3
        cut_at = threshold if light and not verify_all else None
        with _trace.span("types.commit_items") as sp:
            lanes = commit_lanes(chain_id, vals, commit, not light, cut_at)
            if not isinstance(lanes, str) and lanes.n < BATCH_VERIFY_THRESHOLD:
                lanes = "shape"  # nothing to batch
            if isinstance(lanes, str):
                crypto_metrics().commit_path_total.inc(1.0, "per_slot", lanes)
                sp.add(path="per_slot", reason=lanes)
                items, sign_s = _slot_items(
                    chain_id, vals, commit, light, cut_at)
                lanes, n = None, len(items)
            else:
                crypto_metrics().commit_path_total.inc(1.0, "columnar", "")
                sp.add(path="columnar")
                n, sign_s = lanes.n, lanes.sign_s
            sp.add(n=n, sign_bytes_ms=round(sign_s * 1e3, 3))
        root.add(n=n)
        if lanes is None:
            tally = _verify_items(items, backend)
        else:

            def item(lane: int):
                # only where a leg must judge a lane singly: the
                # per-slot code builds it
                slot = int(lanes.slots[lane])
                return (vals.members[slot].pub_key,
                        commit.vote_sign_bytes(chain_id, slot),
                        commit.signatures[slot].signature)

            _judge(*_fill_lanes(lanes, backend), item, backend)
            tally = lanes.power
        if tally <= threshold:
            raise ErrNotEnoughVotingPower(
                f"tallied {tally} <= threshold {threshold}")


def verify_commit(
    chain_id: str,
    vals: ValidatorSet,
    block_id: BlockID,
    height: int,
    commit: Commit,
    backend: str = "tpu",
) -> None:
    """Full verification: every non-absent signature checked
    (reference types/validation.go:21-53)."""
    from .agg_commit import CertCommit

    if isinstance(commit, CertCommit):
        return _verify_cert_commit(
            chain_id, vals, block_id, height, commit, backend=backend)
    _verify_commit(chain_id, vals, block_id, height, commit, backend,
                   light=False, verify_all=True)


def verify_commit_light(
    chain_id: str,
    vals: ValidatorSet,
    block_id: BlockID,
    height: int,
    commit: Commit,
    backend: str = "tpu",
    verify_all_signatures: bool = False,
) -> None:
    """Verify only COMMIT votes; succeed on +2/3
    (reference types/validation.go:61; AllSignatures variant :136)."""
    from .agg_commit import CertCommit

    if isinstance(commit, CertCommit):
        return _verify_cert_commit(
            chain_id, vals, block_id, height, commit, backend=backend)
    _verify_commit(chain_id, vals, block_id, height, commit, backend,
                   light=True, verify_all=verify_all_signatures)


def verify_commit_light_trusting(
    chain_id: str,
    vals: ValidatorSet,
    commit: Commit,
    trust_level: tuple[int, int] = (1, 3),
    backend: str = "tpu",
    verify_all_signatures: bool = False,
) -> None:
    """Trusted-set verification by address with fractional threshold
    (reference types/validation.go:125; AllSignatures variant :124 in
    evidence verify). Skips validators unknown to the trusted set; guards
    against double-counting a validator appearing at two indices.

    Stays slot by slot: the lookup is by address against ANOTHER set, so
    the commit's columns are in no order of `vals`' key columns
    (commit_lanes() needs the two aligned), and no cell times it."""
    num, den = trust_level
    if den <= 0 or num < 0 or num > den:
        raise ValueError("invalid trust level")
    threshold = vals.total_voting_power() * num // den
    seen: set[bytes] = set()
    items = []
    running = 0
    for idx, cs in enumerate(commit.signatures):
        if not cs.is_commit():
            continue
        _, val = vals.get_by_address(cs.validator_address)
        if val is None or val.address in seen:
            continue
        seen.add(val.address)
        items.append((val.pub_key, commit.vote_sign_bytes(chain_id, idx), cs.signature, val.voting_power))
        running += val.voting_power
        if not verify_all_signatures and running > threshold:
            break
    tally = _verify_items(items, backend)
    if tally <= threshold:
        raise ErrNotEnoughVotingPower(f"tallied {tally} <= threshold {threshold}")
