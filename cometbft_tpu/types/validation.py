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
            sp.add(groups=len(groups),
                   singles=sum(len(v) for v in singles.values()))
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
                rows = [(items[i][0].bytes(), items[i][1], items[i][2])
                        for i in idxs]
                if threaded:
                    leg.pending = _secp.submit_many(rows)
                else:
                    bits = _secp.verify_many(rows)
                    leg.verdict = (all(bits), bits)
            else:
                leg = _Leg(tag, "single", idxs)
                bits = [items[i][0].verify_signature(
                    items[i][1], items[i][2]) for i in idxs]
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
            pc0 = None
            if leg.tag == _BLS_TAG:
                from ..crypto import bls as _bls

                pc0 = _bls.pairing_checks()
                t0 = _time.perf_counter()
            ok, bits = leg.resolve()
            if pc0 is not None and _trace.enabled:
                # the whole BLS partition collapsed into aggregate
                # pairing check(s): 1 on accept, +n rescan on blame
                _trace.emit("crypto.bls_aggregate", "span",
                            dur_ms=round(
                                (_time.perf_counter() - t0) * 1e3, 3),
                            n=len(leg.idxs),
                            pairing_checks=_bls.pairing_checks() - pc0)
            if ok:
                continue
            if bits:
                # the bitmap pinpoints failures directly — no rescan
                bad.extend(j for j, b in zip(leg.idxs, bits) if not b)
                continue
            # batch could not localize: fall back to single verification
            # like the reference (:327). If every signature passes singly,
            # the commit is valid — accept.
            bad.extend(j for j in leg.idxs
                       if not items[j][0].verify_signature(
                           items[j][1], items[j][2]))
        if bad:
            raise ErrInvalidSignature(
                f"invalid signature at index {min(bad)}")
    else:
        for i, (pub, msg, sig, _) in enumerate(items):
            if not pub.verify_signature(msg, sig):
                raise ErrInvalidSignature(f"invalid signature at index {i}")
    return sum(p for _, _, _, p in items)


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
    from ..crypto import bls as _bls
    from ..crypto.sched import current_context

    _check_commit_basics(vals, commit, height, block_id)
    if len(vals) != commit.size():
        raise ErrInvalidCommitSize(
            f"validator set size {len(vals)} != commit size {commit.size()}"
        )
    bv = CertCommitVerifier(chain_id, vals, commit)
    ctx = current_context()
    t0 = _time.perf_counter()
    pc0 = _bls.pairing_checks()
    if ctx is not None:
        ok, _bits = ctx.submit(bv).result()
    else:
        ok, _bits = bv.verify()
    dt = _time.perf_counter() - t0
    if _trace.enabled:
        _trace.emit("crypto.bls_aggregate", "span",
                    dur_ms=round(dt * 1e3, 3), n=commit.signer_count(),
                    pairing_checks=_bls.pairing_checks() - pc0)
    _observe_partition(_BLS_TAG, "aggregate", dt)
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
    with _trace.span("types.verify_commit", height=height) as root:
        _check_commit_basics(vals, commit, height, block_id)
        if len(vals) != commit.size():
            raise ErrInvalidCommitSize(
                f"validator set size {len(vals)} != commit size {commit.size()}"
            )
        items = []
        # per-lane time is never a span: accumulated under a local flag
        timed = _trace.enabled
        sign_s = 0.0
        with _trace.span("types.commit_items") as sp:
            for idx, cs in enumerate(commit.signatures):
                if cs.is_absent():
                    continue
                val = vals.get_by_index(idx)
                if val.address != cs.validator_address:
                    raise ErrInvalidSignature(
                        f"address mismatch at index {idx}"
                    )
                counted = val.voting_power if cs.is_commit() else 0
                if timed:
                    t0 = _time.perf_counter()
                    msg = commit.vote_sign_bytes(chain_id, idx)
                    sign_s += _time.perf_counter() - t0
                else:
                    msg = commit.vote_sign_bytes(chain_id, idx)
                items.append((val.pub_key, msg, cs.signature, counted))
            sp.add(n=len(items), sign_bytes_ms=round(sign_s * 1e3, 3))
        root.add(n=len(items))
        tally_power = _verify_items(items, backend)
        threshold = vals.total_voting_power() * 2 // 3
        if tally_power <= threshold:
            raise ErrNotEnoughVotingPower(
                f"tallied {tally_power} <= threshold {threshold}"
            )


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
    with _trace.span("types.verify_commit", height=height,
                     light=True) as root:
        _check_commit_basics(vals, commit, height, block_id)
        if len(vals) != commit.size():
            raise ErrInvalidCommitSize(
                f"validator set size {len(vals)} != commit size {commit.size()}"
            )
        items = []
        threshold = vals.total_voting_power() * 2 // 3
        running = 0
        timed = _trace.enabled
        sign_s = 0.0
        with _trace.span("types.commit_items") as sp:
            for idx, cs in enumerate(commit.signatures):
                if not cs.is_commit():
                    continue
                val = vals.get_by_index(idx)
                if val.address != cs.validator_address:
                    raise ErrInvalidSignature(
                        f"address mismatch at index {idx}")
                if timed:
                    t0 = _time.perf_counter()
                    msg = commit.vote_sign_bytes(chain_id, idx)
                    sign_s += _time.perf_counter() - t0
                else:
                    msg = commit.vote_sign_bytes(chain_id, idx)
                items.append(
                    (val.pub_key, msg, cs.signature, val.voting_power))
                running += val.voting_power
                if not verify_all_signatures and running > threshold:
                    break
            sp.add(n=len(items), sign_bytes_ms=round(sign_s * 1e3, 3))
        root.add(n=len(items))
        tally = _verify_items(items, backend)
        if tally <= threshold:
            raise ErrNotEnoughVotingPower(
                f"tallied {tally} <= threshold {threshold}")


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
    against double-counting a validator appearing at two indices."""
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
