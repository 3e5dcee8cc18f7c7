"""DAS client: seeded random sampling against a header's da_root.

The availability argument: a block is reconstructable unless MORE than
m of the n = k+m extended chunks are unavailable (any k survivors
reconstruct). So an adversary hiding the data must withhold >= m+1
chunks, and a uniformly random sample then fails with probability
>= (m+1)/n. After s independent samples that ALL verify,
P(block actually unavailable) <= (1 - (m+1)/n)^s — the client's
confidence is one minus that. With the default k = m (rate-1/2
extension) each sample halves the doubt, so ~7 samples reach 99%.

Index draws are seeded (sha256 counter stream over
seed/client_id/height/da_root), so a fleet of clients is reproducible
end-to-end while still sampling independently per client.
"""

from __future__ import annotations

import hashlib
import math
import struct
from dataclasses import dataclass, field

from .commit import DACommitment, proof_num_bytes


def confidence_after(samples_ok: int, n: int, m: int) -> float:
    """P[reconstructable] lower bound after `samples_ok` verified
    samples of an n-chunk extension with parity budget m."""
    if n <= 0 or samples_ok <= 0:
        return 0.0
    p_hit = (m + 1) / n
    if p_hit >= 1.0:
        return 1.0
    return 1.0 - (1.0 - p_hit) ** samples_ok


def samples_for_confidence(target: float, n: int, m: int) -> int:
    """Smallest s with confidence_after(s, n, m) >= target."""
    if not 0.0 < target < 1.0:
        raise ValueError("confidence target must be in (0, 1)")
    p_hit = (m + 1) / n
    if p_hit >= 1.0:
        return 1
    return max(1, math.ceil(math.log(1.0 - target) / math.log(1.0 - p_hit)))


@dataclass
class SampleResult:
    height: int
    confident: bool  # reached the target with zero failures
    confidence: float  # achieved lower bound
    samples_ok: int = 0
    samples_failed: int = 0
    failed_indices: list = field(default_factory=list)
    proof_bytes: int = 0  # total wire bytes across this client's samples

    @property
    def detected_withholding(self) -> bool:
        return self.samples_failed > 0


class Sampler:
    """One light client's sampling loop.

    `fetch(height, index)` is the transport: it returns
    (chunk, proof, commitment-ish) or None (unavailable/withheld) —
    backed by the `da_sample` RPC route or an in-process DAServe.
    """

    def __init__(
        self,
        client_id: int,
        n: int,
        k: int,
        *,
        samples: int = 0,
        confidence: float = 0.99,
        seed: int = 0,
    ):
        self.client_id = client_id
        self.n = n
        self.k = k
        self.m = n - k
        self.confidence_target = confidence
        self.samples = samples or samples_for_confidence(
            confidence, n, self.m
        )
        self.seed = seed

    def indices(self, height: int, da_root: bytes) -> list[int]:
        """Seeded draw of `samples` indices in [0, n) — deterministic
        per (seed, client, height, root), uniform via rejection."""
        out: list[int] = []
        ctr = 0
        base = hashlib.sha256(
            struct.pack(">QQQ", self.seed, self.client_id, height) + da_root
        ).digest()
        limit = (1 << 32) - ((1 << 32) % self.n)
        while len(out) < self.samples:
            block = hashlib.sha256(
                base + struct.pack(">Q", ctr)
            ).digest()
            ctr += 1
            for off in range(0, 32, 4):
                v = int.from_bytes(block[off:off + 4], "big")
                if v < limit:
                    out.append(v % self.n)
                    if len(out) == self.samples:
                        break
        return out

    def verify_sample(
        self, com: DACommitment, da_root: bytes, index: int,
        chunk: bytes, proof,
    ) -> bool:
        """One opening proof checked end-to-end: geometry matches the
        header root, chunk hash sits at `index` under chunks_root."""
        return com.root() == da_root and com.verify_sample(
            index, chunk, proof
        )

    def run(self, height: int, da_root: bytes, fetch) -> SampleResult:
        ok = 0
        failed: list[int] = []
        nbytes = 0
        for index in self.indices(height, da_root):
            got = fetch(height, index)
            if got is None:
                failed.append(index)
                continue
            chunk, proof, com = got
            if not self.verify_sample(com, da_root, index, chunk, proof):
                failed.append(index)
                continue
            ok += 1
            nbytes += proof_num_bytes(chunk, proof)
        conf = confidence_after(ok, self.n, self.m)
        return SampleResult(
            height=height,
            confident=not failed and conf >= self.confidence_target,
            confidence=conf,
            samples_ok=ok,
            samples_failed=len(failed),
            failed_indices=failed,
            proof_bytes=nbytes,
        )


@dataclass
class PCSampleResult:
    """One client's verdict on the 2D polynomial-commitment track."""

    height: int
    confident: bool  # target confidence, zero failures, parity holds
    confidence: float
    commitments_ok: bool = True  # the parity-linearity check
    samples_ok: int = 0
    samples_failed: int = 0
    failed_cols: list = field(default_factory=list)
    proof_bytes: int = 0  # multiproof response bytes (evals + proof)
    commitment_bytes: int = 0  # once-per-height commitment download

    @property
    def detected_withholding(self) -> bool:
        return self.samples_failed > 0 or not self.commitments_ok


class PCSampler:
    """One light client's sampling loop on the 2D KZG track.

    A sample is one (row, s distinct columns) draw answered by s
    32-byte evaluations plus ONE 48-byte multiproof. Availability math
    is the column dimension's: withholding enough to block column
    reconstruction means hiding >= m_c + 1 of n_c columns, so each
    sampled column hits with probability >= (m_c + 1)/n_c. Columns are
    drawn DISTINCT, which only raises the detection probability over
    the with-replacement bound `confidence_after` computes — the
    reported confidence stays a valid lower bound.

    Before any sample counts, the client runs the once-per-height
    lying-encoder check (`pc.verify_commitments`): parity commitments
    must be the Lagrange combination of the data commitments. The 1D
    track has no analogue — a Merkle root over garbage parity shards
    verifies every opening (the pinned blindness test).

    `fetch(height, row, cols)` is the transport: (ys, proof) or None —
    backed by the `da_pc_sample` RPC route or an in-process DAServe.
    When an aggregated fetch comes back None the client re-probes the
    columns one at a time, so `failed_cols` names the withheld columns
    instead of the whole draw.
    """

    def __init__(
        self,
        client_id: int,
        n_c: int,
        k_c: int,
        n_r: int,
        *,
        samples: int = 0,
        confidence: float = 0.99,
        seed: int = 0,
    ):
        self.client_id = client_id
        self.n_c = n_c
        self.k_c = k_c
        self.m_c = n_c - k_c
        self.n_r = n_r
        self.confidence_target = confidence
        self.samples = min(
            n_c,
            samples or samples_for_confidence(confidence, n_c, self.m_c),
        )
        self.seed = seed

    def draw(self, height: int, pc_root: bytes) -> tuple[int, list[int]]:
        """Seeded (row, distinct columns) draw — deterministic per
        (seed, client, height, root), uniform via rejection."""
        base = hashlib.sha256(
            b"pc" + struct.pack(
                ">QQQ", self.seed, self.client_id, height) + pc_root
        ).digest()
        row_limit = (1 << 32) - ((1 << 32) % self.n_r)
        col_limit = (1 << 32) - ((1 << 32) % self.n_c)
        row = None
        cols: list[int] = []
        seen: set[int] = set()
        ctr = 0
        while row is None or len(cols) < self.samples:
            block = hashlib.sha256(
                base + struct.pack(">Q", ctr)).digest()
            ctr += 1
            for off in range(0, 32, 4):
                v = int.from_bytes(block[off:off + 4], "big")
                if row is None:
                    if v < row_limit:
                        row = v % self.n_r
                    continue
                if v >= col_limit:
                    continue
                c = v % self.n_c
                if c not in seen:
                    seen.add(c)
                    cols.append(c)
                    if len(cols) == self.samples:
                        break
        return row, cols

    def run(self, height: int, pc_root: bytes, com, fetch
            ) -> PCSampleResult:
        from . import pc as pcmod

        com_bytes = com.num_bytes()
        if com.root() != pc_root:
            return PCSampleResult(
                height=height, confident=False, confidence=0.0,
                commitments_ok=False, commitment_bytes=com_bytes,
            )
        commitments_ok = pcmod.verify_commitments(com)
        row, cols = self.draw(height, pc_root)
        ok = 0
        failed: list[int] = []
        nbytes = 0
        got = fetch(height, row, cols)
        if got is not None:
            ys, proof = got
            if pcmod.verify_sample(com, pc_root, row, cols, ys, proof):
                ok = len(cols)
                nbytes = pcmod.multiproof_num_bytes(len(cols))
            else:
                failed = list(cols)
        else:
            # aggregated draw refused: probe per column for attribution
            for c in cols:
                one = fetch(height, row, [c])
                if one is None:
                    failed.append(c)
                    continue
                ys, proof = one
                if pcmod.verify_sample(
                    com, pc_root, row, [c], ys, proof
                ):
                    ok += 1
                    nbytes += pcmod.multiproof_num_bytes(1)
                else:
                    failed.append(c)
        conf = confidence_after(ok, self.n_c, self.m_c)
        return PCSampleResult(
            height=height,
            confident=(commitments_ok and not failed
                       and conf >= self.confidence_target),
            confidence=conf,
            commitments_ok=commitments_ok,
            samples_ok=ok,
            samples_failed=len(failed),
            failed_cols=failed,
            proof_bytes=nbytes,
            commitment_bytes=com_bytes,
        )
