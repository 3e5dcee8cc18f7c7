"""Node-side DA serving surface.

`DAServe` rides the same commit-time event-handler hook as the light
MMR accumulator (`BlockExecutor.event_handlers`): every applied block's
payload is RS-extended, committed, and retained for the last
`retain_heights` heights so samplers can fetch (chunk, opening proof)
pairs through the `da_sample` RPC route or the `/light_stream` payload
extension. It doubles as the proposal/validation encoder: the executor
asks it for `da_root_for(data)` when building a proposal and when
checking a peer's header.

An explicit withholding knob (`set_withholding`) exists for the
adversarial workload: a byzantine proposer that advertises a root but
refuses to serve some chunks. Samplers hitting a withheld index get
None — exactly the observable a DAS client turns into a
detection/alarm (tools/dasload.py drives a fleet against it).
"""

from __future__ import annotations

import threading
from collections import OrderedDict

from ..utils import trace
from ..utils.metrics import da_metrics
from . import pc as pcmod
from .commit import (
    DACommitment,
    block_payload,
    combined_root,
    commit_shards,
    extend_payload,
    proof_num_bytes,
)


class _HeightEntry:
    __slots__ = ("commitment", "shards", "proofs", "da_root", "pc")

    def __init__(self, commitment, shards, proofs, pc=None):
        self.commitment = commitment
        self.shards = shards
        self.proofs = proofs
        self.pc = pc  # PCEncoding when the 2D KZG track is on
        root = commitment.root()
        self.da_root = (root if pc is None
                        else combined_root(root, pc.com.root()))


class DAServe:
    def __init__(self, cfg):
        """`cfg` is the validated `config.DAConfig`."""
        self.cfg = cfg
        self.k = cfg.data_shards
        self.m = cfg.parity_shards
        self.pc_enabled = bool(getattr(cfg, "pc", False))
        self.pc_k_c = getattr(cfg, "pc_data_cols", 4)
        self.pc_m_c = getattr(cfg, "pc_parity_cols", 4)
        self.pc_max_rows = getattr(cfg, "pc_max_rows", 1024)
        self._lock = threading.Lock()
        self._heights: OrderedDict[int, _HeightEntry] = OrderedDict()
        self._withhold: dict[int, set[int]] = {}
        self._pc_withhold: dict[int, set[int]] = {}
        self._encoded = 0
        self._served = 0
        self._withheld_hits = 0
        self._pc_served = 0
        self._pc_withheld_hits = 0
        self._pc_skipped_rows = 0
        self.metrics = da_metrics()

    # --------------------------------------------------------- encoder side
    def da_root_for(self, data) -> bytes:
        """Root for a proposal's Data (also used to validate a peer's
        header against locally re-encoded chunks)."""
        payload = block_payload(data)
        shards = extend_payload(payload, self.k, self.m)
        com, _ = commit_shards(shards, self.k, len(payload))
        root = com.root()
        enc = self._pc_encode(payload)
        if enc is not None:
            return combined_root(root, enc.com.root())
        return root

    def _pc_encode(self, payload: bytes):
        """The 2D KZG encoding for one payload, or None when the track
        is off / the payload exceeds the row budget (a commitment per
        column is cheap; the SRS and opening costs scale with rows)."""
        if not self.pc_enabled:
            return None
        if pcmod.grid_rows(len(payload), self.pc_k_c) > self.pc_max_rows:
            with self._lock:
                self._pc_skipped_rows += 1
            return None
        enc = pcmod.pc_encode(payload, self.pc_k_c, self.pc_m_c)
        self.metrics.pc_commits_total.inc()
        return enc

    def on_commit(self, block, resp=None) -> None:
        """Commit-time hook (same contract as LightServe.on_commit):
        extend + commit + retain the applied block's payload."""
        self.apply_payload(block.header.height, block_payload(block.data))

    def apply_payload(self, height: int, payload: bytes) -> _HeightEntry:
        """Extend + commit + retain one height's raw payload. The RS
        extension and the shard commitment are deterministic, so a
        serving replica applying the payload off the replication feed
        rebuilds the commitment, shards and opening proofs byte-exactly
        (the feed carries the 1x systematic payload, not the 2x shard
        set). Returns the retained entry so callers can cross-check
        `entry.da_root` against an advertised root."""
        with trace.span(
            "da.encode", height=height, bytes=len(payload)
        ) as sp:
            shards = extend_payload(payload, self.k, self.m)
            com, proofs = commit_shards(shards, self.k, len(payload))
            sp.add(shards=com.n, shard_bytes=len(shards[0]))
        entry = _HeightEntry(com, shards, proofs,
                             pc=self._pc_encode(payload))
        with self._lock:
            self._heights[height] = entry
            self._encoded += 1
            while len(self._heights) > self.cfg.retain_heights:
                h, _ = self._heights.popitem(last=False)
                self._withhold.pop(h, None)
                self._pc_withhold.pop(h, None)
        return entry

    # --------------------------------------------------------- serving side
    def set_withholding(self, height: int, indices) -> None:
        """Adversarial harness: refuse to serve `indices` at `height`."""
        with self._lock:
            self._withhold[height] = set(indices)

    def set_pc_withholding(self, height: int, cols) -> None:
        """Adversarial harness, 2D track: refuse any multiproof sample
        touching one of `cols` at `height`."""
        with self._lock:
            self._pc_withhold[height] = set(cols)

    def corrupt_pc_parity(self, height: int, seed: int = 0) -> bool:
        """Adversarial harness: swap in the lying-encoder world —
        honest commitments over garbage parity columns, every opening
        still verifying (da/pc.py make_inconsistent). The entry's
        da_root IS recomputed: this models a proposer that built and
        advertised the block with garbage parity from the start, so
        every opening a sampler draws verifies against the advertised
        commitments and ONLY the parity-linearity check
        (`pc.verify_commitments`) catches it — the world the 2D design
        exists for."""
        with self._lock:
            entry = self._heights.get(height)
        if entry is None or entry.pc is None:
            return False
        entry.pc = pcmod.make_inconsistent(entry.pc, seed)
        entry.da_root = combined_root(
            entry.commitment.root(), entry.pc.com.root())
        return True

    def stream_fields(self, height: int) -> dict:
        """/light_stream payload extension for one height ({} when the
        height is not retained — e.g. DA enabled mid-run)."""
        with self._lock:
            entry = self._heights.get(height)
        if entry is None:
            return {}
        com = entry.commitment
        out = {
            "da_root": entry.da_root.hex(),
            "da_shards": com.n,
            "da_data_shards": com.k,
            "da_payload_len": com.payload_len,
        }
        if entry.pc is not None:
            pcc = entry.pc.com
            out["da_pc_root"] = pcc.root().hex()
            out["da_pc_rows"] = pcc.n_r
            out["da_pc_cols"] = pcc.n_c
            out["da_pc_data_cols"] = pcc.k_c
        return out

    def sample(self, height: int, index: int):
        """(chunk, Proof, DACommitment) for one sampled index, or None
        when the height is unknown / the index is withheld."""
        with self._lock:
            entry = self._heights.get(height)
            withheld = self._withhold.get(height, ())
        if entry is None or not (0 <= index < entry.commitment.n):
            return None
        if index in withheld:
            with self._lock:
                self._withheld_hits += 1
            return None
        chunk = entry.shards[index]
        proof = entry.proofs[index]
        nbytes = proof_num_bytes(chunk, proof)
        self.metrics.samples_served_total.inc()
        self.metrics.proof_bytes.observe(nbytes)
        with self._lock:
            self._served += 1
        return chunk, proof, entry.commitment

    def pc_sample(self, height: int, row: int, cols):
        """(ys, proof48) answering one multiproof sample — `cols` are
        the client's sampled column indices, all opened at `row` by a
        single aggregated proof. None when the height is unknown, the
        track is off for it, the geometry is out of range, or any
        requested column is withheld."""
        with self._lock:
            entry = self._heights.get(height)
            withheld = self._pc_withhold.get(height, ())
        if entry is None or entry.pc is None:
            return None
        com = entry.pc.com
        cols = list(cols)
        if not cols or not (0 <= row < com.n_r):
            return None
        if any(not (0 <= j < com.n_c) for j in cols):
            return None
        if any(j in withheld for j in cols):
            with self._lock:
                self._pc_withheld_hits += 1
            return None
        nbytes = pcmod.multiproof_num_bytes(len(cols))
        ys, proof = entry.pc.open_row_cols(row, cols)
        self.metrics.pc_samples_served_total.inc()
        self.metrics.pc_proof_bytes.observe(nbytes)
        with self._lock:
            self._pc_served += 1
        return ys, proof

    def pc_commitments(self, height: int):
        """The height's PCCommitment (geometry + per-column KZG
        commitment list), or None off-track."""
        with self._lock:
            entry = self._heights.get(height)
        return entry.pc.com if entry is not None and entry.pc else None

    def commitment(self, height: int) -> DACommitment | None:
        with self._lock:
            entry = self._heights.get(height)
        return entry.commitment if entry is not None else None

    def shards(self, height: int) -> list[bytes] | None:
        with self._lock:
            entry = self._heights.get(height)
        return list(entry.shards) if entry is not None else None

    def stats(self) -> dict:
        with self._lock:
            heights = list(self._heights)
            return {
                "enabled": True,
                "data_shards": self.k,
                "parity_shards": self.m,
                "retained_heights": len(heights),
                "min_height": heights[0] if heights else 0,
                "max_height": heights[-1] if heights else 0,
                "blocks_encoded": self._encoded,
                "samples_served": self._served,
                "withheld_hits": self._withheld_hits,
                "pc_enabled": self.pc_enabled,
                "pc_samples_served": self._pc_served,
                "pc_withheld_hits": self._pc_withheld_hits,
                "pc_skipped_rows": self._pc_skipped_rows,
            }

    def stop(self) -> None:
        with self._lock:
            self._heights.clear()
            self._withhold.clear()
            self._pc_withhold.clear()
