"""MMR header accumulator tests (light/mmr.py).

Covers the ISSUE gates: incremental append vs from-scratch rebuild
bit-exact, proof verify on accept AND reject, peak-bagging edge sizes
1/2/3/2^k/2^k±1, wire round-trip, snapshot binding, persistence, and
the O(log n) proof-size bound (bytes <= 96*log2(n)) for n in
{1k, 50k, 1M} — the 1M point uses synthetically-built structurally
correct proofs so tier-1 never hashes two million nodes.
"""

import hashlib
import math

import pytest

from cometbft_tpu.light import mmr as m
from cometbft_tpu.light import verify_ancestry
from cometbft_tpu.light.mmr import MMR, MMRProof, peak_heights, peak_positions
from cometbft_tpu.light.store import MMRStore
from cometbft_tpu.storage import MemKV

PROOF_SIZE_C = 96  # bytes per log2(n): the bound the proof-size tests hold


def _leaves(n, tag=b"hdr"):
    return [hashlib.sha256(tag + i.to_bytes(8, "big")).digest()
            for i in range(n)]


EDGE_SIZES = sorted(
    {1, 2, 3}
    | {1 << k for k in range(2, 9)}
    | {(1 << k) - 1 for k in range(2, 9)}
    | {(1 << k) + 1 for k in range(2, 9)}
)


def test_incremental_vs_rebuild_bit_exact():
    leaves = _leaves(max(EDGE_SIZES))
    inc = MMR()
    for n in range(1, max(EDGE_SIZES) + 1):
        idx = inc.append(leaves[n - 1])
        assert idx == n - 1
        if n in EDGE_SIZES:
            fresh = MMR.from_leaves(leaves[:n])
            assert inc.node_count == fresh.node_count, n
            assert [inc.node(p) for p in range(inc.node_count)] == [
                fresh.node(p) for p in range(fresh.node_count)
            ], f"node array diverges at n={n}"
            assert inc.root() == fresh.root(), n


@pytest.mark.parametrize("n", EDGE_SIZES)
def test_peak_structure_edge_sizes(n):
    assert peak_heights(n) == sorted(
        (h for h in range(n.bit_length()) if (n >> h) & 1), reverse=True
    )
    assert len(peak_positions(n)) == bin(n).count("1")
    acc = MMR.from_leaves(_leaves(n))
    # node count of an MMR: 2n - popcount(n)
    assert acc.node_count == 2 * n - bin(n).count("1")
    assert acc.peaks() == [acc.node(p) for p in peak_positions(n)]


@pytest.mark.parametrize("n", [1, 2, 3, 4, 7, 8, 9, 16, 17, 33])
def test_proof_accept_every_leaf(n):
    leaves = _leaves(n)
    acc = MMR.from_leaves(leaves)
    root = acc.root()
    for i in range(n):
        proof = acc.prove(i)
        assert proof.verify(root, leaves[i]), (n, i)


def test_proof_reject():
    leaves = _leaves(9)
    acc = MMR.from_leaves(leaves)
    root = acc.root()
    proof = acc.prove(4)
    # wrong leaf hash
    assert not proof.verify(root, leaves[5])
    # wrong root
    assert not proof.verify(hashlib.sha256(b"x").digest(), leaves[4])
    # truncated / padded path fails the structural shape check
    cut = MMRProof(4, 9, proof.path[:-1], proof.left_peaks,
                   proof.right_peaks)
    assert not cut.verify(root, leaves[4])
    fat = MMRProof(4, 9, proof.path + [(bytes(32), False)],
                   proof.left_peaks, proof.right_peaks)
    assert not fat.verify(root, leaves[4])
    # wrong peak count
    nopeak = MMRProof(4, 9, proof.path, [], [])
    assert not nopeak.verify(root, leaves[4])
    # flipped sibling direction changes the folded peak
    if proof.path:
        sib, is_left = proof.path[0]
        flipped = MMRProof(4, 9, [(sib, not is_left)] + proof.path[1:],
                           proof.left_peaks, proof.right_peaks)
        assert not flipped.verify(root, leaves[4])
    # out-of-range index
    assert not MMRProof(9, 9, [], [], []).verify(root, leaves[0])


def test_proof_bound_to_snapshot():
    """The root commits the leaf count: a proof minted at size 8 must
    not verify against the grown (or shrunk) accumulator's root."""
    leaves = _leaves(12)
    acc = MMR.from_leaves(leaves[:8])
    proof8 = acc.prove(3)
    root8 = acc.root()
    assert proof8.verify(root8, leaves[3])
    for lh in leaves[8:]:
        acc.append(lh)
    assert not proof8.verify(acc.root(), leaves[3])
    # and a current proof fails against the old root
    assert not acc.prove(3).verify(root8, leaves[3])


def test_encode_decode_roundtrip():
    leaves = _leaves(33)
    acc = MMR.from_leaves(leaves)
    root = acc.root()
    for i in (0, 1, 15, 16, 31, 32):
        proof = acc.prove(i)
        buf = proof.encode()
        back = MMRProof.decode(buf)
        assert back == proof
        assert back.verify(root, leaves[i])
        assert proof.num_bytes() == len(buf)
    with pytest.raises(ValueError):
        MMRProof.decode(buf + b"\x00")
    with pytest.raises(Exception):
        MMRProof.decode(b"\x01\x02")


def test_verify_ancestry_helper():
    leaves = _leaves(10)
    acc = MMR.from_leaves(leaves)
    root, size, base = acc.root(), acc.leaf_count, 5  # heights 5..14
    proof = acc.prove(3)  # height 8
    assert verify_ancestry(root, size, base, 8, leaves[3], proof)
    assert verify_ancestry(root, size, base, 8, leaves[3], proof.encode())
    # wrong height -> leaf index mismatch
    assert not verify_ancestry(root, size, base, 9, leaves[3], proof)
    # size mismatch vs proof snapshot
    assert not verify_ancestry(root, size + 1, base, 8, leaves[3], proof)
    # undecodable bytes
    assert not verify_ancestry(root, size, base, 8, leaves[3], b"junk")


# -- O(log n) proof-size gate -------------------------------------------


def _max_proof_bytes(acc: MMR, sample: int = 512) -> int:
    n = acc.leaf_count
    step = max(1, n // sample)
    idxs = set(range(0, n, step)) | {0, 1, n - 1, n // 2}
    return max(acc.prove(i).num_bytes() for i in idxs)


@pytest.mark.parametrize("n", [1000, 50_000])
def test_proof_size_log_bound_real(n):
    acc = MMR.from_leaves(_leaves(n))
    bound = PROOF_SIZE_C * math.log2(n)
    worst = _max_proof_bytes(acc)
    assert worst <= bound, f"n={n}: {worst} B > {bound:.1f} B"


def _synthetic_proof(n: int, leaf_index: int):
    """Structurally correct proof for a size-n snapshot with dummy
    sibling/peak hashes, plus the matching root — exercises the exact
    wire size without materializing 2n-popcount(n) nodes."""
    leaf_hash = hashlib.sha256(b"leaf").digest()
    heights = peak_heights(n)
    first = 0
    for k, h in enumerate(heights):
        span = 1 << h
        if leaf_index < first + span:
            mk, mh, local = k, h, leaf_index - first
            break
        first += span
    node = m._leaf(leaf_hash)
    path = []
    for i in range(mh):
        sib = hashlib.sha256(b"sib%d" % i).digest()
        is_left = bool((local >> i) & 1)
        path.append((sib, is_left))
        node = m._inner(sib, node) if is_left else m._inner(node, sib)
    pk = [hashlib.sha256(b"peak%d" % k).digest() for k in range(len(heights))]
    left, right = pk[:mk], pk[mk + 1:]
    root = m._bag([*left, node, *right], n)
    return MMRProof(leaf_index, n, path, left, right), root, leaf_hash


@pytest.mark.parametrize("n", [1_000_000, (1 << 20) - 1, (1 << 20) + 1])
def test_proof_size_log_bound_synthetic_1m(n):
    bound = PROOF_SIZE_C * math.log2(n)
    # leaf 0 sits in the tallest (first) mountain: the longest path
    for idx in (0, n - 1, n // 2):
        proof, root, leaf_hash = _synthetic_proof(n, idx)
        assert proof.verify(root, leaf_hash)
        got = proof.num_bytes()
        assert got <= bound, f"n={n} leaf={idx}: {got} B > {bound:.1f} B"
        assert MMRProof.decode(proof.encode()) == proof


# -- persistence ---------------------------------------------------------


def test_mmr_store_write_through_reload_bit_exact():
    db = MemKV()
    store = MMRStore(db)
    leaves = _leaves(21)
    acc = MMR(store=store)
    for lh in leaves:
        acc.append(lh)
    store.save_base_height(100)

    back = MMR.load(MMRStore(db))
    assert back.leaf_count == acc.leaf_count
    assert back.node_count == acc.node_count
    assert [back.node(p) for p in range(back.node_count)] == [
        acc.node(p) for p in range(acc.node_count)
    ]
    assert back.root() == acc.root()
    assert MMRStore(db).load_base_height() == 100
    # reloaded accumulator keeps appending write-through
    back.append(hashlib.sha256(b"more").digest())
    again = MMR.load(MMRStore(db))
    assert again.leaf_count == 22
    assert again.root() == back.root()


def test_mmr_store_empty_and_prefix_consistency():
    store = MMRStore(MemKV())
    assert store.node_count() == 0
    assert store.load_nodes() == (0, [])
    assert store.load_base_height() is None
    # size record written after nodes: every stored prefix is a valid MMR
    acc = MMR(store=store)
    for lh in _leaves(5):
        acc.append(lh)
    leaf_count, nodes = store.load_nodes()
    assert leaf_count == 5
    assert nodes == [acc.node(p) for p in range(acc.node_count)]
