"""Batched Ed25519 ZIP-215 verification — the TPU data-plane kernel.

Per-lane cofactored verification: each lane checks
    [8]([S]B + [k](-A) - R) == identity
with liberal (ZIP-215) decoding of A and R. This is the device half of the
reference's batch verifier (reference: crypto/ed25519/ed25519.go:207-240,
types/validation.go:214 verifyCommitBatch); unlike the CPU random-linear-
combination trick, per-lane verification is embarrassingly parallel on TPU
lanes AND yields the per-signature validity bitmap that the commit-verify
fallback scan needs (reference: types/validation.go:304-311) for free.

The whole pipeline runs on device (round 2): SHA-512(R||A||M) via the
ops/sha512 kernel, k = digest mod L via Barrett (ops/scalar), signed-digit
recoding, ZIP-215 decompression, the shared-doubling ladder, and the
S < L range check. The host only packs fixed-shape byte arrays.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import curve as C
from . import field as F
from . import scalar as SC
from . import sha512 as H


def _digest_to_bytes(hi, lo):
    """(8, B) u32 big-endian word pairs -> (B, 64) digest bytes in
    hashlib order (byte i weighs 256^i in k)."""
    digest = []
    for w in range(8):
        for part in (hi, lo):
            v = part[w].astype(jnp.int32)
            digest.extend(
                [(v >> 24) & 0xFF, (v >> 16) & 0xFF, (v >> 8) & 0xFF, v & 0xFF]
            )
    return jnp.stack(digest, axis=-1).astype(jnp.uint8)


def verify_batch(a_bytes, r_bytes, s_bytes, msg_words, two_blocks, live):
    """Batched ZIP-215 verify, fully on device.

    a_bytes, r_bytes: (B, 32) uint8 — as-received A and R encodings.
    s_bytes:          (B, 32) uint8 — as-received S encodings.
    msg_words:        (B, 64) uint32 — SHA-512-padded R||A||M layout from
                      ops.sha512.pad_messages.
    two_blocks:       (B,) bool — per-lane 2-block flag from pad_messages.
    live:             (B,) bool — padding mask (False lanes report False).

    Returns (B,) bool validity bitmap.
    """
    # the phases are utils/trace.KERNEL_SCOPES: names on the operations,
    # for a profiler trace; the program is what it is without them
    with jax.named_scope("ladder.sha512"):
        hi, lo = H.sha512_two_blocks(msg_words, two_blocks)  # (8, B) u32, BE
        digest_bytes = _digest_to_bytes(hi, lo)  # (B, 64)

    with jax.named_scope("ladder.scalar_reduce"):
        k = SC.reduce512(digest_bytes)  # (22, B) canonical < L
        k_digits = SC.recode_signed(k)
        s_digits = SC.digits_from_bytes(s_bytes)
        s_ok = SC.lt_l(s_bytes)

    with jax.named_scope("ladder.decompress"):
        ok_a, a_pt = C.decompress(a_bytes)
        ok_r, r_pt = C.decompress(r_bytes)
    with jax.named_scope("ladder.double_scalar"):
        X, Y, Z = C.ladder_sub_mul8(s_digits, k_digits, C.neg(a_pt), r_pt)
    with jax.named_scope("ladder.compare"):
        ok_eq = F.is_zero(X) & F.eq(Y, Z)
        bits = ok_a & ok_r & ok_eq & s_ok & live
        # scalar summary: every LIVE lane verified (padding/oversize lanes
        # are excluded). Fetching this single bool instead of the bitmap
        # keeps the happy-path device→host transfer at pure round-trip
        # latency; the bitmap is only pulled when the summary says some
        # lane failed (reference types/validation.go:304 falls back to a
        # per-sig scan only when the batch verify fails).
        return bits, jnp.all(bits | ~live)


verify_batch_jit = jax.jit(verify_batch)


def verify_batch_prehashed(a_bytes, r_bytes, s_bytes, k_bytes, live):
    """Batched ZIP-215 verify with the challenge scalar computed host-side.

    k_bytes: (B, 32) uint8 little-endian canonical k = SHA-512(R||A||M)
    mod L, hashed on the host. Shipping the 32-byte scalar instead of the
    256-byte padded message block cuts host->device bytes 2.75x — on a
    bandwidth-limited link that transfer, not the curve math, bounds
    sustained throughput — and drops the on-device SHA-512 + Barrett
    stages entirely. The curve-side check is identical to verify_batch:
    [8]([S]B + [k](-A) - R) == identity with liberal decoding.
    """
    with jax.named_scope("ladder.scalar_reduce"):
        k_digits = SC.digits_from_bytes(k_bytes)
        s_digits = SC.digits_from_bytes(s_bytes)
        s_ok = SC.lt_l(s_bytes)
    with jax.named_scope("ladder.decompress"):
        ok_a, a_pt = C.decompress(a_bytes)
        ok_r, r_pt = C.decompress(r_bytes)
    with jax.named_scope("ladder.double_scalar"):
        X, Y, Z = C.ladder_sub_mul8(s_digits, k_digits, C.neg(a_pt), r_pt)
    with jax.named_scope("ladder.compare"):
        ok_eq = F.is_zero(X) & F.eq(Y, Z)
        bits = ok_a & ok_r & ok_eq & s_ok & live
        return bits, jnp.all(bits | ~live)


verify_batch_prehashed_jit = jax.jit(verify_batch_prehashed)


def decompress_pubkeys(a_bytes):
    """(B, 32) uint8 pubkey encodings -> (ok, negated extended point).

    The A half of the verification equation, split out so callers can
    keep a validator set's decompressed points resident on device: in
    commit replay the SAME pubkey column verifies every height, so the
    32 bytes/lane of A never need to re-cross the host->device link and
    the sqrt-decompression (one of the two per-lane exponentiations)
    runs once per validator-set change instead of once per commit."""
    with jax.named_scope("ladder.decompress"):
        ok_a, a_pt = C.decompress(a_bytes)
        return ok_a, C.neg(a_pt)


decompress_pubkeys_jit = jax.jit(decompress_pubkeys)


# delta-wire meta-array layout, shared by the host packer
# (crypto/ed25519._launch_device_delta) and the device unpacker
# (verify_batch_delta): [plen, slen, n_lo, n_mid, n_hi, pad*3,
# prefix[DELTA_PMAX], suffix[DELTA_PMAX]]
DELTA_META_HEADER = 8
DELTA_PMAX = 176  # >= MAX_INPUT_BYTES - 64 (max message length 175)
DELTA_META_LEN = DELTA_META_HEADER + 2 * DELTA_PMAX


def build_delta_msgs(a_enc, rs_mid, mlens, plen, slen, prefix, suffix):
    """Reconstruct the SHA-512-padded R||A||M blocks on device from a
    shared prefix/suffix plus per-lane delta bytes.

    Replay and commit verification hash messages that differ per lane
    only in a small middle section (the vote timestamp): the canonical
    sign-bytes prefix (type, height, round, block id) and suffix (chain
    id) are commit-invariant (types/block.py vote_sign_bytes cache).
    Shipping R||S plus the ~8-16 byte delta instead of a 32-byte
    host-hashed challenge scalar cuts the per-lane wire cost below 80
    bytes — on a bandwidth-limited host->device link that transfer is
    the throughput ceiling (PROFILE.md).

    a_enc:  (B, 32) uint8 pubkey encodings (device-resident cache).
    rs_mid: (B, 64 + MIDMAX) uint8 — R || S || mid bytes.
    mlens:  (B,) int32 — per-lane mid length.
    plen, slen: int32 scalars — shared prefix/suffix lengths (dynamic;
            the arrays are padded to a fixed max so jit keys only on
            the MIDMAX/bucket shapes).
    prefix, suffix: (PMAX,), (SMAX,) uint8 shared bytes.

    Returns (B, 64) uint32 big-endian padded words + (B,) two_blocks.
    """
    nbytes = H.PADDED_BYTES
    midmax = rs_mid.shape[1] - 64
    pos = jnp.arange(nbytes, dtype=jnp.int32)  # (256,)
    m_off = pos - 64
    mlens = mlens.astype(jnp.int32)
    total = plen + mlens + slen  # (B,) message length per lane
    head = jnp.concatenate([rs_mid[:, :32], a_enc], axis=1)  # (B,64) R||A
    head_b = jnp.take(head, jnp.clip(pos, 0, 63), axis=1).astype(jnp.int32)
    pfx_b = jnp.take(
        prefix, jnp.clip(m_off, 0, prefix.shape[0] - 1)
    ).astype(jnp.int32)
    mid_b = jnp.take(
        rs_mid[:, 64:], jnp.clip(m_off - plen, 0, midmax - 1), axis=1
    ).astype(jnp.int32)
    sfx_idx = m_off[None, :] - plen - mlens[:, None]  # (B, 256)
    sfx_b = jnp.take(
        suffix, jnp.clip(sfx_idx, 0, suffix.shape[0] - 1)
    ).astype(jnp.int32)
    b = jnp.where(
        m_off[None, :] < 0,
        head_b,
        jnp.where(
            m_off[None, :] < plen,
            pfx_b[None, :],
            jnp.where(
                m_off[None, :] < plen + mlens[:, None],
                mid_b,
                jnp.where(m_off[None, :] < total[:, None], sfx_b, 0),
            ),
        ),
    )
    # SHA-512 padding: 0x80 terminator + big-endian bit length at the
    # end of the last block (single block iff 64+total <= 111)
    b = jnp.where(pos[None, :] == 64 + total[:, None], 0x80, b)
    two = (64 + total) > 111
    blk = jnp.where(two, nbytes, nbytes // 2)
    bits = (64 + total) * 8  # < 2^16: two length bytes suffice
    b = jnp.where(pos[None, :] == blk[:, None] - 2, bits[:, None] >> 8, b)
    b = jnp.where(pos[None, :] == blk[:, None] - 1, bits[:, None] & 0xFF, b)
    words = (
        b.reshape(b.shape[0], H.PADDED_WORDS, 4).astype(jnp.uint32)
        @ jnp.asarray([1 << 24, 1 << 16, 1 << 8, 1], jnp.uint32)
    )
    return words, two


def verify_batch_delta(ok_a, neg_a, a_enc, packed, meta):
    """verify_batch with cached pubkeys AND device-side challenge
    hashing over reconstructed messages (build_delta_msgs).

    The wire is exactly TWO host arrays per submit — each device_put
    pays a fixed per-transfer cost (its size is unmeasured on today's
    machine), which is why the 96-byte path packs R||S||k into one
    array:
      packed: (B, 64 + MIDMAX + 1) uint8 — R || S || mid || mlen.
      meta:   (360,) uint8 — [plen, slen, n_lo, n_mid, n_hi, pad*3,
              prefix[176], suffix[176]]; live lanes derive from n.
    """
    rs_mid = packed[:, :-1]
    mlens = packed[:, -1]
    meta32 = meta.astype(jnp.int32)
    plen = meta32[0]
    slen = meta32[1]
    n = meta32[2] | (meta32[3] << 8) | (meta32[4] << 16)
    live = jnp.arange(packed.shape[0], dtype=jnp.int32) < n
    h = DELTA_META_HEADER
    prefix = meta[h : h + DELTA_PMAX]
    suffix = meta[h + DELTA_PMAX :]
    with jax.named_scope("ladder.sha512"):
        words, two = build_delta_msgs(
            a_enc, rs_mid, mlens, plen, slen, prefix, suffix
        )
        hi, lo = H.sha512_two_blocks(words, two)
        digest_bytes = _digest_to_bytes(hi, lo)
    with jax.named_scope("ladder.scalar_reduce"):
        k = SC.reduce512(digest_bytes)
        k_digits = SC.recode_signed(k)
        s_bytes = rs_mid[:, 32:64]
        s_digits = SC.digits_from_bytes(s_bytes)
        s_ok = SC.lt_l(s_bytes)
    with jax.named_scope("ladder.decompress"):
        ok_r, r_pt = C.decompress(rs_mid[:, :32])
    with jax.named_scope("ladder.double_scalar"):
        X, Y, Z = C.ladder_sub_mul8(s_digits, k_digits, neg_a, r_pt)
    with jax.named_scope("ladder.compare"):
        ok_eq = F.is_zero(X) & F.eq(Y, Z)
        bits = ok_a & ok_r & ok_eq & s_ok & live
        return bits, jnp.all(bits | ~live)


verify_batch_delta_jit = jax.jit(verify_batch_delta)


def verify_batch_cached_a(ok_a, neg_a, rsk, live):
    """verify_batch_prehashed with the pubkey stage precomputed by
    decompress_pubkeys (device-resident across submits).

    rsk: (B, 96) uint8 — R || S || k packed in one array so the
    per-commit host->device traffic is a single contiguous transfer
    (the link's fixed per-transfer cost matters at this rate)."""
    r_bytes = rsk[:, :32]
    s_bytes = rsk[:, 32:64]
    k_bytes = rsk[:, 64:]
    with jax.named_scope("ladder.scalar_reduce"):
        k_digits = SC.digits_from_bytes(k_bytes)
        s_digits = SC.digits_from_bytes(s_bytes)
        s_ok = SC.lt_l(s_bytes)
    with jax.named_scope("ladder.decompress"):
        ok_r, r_pt = C.decompress(r_bytes)
    with jax.named_scope("ladder.double_scalar"):
        X, Y, Z = C.ladder_sub_mul8(s_digits, k_digits, neg_a, r_pt)
    with jax.named_scope("ladder.compare"):
        ok_eq = F.is_zero(X) & F.eq(Y, Z)
        bits = ok_a & ok_r & ok_eq & s_ok & live
        return bits, jnp.all(bits | ~live)


verify_batch_cached_a_jit = jax.jit(verify_batch_cached_a)
