"""Batch generation of valid Ed25519 signatures using the device kernels.

Signing N distinct messages with the pure-Python oracle costs ~10ms each;
for bench/test datasets we instead run the *device* fixed-base ladder to
compute all A = [a]B and R = [r]B in one batch, then finish S = r + k*a
(mod L) host-side (cheap bignum ops). Signatures produced this way are
standard RFC 8032 signatures (r is random rather than derived — valid and
indistinguishable to a verifier).
"""

from __future__ import annotations

import hashlib

import numpy as np

from . import ed25519_ref as ref


def generate_signed_batch(n: int, seed: int = 0, msg_len: int = 120):
    """Returns list of (pubkey32, msg, sig64) with distinct keys/messages."""
    import jax
    import jax.numpy as jnp

    from ..ops import curve as C

    rng = np.random.default_rng(seed)
    a_sc = [int.from_bytes(rng.bytes(32), "little") % ref.L for _ in range(n)]
    r_sc = [int.from_bytes(rng.bytes(32), "little") % ref.L for _ in range(n)]
    msgs = [rng.bytes(msg_len) for _ in range(n)]

    @jax.jit
    def fixed_base_compress(digs):
        return C.compress(C.fixed_base(digs))

    # pad to the verify bucket: one compiled shape per bucket, and a
    # width the Pallas field kernels tile (an odd n would take the XLA
    # value-form on the chip)
    from .ed25519 import _bucket

    pad = [1] * (_bucket(n) - n)
    a_enc = np.asarray(fixed_base_compress(
        jnp.asarray(C.scalar_digits(a_sc + pad))))[:n]
    r_enc = np.asarray(fixed_base_compress(
        jnp.asarray(C.scalar_digits(r_sc + pad))))[:n]

    out = []
    for i in range(n):
        pub = a_enc[i].tobytes()
        r_b = r_enc[i].tobytes()
        k = int.from_bytes(hashlib.sha512(r_b + pub + msgs[i]).digest(), "little") % ref.L
        s = (r_sc[i] + k * a_sc[i]) % ref.L
        out.append((pub, bytes(msgs[i]), r_b + s.to_bytes(32, "little")))
    return out
