"""Differential tests for ops/scalar.py against Python big ints."""

import numpy as np
import jax
import jax.numpy as jnp

from cometbft_tpu.ops import scalar as S
from cometbft_tpu.ops import field as F

rng = np.random.default_rng(7)


def _rand_bytes(n, width):
    return rng.integers(0, 256, (n, width), dtype=np.uint8)


def _int_le(row):
    return int.from_bytes(bytes(row.tolist()), "little")


def test_lt_l():
    b = _rand_bytes(16, 32)
    vals = [0, S.L_INT - 1, S.L_INT, S.L_INT + 1, (1 << 256) - 1]
    for i, v in enumerate(vals):
        b[i] = np.frombuffer(v.to_bytes(32, "little"), np.uint8)
    out = np.asarray(jax.jit(S.lt_l)(jnp.asarray(b)))
    for lane in range(16):
        assert bool(out[lane]) == (_int_le(b[lane]) < S.L_INT), f"lane {lane}"


def test_recode_signed_roundtrip():
    b = _rand_bytes(32, 32)
    b[:, 31] &= 0x1F  # < 2^253: the post-reduction / valid-S domain
    b[0] = 0
    b[1] = np.frombuffer((S.L_INT - 1).to_bytes(32, "little"), np.uint8)
    digits = np.asarray(jax.jit(S.digits_from_bytes)(jnp.asarray(b)))
    assert digits.min() >= -8 and digits.max() <= 7
    for lane in range(32):
        val = sum(int(digits[i, lane]) * (16 ** i) for i in range(64))
        assert val == _int_le(b[lane]), f"lane {lane}"


def test_recode_signed_from_limbs():
    vals = [0, 1, S.L_INT - 1, (1 << 252) + 12345]
    limbs = np.stack([np.asarray(F.from_int(v)) for v in vals], axis=1)
    digits = np.asarray(jax.jit(S.recode_signed)(jnp.asarray(limbs)))
    for lane, v in enumerate(vals):
        got = sum(int(digits[i, lane]) * (16 ** i) for i in range(64))
        assert got == v
