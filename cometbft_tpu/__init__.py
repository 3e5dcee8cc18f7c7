"""cometbft_tpu — a TPU-native BFT state-machine-replication framework.

A from-scratch rebuild of the capabilities of CometBFT (Tendermint
consensus + ABCI), designed TPU-first:

- **Control plane** (consensus state machine, p2p, storage, RPC): host-side
  Python/C++, sequential and I/O bound.
- **Data plane** (Ed25519/sr25519 batch signature verification, SHA-256
  merkle hashing): JAX kernels on TPU, batched over the signature axis,
  sharded over a device mesh with `shard_map` for multi-chip scale-out.

Reference behavior parity is tracked against CometBFT (see SURVEY.md);
file:line citations in docstrings point at the reference implementation
whose *behavior* (not code) each component mirrors.
"""

__version__ = "0.3.0"

# The persistent XLA compilation cache is placed by
# cometbft_tpu/ops/__init__.py (every device-kernel path imports it):
# jax honours JAX_COMPILATION_CACHE_DIR, and where that is unset the
# cache goes to `.jax_cache/` at the root of the checkout.
