#!/bin/sh
# ASAN/UBSAN build + run of the native Ed25519 engine (SURVEY §5.2's
# sanitizer leg for csrc; the Python suite covers the logic, this
# catches memory errors the .so build would hide). Covers the ed25519
# engine's batch and wire-packing entry points (the packer inline and
# in chunks over the worker pool), the commit codec (commit_count, then
# commit_parse into buffers of exactly the counted slots, over
# truncations, mutations and garbage: the count is what the parse fills,
# one slot fewer is refused inside its buffers), the secp256k1 verify
# engine (r/s boundary values, bad point
# encodings, multi-verify chunk determinism), the sr25519 unit
# (ristretto decode rejects, merlin challenge, batch residue s >= L,
# n==0 batches), the BLS12-381 pairing engine (PoP cycle,
# identity-point rejection, n==0 aggregates, 128-key max-size
# aggregation chunk determinism, single cert pairing check), and the
# GF(2^16) Reed-Solomon DA codec (parameter guards, insufficient
# survivors, 4096-shard ceiling, threaded encode/reconstruct roundtrip
# with chunk-count determinism), and the G1 Pippenger MSM / KZG engine
# (oracle-pinned commit/open/verify roundtrip closed with a native
# pairing check, n==0, skip masks, identity points, zero scalars, the
# max-bucket digit tier, chunk-count determinism, scalar >= r and
# bad-encoding rejects).
set -e
cd "$(dirname "$0")/.."
out="${TMPDIR:-/tmp}"
# -std=c++17: std::shared_mutex in the IFMA engine; g++ <= 10 defaults
# to gnu++14 and would fail the build outright
g++ -std=c++17 -O1 -g -fsanitize=address,undefined -fno-omit-frame-pointer -pthread \
    cometbft_tpu/csrc/ed25519_native.cpp cometbft_tpu/csrc/asan_selftest.cpp -o "$out/ed25519_asan"
"$out/ed25519_asan"
# second pass with -march=native: on IFMA-capable hosts this compiles
# and sanitizes the AVX-512 vector engine (cometbft_tpu/csrc/ed25519_ifma.inc) too
g++ -std=c++17 -O1 -g -march=native -fsanitize=address,undefined \
    -fno-omit-frame-pointer -pthread \
    cometbft_tpu/csrc/ed25519_native.cpp cometbft_tpu/csrc/asan_selftest.cpp -o "$out/ed25519_asan_nat"
"$out/ed25519_asan_nat"
