"""ctypes binding for the C++ Ed25519 engine (csrc/ed25519_native.cpp).

Build-on-demand: the shared object compiles into the package directory
(g++ is in the base image; pybind11 is not, hence the plain C ABI +
ctypes) under a name keyed by what it was built FROM and FOR: a hash of
the source contents, the compiler flags and this host's CPU features.
`-march=native` code is only valid on a CPU with the features it was
built for, so a binary copied in from another machine (or left by an
older tree) has another key and is never loaded; the engine is rebuilt
from the committed sources instead. build_state() says which happened.

Every entry point degrades to the pure-Python oracle when the toolchain
is missing, so the framework never hard-depends on a compiler — but a
failed build is logged, not silent, and programs that need the engine's
speed (chip_smoke.py) check available() and fail without it.

This is the host-side native path the reference gets from
curve25519-voi's assembly (reference crypto/ed25519/ed25519.go:13):
individual vote verification in consensus gossip, privval signing, p2p
handshake identity. Batch verification stays on the TPU kernels.
"""

from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import platform
import subprocess
import threading

_log = logging.getLogger(__name__)

_SRC = os.path.join(os.path.dirname(__file__), "..", "csrc",
                    "ed25519_native.cpp")
# every source the build reads (the .cpp includes the .inc engines):
# their contents are part of the binary's key
_SRC_DEPS = (
    _SRC,
    os.path.join(os.path.dirname(_SRC), "ed25519_ifma.inc"),
    os.path.join(os.path.dirname(_SRC), "merkle_native.inc"),
    os.path.join(os.path.dirname(_SRC), "commit_codec.inc"),
    os.path.join(os.path.dirname(_SRC), "sha512_mb.inc"),
    os.path.join(os.path.dirname(_SRC), "worker_pool.inc"),
    os.path.join(os.path.dirname(_SRC), "secp256k1.inc"),
    os.path.join(os.path.dirname(_SRC), "sr25519_native.inc"),
    os.path.join(os.path.dirname(_SRC), "bls12_381.inc"),
    os.path.join(os.path.dirname(_SRC), "rs_gf16.inc"),
    os.path.join(os.path.dirname(_SRC), "g1_msm.inc"),
)
# -std=c++17 explicitly: the IFMA engine uses std::shared_mutex and
# g++ <= 10 still defaults to gnu++14, which fails the whole build
_FLAGS = ("-std=c++17", "-O3", "-march=native", "-pthread", "-fPIC",
          "-shared")
_BUILD_TIMEOUT_S = 600  # ~19 s on an idle 8-core host; six test workers
#                         building at once on a loaded one take longer

_lock = threading.Lock()
_lib = None
_tried = False
_state = {"path": None, "built": None, "error": None}


def _cpu_features() -> str:
    """What -march=native keys on: the CPU's feature flags (Linux), else
    the coarsest honest stand-in."""
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.startswith(("flags", "Features")):
                    return " ".join(sorted(line.split(":", 1)[1].split()))
    except OSError:
        pass
    return platform.machine() + "|" + platform.processor()


def _so_path() -> str | None:
    """The binary for these sources, these flags and this CPU; None
    when the sources are absent."""
    h = hashlib.sha256()
    try:
        for p in _SRC_DEPS:
            with open(p, "rb") as f:
                h.update(hashlib.sha256(f.read()).digest())
    except OSError:
        return None
    h.update(" ".join(_FLAGS).encode())
    h.update(_cpu_features().encode())
    return os.path.join(os.path.dirname(__file__),
                        f"_ed25519_native.{h.hexdigest()[:16]}.so")


def _build(so: str) -> bool:
    # built under a private name and renamed into place: concurrent
    # processes (test workers) never load a half-written file
    tmp = f"{so}.{os.getpid()}.tmp"
    cmd = ["g++", *_FLAGS, "-o", tmp, os.path.abspath(_SRC)]
    try:
        proc = subprocess.run(cmd, capture_output=True,
                              timeout=_BUILD_TIMEOUT_S)
        if proc.returncode != 0:
            _state["error"] = proc.stderr.decode(errors="replace")[-2000:]
            return False
        os.replace(tmp, so)
        return True
    except (OSError, subprocess.TimeoutExpired) as e:
        _state["error"] = repr(e)
        return False
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)


def get_lib():
    """The loaded library, building it if needed; None if unavailable
    (no sources, no toolchain, failed build — logged once)."""
    global _lib, _tried
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        so = _so_path()
        if so is None:
            _state["error"] = "csrc sources not found"
        else:
            _state["path"] = so
            _state["built"] = not os.path.exists(so)
            if not _state["built"] or _build(so):
                lib = ctypes.CDLL(so)
                _bind(lib)
                _lib = lib
                return _lib
        _state["built"] = None
        _log.warning("native engine unavailable, pure-Python oracles "
                     "take over (orders slower): %s", _state["error"])
        return None


def build_state() -> dict:
    """{'path', 'built', 'error'} of this process's engine: built=True
    when this process compiled the binary, False when one with the
    matching key was already there, None when there is no engine."""
    get_lib()
    return dict(_state)


def _bind(lib) -> None:
    lib.ed25519_verify.restype = ctypes.c_int
    lib.ed25519_verify.argtypes = [
        ctypes.c_char_p, ctypes.c_char_p, ctypes.c_uint64, ctypes.c_char_p,
    ]
    lib.ed25519_sign.restype = None
    lib.ed25519_sign.argtypes = [
        ctypes.c_char_p, ctypes.c_char_p, ctypes.c_char_p,
        ctypes.c_uint64, ctypes.c_char_p,
    ]
    lib.ed25519_pubkey.restype = None
    lib.ed25519_pubkey.argtypes = [ctypes.c_char_p, ctypes.c_char_p]
    lib.ed25519_batch_verify.restype = ctypes.c_int
    lib.ed25519_batch_verify.argtypes = [
        ctypes.c_uint64, ctypes.c_char_p, ctypes.c_char_p,
        ctypes.POINTER(ctypes.c_uint64), ctypes.c_char_p,
    ]
    lib.ed25519_engine.restype = ctypes.c_int
    lib.ed25519_engine.argtypes = []
    lib.merkle_root_native.restype = None
    lib.merkle_root_native.argtypes = [
        ctypes.c_uint64, ctypes.c_char_p,
        ctypes.POINTER(ctypes.c_uint64), ctypes.c_char_p,
    ]
    lib.sha256_oneshot.restype = None
    lib.sha256_oneshot.argtypes = [
        ctypes.c_char_p, ctypes.c_uint64, ctypes.c_char_p,
    ]
    lib.sha256_engine.restype = ctypes.c_int
    lib.sha256_engine.argtypes = []
    lib.sha256_force_portable.restype = None
    lib.sha256_force_portable.argtypes = [ctypes.c_int]
    lib.ed25519_batch_k.restype = None
    lib.ed25519_batch_k.argtypes = [
        ctypes.c_uint64, ctypes.c_char_p, ctypes.c_char_p,
        ctypes.c_char_p, ctypes.POINTER(ctypes.c_uint64), ctypes.c_char_p,
    ]
    lib.ed25519_pack_rsk.restype = ctypes.c_int
    # void_p operands: callers pass numpy views over their accumulation
    # buffers zero-copy (bytes() snapshots of MB-scale blobs cost ~0.5 ms
    # on the submit hot path)
    lib.ed25519_pack_rsk.argtypes = [
        ctypes.c_uint64, ctypes.c_void_p, ctypes.c_void_p,
        ctypes.c_void_p, ctypes.POINTER(ctypes.c_uint64), ctypes.c_void_p,
        ctypes.c_int,
    ]
    lib.keccak_f1600.restype = None
    lib.keccak_f1600.argtypes = [ctypes.c_void_p]
    lib.edwards_msm_is_identity.restype = ctypes.c_int
    lib.edwards_msm_is_identity.argtypes = [
        ctypes.c_uint64, ctypes.c_char_p, ctypes.c_char_p, ctypes.c_char_p,
    ]
    lib.commit_sign_bytes.restype = ctypes.c_long
    lib.commit_sign_bytes.argtypes = [
        ctypes.c_uint64, ctypes.c_void_p,
        ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_int64),
        ctypes.c_char_p, ctypes.c_uint64, ctypes.c_char_p, ctypes.c_uint64,
        ctypes.c_char_p, ctypes.c_uint64, ctypes.c_void_p, ctypes.c_uint64,
        ctypes.POINTER(ctypes.c_uint32),
    ]
    lib.secp256k1_engine.restype = ctypes.c_int
    lib.secp256k1_engine.argtypes = []
    lib.secp256k1_verify.restype = ctypes.c_int
    lib.secp256k1_verify.argtypes = [
        ctypes.c_char_p, ctypes.c_char_p, ctypes.c_uint64, ctypes.c_char_p,
    ]
    lib.secp256k1_multi_verify.restype = ctypes.c_long
    lib.secp256k1_multi_verify.argtypes = [
        ctypes.c_uint64, ctypes.c_char_p, ctypes.c_char_p,
        ctypes.POINTER(ctypes.c_uint64), ctypes.c_char_p,
        ctypes.c_int, ctypes.c_char_p,
    ]
    lib.sr25519_engine.restype = ctypes.c_int
    lib.sr25519_engine.argtypes = []
    lib.sr25519_challenge.restype = None
    lib.sr25519_challenge.argtypes = [
        ctypes.c_char_p, ctypes.c_char_p, ctypes.c_uint64,
        ctypes.c_char_p, ctypes.c_char_p,
    ]
    lib.sr25519_ristretto_decode.restype = ctypes.c_int
    lib.sr25519_ristretto_decode.argtypes = [
        ctypes.c_char_p, ctypes.c_char_p, ctypes.c_char_p,
    ]
    lib.sr25519_batch_residue.restype = ctypes.c_int
    lib.sr25519_batch_residue.argtypes = [
        ctypes.c_uint64, ctypes.c_char_p, ctypes.c_char_p, ctypes.c_char_p,
        ctypes.c_char_p, ctypes.c_char_p,
    ]
    lib.sr25519_batch_verify.restype = ctypes.c_int
    lib.sr25519_batch_verify.argtypes = [
        ctypes.c_uint64, ctypes.c_char_p, ctypes.c_char_p,
        ctypes.POINTER(ctypes.c_uint64), ctypes.c_char_p, ctypes.c_char_p,
    ]
    lib.bls_engine.restype = ctypes.c_int
    lib.bls_engine.argtypes = []
    lib.bls_pubkey.restype = ctypes.c_int
    lib.bls_pubkey.argtypes = [ctypes.c_char_p, ctypes.c_char_p]
    lib.bls_sign.restype = ctypes.c_int
    lib.bls_sign.argtypes = [
        ctypes.c_char_p, ctypes.c_char_p, ctypes.c_uint64,
        ctypes.c_char_p, ctypes.c_uint64, ctypes.c_char_p,
    ]
    lib.bls_verify.restype = ctypes.c_int
    lib.bls_verify.argtypes = [
        ctypes.c_char_p, ctypes.c_char_p, ctypes.c_uint64,
        ctypes.c_char_p, ctypes.c_uint64, ctypes.c_char_p,
    ]
    lib.bls_hash_to_g2.restype = ctypes.c_int
    lib.bls_hash_to_g2.argtypes = [
        ctypes.c_char_p, ctypes.c_uint64,
        ctypes.c_char_p, ctypes.c_uint64, ctypes.c_char_p,
    ]
    lib.bls_g1_decompress.restype = ctypes.c_int
    lib.bls_g1_decompress.argtypes = [ctypes.c_char_p, ctypes.c_char_p]
    lib.bls_g2_decompress.restype = ctypes.c_int
    lib.bls_g2_decompress.argtypes = [ctypes.c_char_p, ctypes.c_char_p]
    lib.bls_g1_subgroup_check.restype = ctypes.c_int
    lib.bls_g1_subgroup_check.argtypes = [ctypes.c_char_p]
    lib.bls_g2_subgroup_check.restype = ctypes.c_int
    lib.bls_g2_subgroup_check.argtypes = [ctypes.c_char_p]
    lib.bls_pairing.restype = ctypes.c_int
    lib.bls_pairing.argtypes = [
        ctypes.c_char_p, ctypes.c_char_p, ctypes.c_char_p,
    ]
    lib.bls_aggregate_sigs.restype = ctypes.c_int
    lib.bls_aggregate_sigs.argtypes = [
        ctypes.c_uint64, ctypes.c_char_p, ctypes.c_int, ctypes.c_char_p,
    ]
    lib.bls_aggregate_pubkeys.restype = ctypes.c_int
    lib.bls_aggregate_pubkeys.argtypes = [
        ctypes.c_uint64, ctypes.c_char_p, ctypes.c_char_p,
        ctypes.c_int, ctypes.c_char_p,
    ]
    lib.bls_aggregate_verify.restype = ctypes.c_int
    lib.bls_aggregate_verify.argtypes = [
        ctypes.c_uint64, ctypes.c_char_p, ctypes.c_char_p,
        ctypes.POINTER(ctypes.c_uint32),                    # gids
        ctypes.c_uint64, ctypes.c_char_p,                   # k, msgs blob
        ctypes.POINTER(ctypes.c_uint64),                    # msg_lens
        ctypes.c_char_p, ctypes.c_uint64, ctypes.c_int,     # dst, nchunks
    ]
    lib.bls_cert_verify.restype = ctypes.c_int
    lib.bls_cert_verify.argtypes = [
        ctypes.c_uint64, ctypes.c_char_p, ctypes.c_char_p,  # n, pubs, bitmap
        ctypes.c_char_p, ctypes.c_uint64,                   # msg
        ctypes.c_char_p,                                    # agg sig
        ctypes.c_char_p, ctypes.c_uint64, ctypes.c_int,     # dst, nchunks
    ]
    lib.rs_gf16_threads.restype = ctypes.c_int
    lib.rs_gf16_threads.argtypes = []
    lib.g1_msm_threads.restype = ctypes.c_int
    lib.g1_msm_threads.argtypes = []
    lib.g1_msm.restype = ctypes.c_int
    lib.g1_msm.argtypes = [
        ctypes.c_uint64, ctypes.c_char_p, ctypes.c_char_p,  # n, scalars, points
        ctypes.c_char_p, ctypes.c_int, ctypes.c_char_p,     # skip, nchunks, out
    ]
    lib.rs_encode16.restype = ctypes.c_long
    lib.rs_encode16.argtypes = [
        ctypes.c_uint64, ctypes.c_uint32, ctypes.c_uint32,  # shard_len, k, m
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int,     # data, parity, nchunks
    ]
    lib.rs_reconstruct16.restype = ctypes.c_long
    lib.rs_reconstruct16.argtypes = [
        ctypes.c_uint64, ctypes.c_uint32, ctypes.c_uint32,  # shard_len, k, m
        ctypes.c_void_p, ctypes.c_char_p, ctypes.c_void_p,  # shards, present, out
        ctypes.c_int,                                       # nchunks
    ]
    lib.commit_count.restype = ctypes.c_long
    lib.commit_count.argtypes = [ctypes.c_char_p, ctypes.c_uint64]
    lib.commit_parse.restype = ctypes.c_long
    lib.commit_parse.argtypes = [
        ctypes.c_char_p, ctypes.c_uint64, ctypes.c_uint64,
        ctypes.POINTER(ctypes.c_uint64),                    # head
        ctypes.c_char_p,                                    # flags
        ctypes.c_char_p, ctypes.c_char_p,                   # addr_lens, addrs
        ctypes.POINTER(ctypes.c_int64),                     # ts_s
        ctypes.POINTER(ctypes.c_int64),                     # ts_n
        ctypes.c_char_p, ctypes.c_char_p,                   # sig_lens, sigs
        ctypes.POINTER(ctypes.c_uint64),                    # spans
    ]


def engine() -> str:
    """Which code path serves verification: "avx512-ifma" (the 8-lane
    vpmadd52 engine) or "portable" (the scalar 5x51 engine)."""
    lib = get_lib()
    if lib is None:
        return "unavailable"
    return "avx512-ifma" if lib.ed25519_engine() else "portable"


def available() -> bool:
    return get_lib() is not None


def verify(pub: bytes, msg: bytes, sig: bytes) -> bool:
    """ZIP-215 verify; raises RuntimeError if the native lib is absent."""
    lib = get_lib()
    if lib is None:
        raise RuntimeError("native ed25519 unavailable")
    return bool(lib.ed25519_verify(pub, msg, len(msg), sig))


def sign(seed: bytes, pub: bytes, msg: bytes) -> bytes:
    lib = get_lib()
    if lib is None:
        raise RuntimeError("native ed25519 unavailable")
    out = ctypes.create_string_buffer(64)
    lib.ed25519_sign(seed, pub, msg, len(msg), out)
    return out.raw


def batch_verify(items) -> bool:
    """RLC batch verify of [(pub32, msg, sig64), ...] — ONE Pippenger
    multi-scalar multiplication in C++ (the CPU fast path for
    commit-sized batches; the device ladder takes larger ones). False
    means "some signature failed" — the caller re-verifies singly for
    the bitmap, mirroring the reference fallback."""
    lib = get_lib()
    if lib is None:
        raise RuntimeError("native ed25519 unavailable")
    n = len(items)
    if n == 0:
        return False
    pubs = b"".join(it[0] for it in items)
    sigs = b"".join(it[2] for it in items)
    msgs = b"".join(it[1] for it in items)
    lens = (ctypes.c_uint64 * n)(*(len(it[1]) for it in items))
    return bool(lib.ed25519_batch_verify(n, pubs, msgs, lens, sigs))


def batch_challenge_scalars(items) -> bytes | None:
    """k_i = SHA-512(R_i || A_i || M_i) mod L for every (pub, msg, sig)
    triple, concatenated 32-byte little-endian scalars; None when the
    native lib is absent (caller hashes via hashlib). The hot submit
    path uses pack_rsk instead (same engine, strided straight into the
    wire buffer); this entry serves ad-hoc callers and the differential
    tests."""
    lib = get_lib()
    if lib is None:
        return None
    n = len(items)
    sigs = b"".join(it[2] for it in items)
    pubs = b"".join(it[0] for it in items)
    msgs = b"".join(it[1] for it in items)
    lens = (ctypes.c_uint64 * n)(*(len(it[1]) for it in items))
    out = ctypes.create_string_buffer(n * 32)
    lib.ed25519_batch_k(n, sigs, pubs, msgs, lens, out)
    return out.raw


def pack_rsk(n: int, sig_blob, pub_blob, msg_blob,
             msg_lens, out_rsk, nchunks: int = 0) -> int | None:
    """Assemble the R||S||k device wire rows (stride 96) for n lanes
    straight into `out_rsk` (a C-contiguous uint8 numpy array with at
    least n*96 leading bytes): signature copy + 8-wide challenge
    hashing + mod-L in one native call, in chunks over the C++ worker
    pool when its slot is free. Returns the number of chunks the lanes
    went in (1: too few lanes to split; 0: another engine held the
    pool, so the calling thread packed them all), None when the lib is
    absent (caller packs in Python). `nchunks` > 0 pins the chunk count
    (tests; the rows are the same for every count). The blobs may be
    bytes, bytearray, or uint8 numpy arrays — all passed zero-copy;
    `msg_lens` is a uint64 numpy array."""
    lib = get_lib()
    if lib is None or not hasattr(lib, "ed25519_pack_rsk"):
        return None
    import numpy as _np

    def _addr(buf):
        return _np.frombuffer(buf, _np.uint8).ctypes.data_as(ctypes.c_void_p)

    return lib.ed25519_pack_rsk(
        n, _addr(sig_blob), _addr(pub_blob), _addr(msg_blob),
        msg_lens.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
        out_rsk.ctypes.data_as(ctypes.c_void_p), nchunks,
    )


def commit_parse(buf: bytes):
    """Columnar parse of a Commit wire buffer's signature list in two C
    calls: `commit_count` walks the top-level fields for the number of
    signature slots, `commit_parse` fills columns allocated for exactly
    that many (a buffer's length says little: an absent slot is 4 bytes
    on the wire and a signed one about 105). Returns (height_u64,
    round_u64, bid_span, cols) where cols = (count, flags, addr_lens,
    addrs, ts_s, ts_n, sig_lens, sigs, spans), every column `count`
    slots long, or None when the native lib is absent or the buffer
    needs the (bug-compatible, stricter-error) Python path."""
    lib = get_lib()
    if lib is None:
        return None
    n = lib.commit_count(buf, len(buf))
    if n < 0:
        return None
    head = (ctypes.c_uint64 * 4)()
    flags = ctypes.create_string_buffer(n)
    addr_lens = ctypes.create_string_buffer(n)
    addrs = ctypes.create_string_buffer(n * 20)
    ts_s = (ctypes.c_int64 * n)()
    ts_n = (ctypes.c_int64 * n)()
    sig_lens = ctypes.create_string_buffer(n)
    sigs = ctypes.create_string_buffer(n * 64)
    spans = (ctypes.c_uint64 * (n * 2))()
    rc = lib.commit_parse(
        buf, len(buf), n, head, flags, addr_lens, addrs,
        ts_s, ts_n, sig_lens, sigs, spans,
    )
    if rc != n:  # an entry the parser refuses (-1): the Python path's
        return None
    return (
        int(head[0]),
        int(head[1]),
        (int(head[2]), int(head[3])),
        (n, flags.raw, addr_lens.raw, addrs.raw, ts_s, ts_n,
         sig_lens.raw, sigs.raw, spans),
    )


_KECCAK_FN = None  # resolved once: the permutation runs ~6k times per
# sr25519 batch and get_lib's lock + hasattr per call cost more than
# the C permutation itself


def keccak_f1600(state: bytearray) -> bool:
    """In-place Keccak-f[1600] on a 200-byte state; False when the lib
    is absent (caller runs the Python permutation)."""
    global _KECCAK_FN
    fn = _KECCAK_FN
    if fn is None:
        lib = get_lib()
        fn = _KECCAK_FN = (
            lib.keccak_f1600
            if lib is not None and hasattr(lib, "keccak_f1600")
            else False
        )
    if fn is False:
        return False
    buf = (ctypes.c_char * 200).from_buffer(state)
    fn(ctypes.addressof(buf))
    return True


def edwards_msm_is_identity(pairs) -> bool | None:
    """sum [k_i]P_i lands in the RISTRETTO identity coset — the
    4-torsion {(0,1), (0,-1), (+-i,0)}, checked as T == 0 — via one
    native Pippenger call. NOT an exact Edwards identity check: do not
    reuse for cofactored ed25519 equations, where accepting torsion is
    a forgery vector (those go through ed25519_batch_verify, which
    multiplies by 8). `pairs` is a list of (k int, (x int, y int))
    with points already decoded/validated by the caller (the sr25519
    ristretto batch). None when the lib is absent (caller uses the
    Python MSM)."""
    lib = get_lib()
    if lib is None or not hasattr(lib, "edwards_msm_is_identity"):
        return None
    n = len(pairs)
    xs = b"".join(p[1][0].to_bytes(32, "little") for p in pairs)
    ys = b"".join(p[1][1].to_bytes(32, "little") for p in pairs)
    ks = b"".join((p[0] % _L_ORDER).to_bytes(32, "little") for p in pairs)
    return bool(lib.edwards_msm_is_identity(n, xs, ys, ks))


_L_ORDER = 2**252 + 27742317777372353535851937790883648493


def commit_sign_bytes(n, flags, ts_s, ts_n, prefix_commit: bytes,
                      prefix_nil: bytes, tail: bytes):
    """Canonical sign bytes for all commit slots in one C call.

    flags: uint8 numpy array; ts_s/ts_n: int64 numpy arrays (zero-copy).
    Returns (blob bytes, lens uint32 numpy array) or None when the lib
    is absent or a flag is outside ABSENT/COMMIT/NIL."""
    lib = get_lib()
    if lib is None or not hasattr(lib, "commit_sign_bytes"):
        return None
    import numpy as _np

    # worst case per slot: 3B length prefix + prefix + 24B ts field + tail
    cap = int(n) * (max(len(prefix_commit), len(prefix_nil))
                    + len(tail) + 32)
    out = _np.empty(cap, _np.uint8)
    lens = _np.empty(n, _np.uint32)
    total = lib.commit_sign_bytes(
        n, flags.ctypes.data_as(ctypes.c_void_p),
        ts_s.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        ts_n.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        prefix_commit, len(prefix_commit), prefix_nil, len(prefix_nil),
        tail, len(tail), out.ctypes.data_as(ctypes.c_void_p), cap,
        lens.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32)),
    )
    if total < 0:
        return None
    return out[:total].tobytes(), lens


def merkle_root(items) -> bytes:
    """RFC-6962 merkle root of a list of byte leaves in one C call
    (leaf/inner prefixes per reference crypto/merkle/hash.go); raises
    RuntimeError if the native lib is absent."""
    lib = get_lib()
    if lib is None:
        raise RuntimeError("native merkle unavailable")
    n = len(items)
    offs = (ctypes.c_uint64 * (n + 1))()
    pos = 0
    for i, it in enumerate(items):
        offs[i] = pos
        pos += len(it)
    offs[n] = pos
    out = ctypes.create_string_buffer(32)
    lib.merkle_root_native(n, b"".join(items), offs, out)
    return out.raw


def sha256(data: bytes) -> bytes:
    lib = get_lib()
    if lib is None:
        raise RuntimeError("native sha256 unavailable")
    out = ctypes.create_string_buffer(32)
    lib.sha256_oneshot(data, len(data), out)
    return out.raw


def sha256_engine() -> str:
    lib = get_lib()
    if lib is None:
        return "unavailable"
    return "sha-ni" if lib.sha256_engine() else "portable"


def sha256_force_portable(on: bool) -> None:
    """Test hook: pin the portable scalar compression so differential
    tests exercise both engines on a SHA-NI host."""
    lib = get_lib()
    if lib is not None:
        lib.sha256_force_portable(1 if on else 0)


def pubkey(seed: bytes) -> bytes:
    lib = get_lib()
    if lib is None:
        raise RuntimeError("native ed25519 unavailable")
    out = ctypes.create_string_buffer(32)
    lib.ed25519_pubkey(seed, out)
    return out.raw


def secp256k1_available() -> bool:
    """True when the .so exports the secp256k1 verify engine."""
    lib = get_lib()
    return (lib is not None and hasattr(lib, "secp256k1_engine")
            and bool(lib.secp256k1_engine()))


def secp256k1_verify(pub: bytes, msg: bytes, sig: bytes) -> bool | None:
    """One native ECDSA verify (33-byte SEC1 compressed pub, 64-byte
    R||S big-endian sig, low-S enforced). None when the lib is absent —
    caller uses the Python oracle."""
    lib = get_lib()
    if lib is None or not hasattr(lib, "secp256k1_verify"):
        return None
    return bool(lib.secp256k1_verify(pub, msg, len(msg), sig))


def secp256k1_multi_verify(items, nchunks: int = 0):
    """Verify [(pub33, msg, sig64), ...] in ONE native call spread over
    the worker pool (`nchunks` pins the split for determinism tests; 0
    means pool width). Returns a per-item list of bools, or None when
    the lib is absent. Unlike the ed25519 batch path there is no
    all-or-nothing equation — each item is independent, so blame is
    exact and free."""
    lib = get_lib()
    if lib is None or not hasattr(lib, "secp256k1_multi_verify"):
        return None
    n = len(items)
    if n == 0:
        return []
    pubs = b"".join(it[0] for it in items)
    msgs = b"".join(it[1] for it in items)
    lens = (ctypes.c_uint64 * n)(*(len(it[1]) for it in items))
    sigs = b"".join(it[2] for it in items)
    out = ctypes.create_string_buffer(n)
    lib.secp256k1_multi_verify(n, pubs, msgs, lens, sigs, nchunks, out)
    return [b != 0 for b in out.raw]


def sr25519_available() -> bool:
    """True when the .so exports the sr25519 batch unit."""
    lib = get_lib()
    return (lib is not None and hasattr(lib, "sr25519_engine")
            and bool(lib.sr25519_engine()))


def sr25519_batch_verify(items, z16: bytes) -> bool | None:
    """Whole sr25519 batch — ristretto decode + merlin transcripts +
    mod-L residue + one Pippenger identity check — in ONE native call.
    `items` is [(pub32, msg, sig64), ...]; `z16` is n*16 bytes of
    caller randomness (bit 0 of each z forced on inside). False means
    "batch failed" — caller rescans per-signature for blame, same
    contract as the Python RLC path. None when the lib is absent."""
    lib = get_lib()
    if lib is None or not hasattr(lib, "sr25519_batch_verify"):
        return None
    n = len(items)
    pubs = b"".join(it[0] for it in items)
    msgs = b"".join(it[1] for it in items)
    lens = (ctypes.c_uint64 * max(n, 1))(*(len(it[1]) for it in items))
    sigs = b"".join(it[2] for it in items)
    return bool(lib.sr25519_batch_verify(n, pubs, msgs, lens, sigs, z16))


def sr25519_batch_residue(ss: bytes, cs: bytes, z16: bytes):
    """The batch scalar residue alone: per-sig z_i*c_i mod L and the
    accumulated sum z_i*s_i mod L for n 32-byte LE scalars in `ss`/`cs`
    and n*16 randomness bytes. Returns (zc_blob, zsum32) or False when
    some s_i is non-canonical (>= L); None when the lib is absent."""
    lib = get_lib()
    if lib is None or not hasattr(lib, "sr25519_batch_residue"):
        return None
    n = len(ss) // 32
    zc = ctypes.create_string_buffer(n * 32)
    zsum = ctypes.create_string_buffer(32)
    if not lib.sr25519_batch_residue(n, ss, cs, z16, zc, zsum):
        return False
    return zc.raw, zsum.raw


def sr25519_challenge(pub: bytes, msg: bytes, r32: bytes) -> bytes | None:
    """Merlin "sign:c" challenge scalar (32-byte LE, mod L) for one
    signature — differential entry against crypto/merlin.py; None when
    the lib is absent."""
    lib = get_lib()
    if lib is None or not hasattr(lib, "sr25519_challenge"):
        return None
    out = ctypes.create_string_buffer(32)
    lib.sr25519_challenge(pub, msg, len(msg), r32, out)
    return out.raw


def bls_available() -> bool:
    """True when the .so exports the BLS12-381 pairing unit."""
    lib = get_lib()
    return (lib is not None and hasattr(lib, "bls_engine")
            and bool(lib.bls_engine()))


def bls_pubkey(sk32: bytes) -> bytes | None:
    """48-byte compressed G1 pubkey for a 32-byte BE scalar; None when
    the lib is absent or the scalar is out of [1, r) (caller falls back
    to the Python oracle)."""
    lib = get_lib()
    if lib is None or not hasattr(lib, "bls_pubkey"):
        return None
    out = ctypes.create_string_buffer(48)
    if not lib.bls_pubkey(sk32, out):
        return None
    return out.raw


def bls_sign(sk32: bytes, msg: bytes, dst: bytes) -> bytes | None:
    """96-byte compressed G2 signature [sk]H(msg); None when the lib is
    absent or the scalar is invalid."""
    lib = get_lib()
    if lib is None or not hasattr(lib, "bls_sign"):
        return None
    out = ctypes.create_string_buffer(96)
    if not lib.bls_sign(sk32, msg, len(msg), dst, len(dst), out):
        return None
    return out.raw


def bls_verify(pub: bytes, msg: bytes, sig: bytes,
               dst: bytes) -> bool | None:
    """One native BLS verify (KeyValidate + sig subgroup + 2-pair
    product); None when the lib is absent."""
    lib = get_lib()
    if lib is None or not hasattr(lib, "bls_verify"):
        return None
    return bool(lib.bls_verify(pub, msg, len(msg), dst, len(dst), sig))


def bls_hash_to_g2(msg: bytes, dst: bytes) -> bytes | None:
    """96-byte compressed RFC 9380 hash_to_curve output; None when the
    lib is absent."""
    lib = get_lib()
    if lib is None or not hasattr(lib, "bls_hash_to_g2"):
        return None
    out = ctypes.create_string_buffer(96)
    if not lib.bls_hash_to_g2(msg, len(msg), dst, len(dst), out):
        return None
    return out.raw


def bls_g1_decompress(b48: bytes):
    """Native G1 decode: (x int, y int) affine, "inf", False on a
    rejected encoding, None when the lib is absent. Differential
    surface for the canonicality rules."""
    lib = get_lib()
    if lib is None or not hasattr(lib, "bls_g1_decompress"):
        return None
    out = ctypes.create_string_buffer(96)
    rc = lib.bls_g1_decompress(b48, out)
    if rc == 2:
        return "inf"
    if rc != 1:
        return False
    return (int.from_bytes(out.raw[:48], "big"),
            int.from_bytes(out.raw[48:], "big"))


def bls_g2_decompress(b96: bytes):
    """Native G2 decode: ((x0,x1),(y0,y1)) affine, "inf", False on a
    rejected encoding, None when the lib is absent."""
    lib = get_lib()
    if lib is None or not hasattr(lib, "bls_g2_decompress"):
        return None
    out = ctypes.create_string_buffer(192)
    rc = lib.bls_g2_decompress(b96, out)
    if rc == 2:
        return "inf"
    if rc != 1:
        return False
    c = [int.from_bytes(out.raw[i * 48:(i + 1) * 48], "big")
         for i in range(4)]
    return ((c[0], c[1]), (c[2], c[3]))


def bls_g1_subgroup_check(b48: bytes) -> int | None:
    """1 = in the r-order subgroup, 0 = on curve but not, 2 = infinity,
    -1 = decode failure; None when the lib is absent. The native check
    is the fast endomorphism one — differentially pinned against the
    oracle's naive [r]P."""
    lib = get_lib()
    if lib is None or not hasattr(lib, "bls_g1_subgroup_check"):
        return None
    return int(lib.bls_g1_subgroup_check(b48))


def bls_g2_subgroup_check(b96: bytes) -> int | None:
    """Same contract as bls_g1_subgroup_check for G2 (psi-endomorphism
    fast check natively)."""
    lib = get_lib()
    if lib is None or not hasattr(lib, "bls_g2_subgroup_check"):
        return None
    return int(lib.bls_g2_subgroup_check(b96))


def bls_pairing(p48: bytes, q96: bytes) -> bytes | bool | None:
    """Serialized GT element e(P, Q) (576 bytes, 12 Fp coords BE) —
    pins the native Miller loop + final exponentiation bit-for-bit
    against the oracle. False on invalid/out-of-subgroup inputs; None
    when the lib is absent."""
    lib = get_lib()
    if lib is None or not hasattr(lib, "bls_pairing"):
        return None
    out = ctypes.create_string_buffer(576)
    if not lib.bls_pairing(p48, q96, out):
        return False
    return out.raw


def bls_aggregate_sigs(blob: bytes, n: int,
                       nchunks: int = 0) -> bytes | None:
    """Sum n 96-byte G2 signatures across the worker pool -> one
    96-byte aggregate. None when the lib is absent OR any input fails
    decode/subgroup — the caller's Python rescan then produces the
    (identical) rejection."""
    lib = get_lib()
    if lib is None or not hasattr(lib, "bls_aggregate_sigs"):
        return None
    out = ctypes.create_string_buffer(96)
    if not lib.bls_aggregate_sigs(n, blob, nchunks, out):
        return None
    return out.raw


def bls_aggregate_pubkeys(blob: bytes, n: int, bitmap: bytes,
                          nchunks: int = 0) -> bytes | None:
    """Aggregate pubkey over a signer bitmap in one native call
    (KeyValidate per participant, identity aggregate rejected). None
    when the lib is absent or the aggregate is invalid (Python rescan
    reproduces the rejection)."""
    lib = get_lib()
    if lib is None or not hasattr(lib, "bls_aggregate_pubkeys"):
        return None
    out = ctypes.create_string_buffer(48)
    if not lib.bls_aggregate_pubkeys(n, blob, bitmap, nchunks, out):
        return None
    return out.raw


def bls_aggregate_verify(pubs_blob: bytes, sigs_blob: bytes, n: int,
                         gids, msgs, dst: bytes,
                         nchunks: int = 0) -> bool | None:
    """n (pub, msg, sig) triples -> ONE native product-of-pairings
    check. `gids[i]` names the message group of item i; `msgs` lists
    the k distinct messages in group order. None when the lib is
    absent."""
    lib = get_lib()
    if lib is None or not hasattr(lib, "bls_aggregate_verify"):
        return None
    k = len(msgs)
    gid_arr = (ctypes.c_uint32 * max(n, 1))(*gids)
    msg_lens = (ctypes.c_uint64 * max(k, 1))(*(len(m) for m in msgs))
    return bool(lib.bls_aggregate_verify(
        n, pubs_blob, sigs_blob, gid_arr, k, b"".join(msgs), msg_lens,
        dst, len(dst), nchunks))


def bls_cert_verify(pubs_blob: bytes, n: int, bitmap: bytes,
                    msg: bytes, agg_sig: bytes, dst: bytes,
                    nchunks: int = 0) -> bool | None:
    """Aggregate-certificate verify in one call: pool-parallel apk over
    the bitmap + e(apk, H(msg)) == e(g1, agg_sig). None when the lib is
    absent."""
    lib = get_lib()
    if lib is None or not hasattr(lib, "bls_cert_verify"):
        return None
    return bool(lib.bls_cert_verify(
        n, pubs_blob, bitmap, msg, len(msg), agg_sig,
        dst, len(dst), nchunks))


def rs_available() -> bool:
    """True when the .so exports the GF(2^16) Reed-Solomon codec."""
    lib = get_lib()
    return lib is not None and hasattr(lib, "rs_encode16")


def rs_threads() -> int:
    """Worker count the RS codec spreads a shard set across (1 when the
    lib is absent — the numpy oracle is single-core anyway)."""
    lib = get_lib()
    if lib is None or not hasattr(lib, "rs_gf16_threads"):
        return 1
    return max(1, int(lib.rs_gf16_threads()))


def rs_encode(data_blob, k: int, m: int, shard_len: int,
              nchunks: int = 0) -> bytes | None:
    """m parity shards from `data_blob` (k*shard_len bytes, any
    buffer-protocol object — passed zero-copy) as one m*shard_len
    bytes string. None when the lib is absent or the engine declines
    the parameters (caller uses the numpy oracle)."""
    lib = get_lib()
    if lib is None or not hasattr(lib, "rs_encode16"):
        return None
    import numpy as _np

    parity = _np.empty(m * shard_len, _np.uint8)
    rc = lib.rs_encode16(
        shard_len, k, m,
        _np.frombuffer(data_blob, _np.uint8).ctypes.data_as(ctypes.c_void_p),
        parity.ctypes.data_as(ctypes.c_void_p), nchunks,
    )
    if rc != 0:
        return None
    return parity.tobytes()


def rs_reconstruct(shards_blob, present: bytes, k: int, m: int,
                   shard_len: int, nchunks: int = 0) -> bytes | None:
    """All n = k+m shards reconstructed from the survivors flagged in
    `present` (n 0/1 bytes; missing rows of `shards_blob` are ignored).
    Returns the full n*shard_len buffer, or None when the lib is
    absent / parameters are declined / fewer than k shards survive —
    the caller's oracle path reproduces the exact error."""
    lib = get_lib()
    if lib is None or not hasattr(lib, "rs_reconstruct16"):
        return None
    import numpy as _np

    out = _np.empty((k + m) * shard_len, _np.uint8)
    rc = lib.rs_reconstruct16(
        shard_len, k, m,
        _np.frombuffer(shards_blob, _np.uint8).ctypes.data_as(
            ctypes.c_void_p),
        present, out.ctypes.data_as(ctypes.c_void_p), nchunks,
    )
    if rc != 0:
        return None
    return out.tobytes()


def sr25519_ristretto_decode(enc: bytes):
    """Native ristretto255 decode: (x int, y int) affine coordinates,
    False on a rejected encoding, None when the lib is absent."""
    lib = get_lib()
    if lib is None or not hasattr(lib, "sr25519_ristretto_decode"):
        return None
    ox = ctypes.create_string_buffer(32)
    oy = ctypes.create_string_buffer(32)
    if not lib.sr25519_ristretto_decode(enc, ox, oy):
        return False
    return (int.from_bytes(ox.raw, "little"),
            int.from_bytes(oy.raw, "little"))


def g1_msm_available() -> bool:
    """True when the native G1 Pippenger MSM engine is loadable."""
    lib = get_lib()
    return lib is not None and hasattr(lib, "g1_msm")


def g1_msm_threads() -> int:
    """Worker count the MSM engine spreads a call across (1 when the
    lib is absent — the Python oracle is single-core anyway). The
    dispatch model divides its msm host term by this."""
    lib = get_lib()
    if lib is None or not hasattr(lib, "g1_msm_threads"):
        return 1
    return max(1, int(lib.g1_msm_threads()))


def g1_msm(scalars_blob: bytes, points_blob: bytes, n: int,
           skip: bytes | None = None, nchunks: int = 0):
    """sum scalars[i]*points[i] over BLS12-381 G1: n 32-byte big-endian
    scalars against n zcash-compressed points, entries with a truthy
    `skip` byte excluded without validation. Returns the 48-byte
    compressed sum, False when the engine rejects the input (bad
    point / scalar >= r on a live entry — the oracle rejects the same
    inputs), or None when the lib is absent."""
    lib = get_lib()
    if lib is None or not hasattr(lib, "g1_msm"):
        return None
    out = ctypes.create_string_buffer(48)
    rc = lib.g1_msm(n, scalars_blob, points_blob, skip, nchunks, out)
    if rc != 1:
        return False
    return out.raw
