"""JAX/Pallas device kernels — the TPU data plane.

Layout convention: field elements are int32 arrays of shape (NLIMBS, B)
with the *batch* on the trailing axis, so every limb operation is a wide
vector op across TPU lanes and carry chains walk the (small) leading axis.
"""

import os as _os

import jax as _jax

# Persistent XLA compilation cache: on the chip's compiler each verify
# shape takes ~25-30 s (the Mosaic kernel dominates, whatever the
# bucket), so a cold process that warms what it uses pays minutes; with
# the cache a second process pays none.
#
# Where it lives is decided from outside: jax itself reads
# JAX_COMPILATION_CACHE_DIR, and when that is set nothing here touches
# the directory. When it is not (and no caller configured one), the
# cache goes to ONE fixed path inside the checkout — the path is part
# of the cache key, so it must not move with home, a temp name, a pid
# or a time. `.jax_cache/` is git-ignored and chiprun-ignored.
CACHE_DIR = _os.path.join(
    _os.path.dirname(_os.path.dirname(_os.path.dirname(
        _os.path.abspath(__file__)))),
    ".jax_cache",
)
if (not _os.environ.get("JAX_COMPILATION_CACHE_DIR")
        and _jax.config.jax_compilation_cache_dir is None):
    _jax.config.update("jax_compilation_cache_dir", CACHE_DIR)

# What gets written: every program that took >= 1 s to compile, of any
# size. Every kernel of the main path is far above that (the cheapest,
# decompress_pubkeys, takes ~5 s), while the sub-second glue programs
# (a stack of summary scalars, a device_put layout change) cost less to
# recompile than a cache of hundreds of tiny files costs to keep. Pinned
# here rather than left to jax's defaults, unless the environment says
# otherwise.
if "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS" not in _os.environ:
    _jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
if "JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES" not in _os.environ:
    _jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

# The cache key of a program that holds a Pallas kernel covers the
# kernel's serialized Mosaic module, and by default jax writes the Python
# call stack of every op (ten frames of it) into that module's locations
# — which the key's canonicalisation does not strip. The same shape first
# reached through another caller (verify_commit, the replay engine, a
# blame re-run) then has another key and compiles again: on the chip a
# warm run recompiled both ladder shapes (PR 21). One frame: locations
# keep the op's own line and nothing of its callers; the key is then a
# function of the program alone
# (tests/test_tpu_device.py::test_cache_key_does_not_depend_on_the_caller).
#
# Why the limit and not jax_include_full_tracebacks_in_locations=False,
# which also keeps one frame (PR 21 to 23): with that switch off, this
# jax (0.9) inlines each operation's cached lowering under a location
# whose name stack the HLO converter drops, so op_name is the bare
# primitive ("and", "add") and the phases below (jax.named_scope, the
# names of utils/trace.KERNEL_SCOPES) never reach a profiler trace. With
# tracebacks on, op_name is the whole path, "jit(verify_batch_cached_a)/
# ladder.double_scalar/...", which utils/traceview.device_join reads.
_jax.config.update("jax_traceback_in_locations_limit", 1)
