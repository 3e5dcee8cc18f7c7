"""Compile guards: the verify data plane, asked of the chip's compiler.

The suite is pinned to the CPU (conftest.py), where `ops.field._use_pallas`
is False and every test runs the XLA value-form of the field and curve
ops. These tests compile the main path's jitted functions at deployment
widths for a DESCRIBED v5e:2x2 (section 2 of the on-chip-measurement
guide): the TPU compiler is installed here and raises what it would raise
on the chip — a misaligned slice, too much VMEM, a kernel that cannot be
partitioned — at no chip time. Nothing runs; results and times are
chip_smoke.py's to prove on the attached chip.

Rules this file keeps (a worker that loads the TPU library keeps it until
it exits, and every worker imports every test file): the topology is
described inside a module-scoped fixture, never at import, in a skipif,
in parametrize or in conftest.py; no child process; the persistent
compilation cache is off around the compiles (an entry written for a
described chip cannot be read back without one); all in this one file.
`_use_pallas` is steered from here by monkeypatching its backend probe,
not through an option of the program.
"""

import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from cometbft_tpu.crypto import ed25519 as E
from cometbft_tpu.ops import ed25519_verify as EV
from cometbft_tpu.ops import field as F
from cometbft_tpu.parallel import mesh as M

# one v5e chip holds 16 GB and the replay engine keeps two windows in
# flight: a single program's temporaries may take a quarter
TEMP_BUDGET_BYTES = 4 << 30


@pytest.fixture(scope="module")
def topo():
    import signal

    from jax.experimental import topologies

    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # the TPU library installs a SIGTERM handler that prints a stack
    # trace; a suite cut by `timeout` would get it in the middle of its
    # last line of dots. This worker dies quietly like the others.
    signal.signal(signal.SIGTERM, signal.SIG_DFL)
    return desc


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture
def chip(one_chip, no_persistent_cache, monkeypatch):
    """Trace as the chip would: kernels on, one described device."""
    monkeypatch.setattr(F, "_on_tpu", lambda: True)
    return one_chip


def _compile(fn, *args, scopes=(), kernels=()):
    """Lower + compile a FRESH jit of fn (a fresh function identity: the
    program's own module-level jits may hold a CPU trace of the same
    shapes from another test of this worker). Returns the compiled
    program after the checks every guard shares. `scopes`: phases of
    trace.KERNEL_SCOPES that must be in the op_name of operations of the
    COMPILED program, which is what a profiler trace carries and
    utils/traceview.device_join reads (ops/__init__.py says which jax
    setting that hangs on). `kernels`: pallas name= that must be in a
    kernel's op_name, inside its phase."""
    from cometbft_tpu.utils.trace import KERNEL_SCOPES

    t0 = time.perf_counter()
    fresh = jax.jit(lambda *a: fn(*a))
    compiled = fresh.lower(*args).compile()
    dt = time.perf_counter() - t0
    names = set(re.findall(r'op_name="([^"]*)"', compiled.as_text()))
    for name in scopes:
        assert name in KERNEL_SCOPES, name
        assert any(f"/{name}/" in n for n in names), (
            f"no operation of the compiled program under scope {name}")
    for name in kernels:
        assert name in KERNEL_SCOPES, name
        assert any(n.endswith(f"/{name}/pallas_call") and
                   any(p in KERNEL_SCOPES for p in n.split("/")[:-2])
                   for n in names), f"no kernel named {name} in a phase"
    mem = compiled.memory_analysis()
    print(f"\n  {fn.__name__}: compiled in {dt:.1f}s, "
          f"temp {mem.temp_size_in_bytes / 1e6:.1f} MB, "
          f"code {mem.generated_code_size_in_bytes / 1e6:.1f} MB")
    assert mem.temp_size_in_bytes < TEMP_BUDGET_BYTES
    return compiled


def _kernels(compiled) -> int:
    """Pallas kernels in a compiled program (by call target: instruction
    names and users may repeat the string)."""
    return compiled.as_text().count('custom_call_target="tpu_custom_call"')


def _point(b, sh):
    return tuple(
        jax.ShapeDtypeStruct((F.NLIMBS, b), jnp.int32, sharding=sh)
        for _ in range(4)
    )


def _S(shape, dtype, sh):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sh)


def _ladder_args(b, sh, limbs_sh=None):
    """verify_batch_cached_a's arguments with the pair decompress_pubkeys
    returns (32 windows); `limbs_sh` where a point's (NLIMBS, b) arrays
    are laid out otherwise than the (b, ...) ones (a mesh: lanes last)."""
    return (_S((b,), jnp.bool_, sh), (_point(b, limbs_sh or sh),) * 2,
            _S((b, 96), jnp.uint8, sh), _S((b,), jnp.bool_, sh))


def test_every_device_bucket_takes_the_kernel():
    """Pure arithmetic, no topology: each bucket that reaches the device
    (n >= NATIVE_MAX), and its per-shard width on a 4-device mesh
    (n >= MESH_MIN), is a width the Pallas kernels tile. 8 devices do
    NOT hold this (10240 / 8 = 1280): MeshVerifyEngine logs that."""
    for b in E.BUCKETS:
        if b >= E.NATIVE_MAX:
            assert F.kernel_width(b), b
        if b >= E.MESH_MIN:
            padded = M.pad_to_shards(b - 3, 4, bucket=b)
            assert padded == b and F.kernel_width(padded // 4), b
    assert not F.kernel_width(M.pad_to_shards(10240, 8, bucket=10240) // 8)
    assert not F.kernel_width(64) and not F.kernel_width(1000)


def test_cache_key_does_not_depend_on_the_caller(chip):
    """The persistent-cache key of a program that holds a Pallas kernel
    must be the same whatever Python stack first traced it (the chip run
    of PR 21 recompiled the ladder in a warm process because its first
    caller differed): ops/__init__.py keeps callers' frames out of the
    kernel's locations."""
    import hashlib

    from jax._src import cache_key

    def key():
        lowered = jax.jit(lambda a: EV.decompress_pubkeys(a)).lower(
            _S((1024, 32), jnp.uint8, chip))
        h = hashlib.sha256()
        cache_key._hash_computation(
            h, lowered.compiler_ir(), cache_key.IgnoreCallbacks.NO)
        return h.hexdigest()

    def from_another_stack():
        return (lambda: key())()

    assert key() == from_another_stack()


@pytest.mark.parametrize("b", [1024, 10240])
def test_decompress_pubkeys_compiles_for_v5e(chip, b):
    """A's decompression and the 128 doublings of the cached pair."""
    c = _compile(EV.decompress_pubkeys, _S((b, 32), jnp.uint8, chip),
                 scopes=("ladder.decompress", "ladder.a_hi"),
                 kernels=("curve_decompress", "curve_mul_2_128"))
    assert _kernels(c) == 2


@pytest.mark.parametrize("b", [1024, 10240])
def test_ladder_compiles_for_v5e(chip, b):
    """verify_batch_cached_a: the production ladder entry (R decompress
    kernel + the fused ladder kernel, given the cached pair: two lane
    tables in the VMEM scratch)."""
    c = _compile(EV.verify_batch_cached_a, *_ladder_args(b, chip),
                 scopes=("ladder.scalar_reduce", "ladder.decompress",
                         "ladder.double_scalar", "ladder.compare"),
                 kernels=("curve_decompress", "curve_ladder_sub_mul8"))
    assert _kernels(c) == 2


def _four_chips(topo):
    """(mesh, lanes-first sharding, lanes-last sharding, b): the engine's
    layout of the 10240 bucket over the four described devices."""
    mesh = Mesh(np.asarray(topo.devices), ("sig",))
    b = M.pad_to_shards(10_000, 4, bucket=E._bucket(10_000))
    return (mesh, NamedSharding(mesh, P("sig")),
            NamedSharding(mesh, P(None, "sig")), b)


def test_sharded_verifier_compiles_for_four_chips(topo, chip):
    """The shard_map verifier of MeshVerifyEngine over a 4-device Mesh
    built from the described devices: 10240 lanes, 2560 a shard, against
    the pair the staging program left on the shards (limbs sharded on
    their last axis). A shard runs the single chip's program: nothing of
    A is computed in it."""
    mesh, sh, sh_limbs, b = _four_chips(topo)
    fn = M.sharded_verify_rsk_fn(mesh, ("sig",))
    compiled = fn.lower(*_ladder_args(b, sh, limbs_sh=sh_limbs)).compile()
    assert _kernels(compiled) == 2  # R's decompression + the ladder
    text = compiled.as_text()
    assert "curve_ladder_sub_mul8" in text and "curve_mul_2_128" not in text
    assert "all-reduce" in text  # the invalid-lane psum
    assert compiled.memory_analysis().temp_size_in_bytes < TEMP_BUDGET_BYTES


def test_sharded_staging_compiles_for_four_chips(topo, chip):
    """The engine's staging program, decompress_pubkeys in every shard:
    A's decompression and the 128 doublings at 2560 lanes a shard, no
    collective, the pair left sharded on the lanes; and it is
    jit(decompress_pubkeys) to jax.monitoring, which the benchmark's
    "no verify program compiles inside the window" check matches."""
    mesh, sh, sh_limbs, b = _four_chips(topo)
    fn = M.sharded_decompress_pubkeys_fn(mesh, ("sig",))
    lowered = fn.lower(_S((b, 32), jnp.uint8, sh))
    assert "jit_decompress_pubkeys" in lowered.as_text()[:400]
    compiled = lowered.compile()
    assert _kernels(compiled) == 2
    text = compiled.as_text()
    names = set(re.findall(r'op_name="([^"]*)"', text))
    for kernel in ("curve_decompress", "curve_mul_2_128"):
        assert any(n.endswith(f"/{kernel}/pallas_call") for n in names)
    assert "all-reduce" not in text and "all-gather" not in text
    ok_a, (neg_a, neg_a_hi) = compiled.output_shardings
    assert ok_a.is_equivalent_to(sh, 1)
    for limb in (*neg_a, *neg_a_hi):
        assert limb.is_equivalent_to(sh_limbs, 2)
    assert compiled.memory_analysis().temp_size_in_bytes < TEMP_BUDGET_BYTES


@pytest.mark.parametrize("b", [2560] + [
    pytest.param(b, marks=pytest.mark.slow) for b in (1024, 4096, 16384)])
def test_pair_ladder_compiles_at_the_mesh_shard_widths(chip, b):
    """A mesh's shard runs the single chip's program on a quarter of
    the bucket (the pair the engine staged, 32 windows): each bucket
    from MESH_MIN up, at the width a shard of four sees."""
    assert b * 4 in E.BUCKETS and b * 4 >= E.MESH_MIN
    c = _compile(EV.verify_batch_cached_a, *_ladder_args(b, chip),
                 kernels=("curve_decompress", "curve_ladder_sub_mul8"))
    assert _kernels(c) == 2


@pytest.mark.slow
@pytest.mark.parametrize("b", [4096, 16384, 65536])
def test_ladder_other_buckets_compile_for_v5e(chip, b):
    c = _compile(EV.verify_batch_cached_a, *_ladder_args(b, chip))
    assert _kernels(c) == 2
    c = _compile(EV.decompress_pubkeys, _S((b, 32), jnp.uint8, chip))
    assert _kernels(c) == 2
