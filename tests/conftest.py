"""Test configuration: run JAX on a virtual 8-device CPU mesh.

The suite never takes a chip: jax honours JAX_PLATFORMS (the driver sets
it to cpu), and the config pin below holds the suite to the CPU even
where the variable is unset, so `pytest` on a machine with a chip does
not grab it from whoever holds it. Multi-chip sharding is validated on
virtual CPU devices. What the chip's compiler accepts is asked of a
described (not attached) chip in tests/test_tpu_device.py; what runs on
the attached chip is chip_smoke.py's to prove, through the chip tool.
"""

import os

os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", "/tmp/jax_test_cache")
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "1")
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "0")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax

jax.config.update("jax_platforms", "cpu")

import pytest


@pytest.fixture(autouse=True)
def _fresh_metric_bundles():
    """Every test starts with empty singleton metric bundles: counters
    incremented by one test must not leak into another's assertions
    (utils.metrics.reset_bundles clears the default registry in place,
    so a live MetricsServer keeps serving the same Registry object)."""
    from cometbft_tpu.utils import metrics

    metrics.reset_bundles()
    yield
