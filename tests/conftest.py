"""Test config: force an 8-virtual-device CPU platform before jax imports.

Mirrors the reference's testing approach (realhf/base/testing.py fabricates
topologies without a cluster): distributed sharding logic is exercised on a
virtual CPU mesh; speeds are measured on the chip by benchmarks/run.py, not
by tests.
"""

import os

# force CPU even when the ambient environment selects a TPU platform —
# tests exercise distributed sharding on 8 virtual devices
os.environ["JAX_PLATFORMS"] = "cpu"
xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

# The engines turn the persistent compile cache on (utils/runtime.py).  The
# suite compiles everything itself: no test may depend on what an earlier
# run left in the checkout's .jax_cache, and XLA:CPU executables read back
# from it warn about machine features on every load.
jax.config.update("jax_enable_compilation_cache", False)

# Numerics tests compare against fp32 torch references; XLA:CPU's default
# (lower) einsum precision would drown parity in ~1e-3 noise.
jax.config.update("jax_default_matmul_precision", "highest")

import pytest  # noqa: E402

# areal-lint fixture trees under data/ contain test-shaped files (e.g. the
# C4 dead-module tree's tests/ dir) that are lint *inputs*, not tests
collect_ignore_glob = ["data/*"]


@pytest.fixture(autouse=True)
def _seed():
    from areal_tpu.utils import seeding

    seeding.set_random_seed(1, "test")
    yield


@pytest.fixture(autouse=True)
def _fresh_name_resolve():
    from areal_tpu.utils import name_resolve

    name_resolve.DEFAULT_REPOSITORY = name_resolve.MemoryNameRecordRepository()
    yield
    name_resolve.DEFAULT_REPOSITORY.reset()
