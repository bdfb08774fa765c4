"""Test harness: force an 8-virtual-device CPU platform BEFORE jax initializes.

This is the TPU-world analog of the reference's SparkTestUtils.sparkTest
(`local[4]` in-process Spark, SparkTestUtils.scala:61-77): multi-device
semantics are simulated in one process so sharding/collective code paths are
exercised without real hardware.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
# Tests must exercise the real routing/compile paths, never a persistent
# cache left by an earlier run (a stale-but-correct cached plan would mask
# routing regressions): no plan cache, and no compile cache. The program
# keeps the compile cache off on the CPU anyway (utils/cachedir.py: a
# multi-device XLA:CPU program loaded back from it deadlocks), but a
# variable inherited from the caller's shell would switch JAX's own on.
os.environ["PHOTON_ML_TPU_PLAN_CACHE"] = ""
os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

# tests run on the CPU, whatever the caller's environment asked for
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", False)

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture
def rng():
    return np.random.default_rng(seed=42)


@pytest.fixture
def interpret_kernels():
    """The fused routed-map kernels through the Pallas interpreter (the
    engine's test hook, read when a program is traced)."""
    from photon_ml_tpu.ops import fused_perm

    old = fused_perm._INTERPRET
    fused_perm._INTERPRET = True
    yield
    fused_perm._INTERPRET = old
