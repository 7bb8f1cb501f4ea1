"""Test harness: force an 8-device virtual CPU mesh BEFORE jax import.

This is the TPU-native analogue of the reference's gloo/CPU fallback path
(cifar10_mpi_mobilenet_224.py:34,41-43) — multi-device sharding logic is
exercised on any machine with no TPU attached (SURVEY.md section 4).
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

# The tests never touch a chip, whatever JAX_PLATFORMS the caller's
# shell exported before this file ran: pin the config too (must happen
# before the first backend use).
jax.config.update("jax_platforms", "cpu")

# Persistent compilation cache: repeated Trainer/jit builds across test
# files reuse compiled executables instead of re-tracing XLA each time.
# Shared convention (path + thresholds) lives in tpunet.utils.cache:
# <checkout>/.jax_cache unless JAX_COMPILATION_CACHE_DIR says otherwise.
from tpunet.utils.cache import enable_persistent_compile_cache  # noqa: E402

enable_persistent_compile_cache()

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session")
def rng_np():
    return np.random.default_rng(42)
