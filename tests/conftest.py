"""Test config: force an 8-device virtual CPU platform.

Mirrors SURVEY.md §4's rebuild test pyramid: all unit/sharding tests run on
CPU with xla_force_host_platform_device_count=8 so the data-parallel mesh is
exercised without a TPU pod.  The chip is met outside pytest
(``chip_smoke.py``); the one file that talks to the TPU compiler,
tests/test_tpu_kernels.py, describes its topology inside a fixture.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

assert jax.devices()[0].platform == "cpu", jax.devices()

import numpy as np  # noqa: E402
import pytest  # noqa: E402

import pathlib  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))
from __graft_entry__ import machine_cache_dir  # noqa: E402

# persistent compile cache (full-model CPU compiles dominate suite
# runtime).  The suite's cache lives OUTSIDE the checkout, at a fixed path
# keyed by machine fingerprint (entries AOT-compiled on a different host
# are rejected at load and risk SIGILL): the chip tool copies the checkout
# as it is on disk, and a suite's worth of CPU executables has no business
# in that copy.  Like the program's own cache (compile/registry.py
# setup_compile_cache) it yields to JAX_COMPILATION_CACHE_DIR — jax reads
# that variable itself.  machine_cache_dir reads JAX_TEST_CACHE for the
# base dir.
if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    jax.config.update("jax_compilation_cache_dir", machine_cache_dir())
jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)


@pytest.fixture
def rng():
    return np.random.RandomState(42)
