"""Test configuration: run everything on 8 virtual CPU devices.

The reference runs its suite as 4 MPI processes on one host
(reference Makefile:14-52, scripts/run_unittest.sh).  JAX gives a better
story: ``--xla_force_host_platform_device_count`` provides N devices in one
process, so "ranks" are devices and the whole suite is single-process
(SURVEY.md §4).  This must run before jax initializes a backend, hence the
env mutation at import time.
"""

import os

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
)

import jax

# The suite runs on the CPU wherever it is started, a machine with chips
# included (works because no backend has been initialized yet at
# conftest import time).
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)

import pytest  # noqa: E402


@pytest.fixture
def bf_ctx():
    """Fresh bluefog context over all 8 virtual devices."""
    import bluefog_tpu as bf

    bf.init()
    yield bf
    bf.shutdown()
