"""Test configuration: force an 8-device virtual CPU platform.

The tests never touch a chip (the chip is reached only through
chip_smoke.py): sharding tests run on a virtual 8-device CPU mesh (same
XLA partitioner as a real TPU). XLA_FLAGS is set before the CPU client
initializes (first device use), and the platform is pinned via
jax.config, so a bare `pytest` on a chip host stays on the CPU too.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture
def rng():
    return np.random.default_rng(0x7B9)


@pytest.fixture
def traced():
    """The tracer on and empty for one test (yields the module); test
    files with needs of their own keep their own fixture of this name."""
    from tigerbeetle_tpu import tracer

    was = tracer.enabled()
    tracer.enable()
    tracer.reset()
    yield tracer
    tracer.reset()
    if not was:
        tracer.disable()
