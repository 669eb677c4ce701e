"""Test config: force an 8-device virtual CPU mesh BEFORE jax initializes.

The analogue of the reference's distributed-without-a-cluster strategy (Spark
tests run `local[*]` inside the JUnit JVM — SURVEY §4): sharding/pjit tests
run against 8 virtual CPU devices so multi-chip code paths execute on one
host.
"""

import os

# Unit tests run on the CPU whatever is attached: a chip belongs to one
# process, and gradient checks need f64.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture
def rng():
    return np.random.default_rng(12345)
