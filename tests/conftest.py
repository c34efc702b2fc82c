"""Test harness: 8 virtual CPU devices — the MiniCluster analog.

The reference exercises distributed behavior with Flink's in-process
MiniCluster (multiple parallel subtasks in one JVM, SURVEY.md §4 tier 2).
Here we force the JAX CPU backend with 8 virtual devices so shard_map /
collective paths run multi-device without TPU hardware.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ.setdefault("JAX_ENABLE_X64", "1")

import jax  # noqa: E402

# Tests run on the CPU backend (JAX_PLATFORMS=cpu in the tier-1 command);
# the config update (post-import, pre-device-init) pins it even where a
# TPU is attached, so the 8-virtual-device test mesh is always the CPU's.
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session")
def devices():
    return jax.devices()


# Canonical 5-vertex / 7-edge fixture used across the reference's operation
# tests (T/test/GraphStreamTestUtils.java:29-68): edges (1,2,12) ... (5,1,51).
REFERENCE_EDGES = [
    (1, 2, 12.0),
    (1, 3, 13.0),
    (2, 3, 23.0),
    (3, 4, 34.0),
    (3, 5, 35.0),
    (4, 5, 45.0),
    (5, 1, 51.0),
]


@pytest.fixture
def reference_edges():
    return list(REFERENCE_EDGES)
