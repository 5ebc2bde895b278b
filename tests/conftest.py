"""Test config: run JAX on a virtual 8-device CPU mesh (no TPU needed).

Mirrors the reference's "fake cluster" testing stance (SURVEY.md section 4:
MemStore + localhost daemons); here the CPU backend with 8 virtual devices
is the hardware-free cluster.
"""

import os

# Force CPU whatever the host has attached: unit tests must be
# hardware-free and fast. Set before jax is imported, so the backend
# never initializes on anything else.
xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture
def rng():
    return np.random.default_rng(0xCEF)
