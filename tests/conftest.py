"""Test harness config: force CPU JAX with a virtual 8-device mesh.

Per SURVEY.md section 4 the multi-chip story is tested on a simulated mesh
(`--xla_force_host_platform_device_count=8`) — the standard JAX stand-in for
multi-chip without real hardware.  Must run before jax initializes a backend.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
# The suite engages NO persistent XLA compile cache: the program places one
# from its process entry points only (utils/device.py), never at import.
# PR 6 tried the cache here as a wall-time shave and reverted it: on the
# virtual 8-device CPU mesh it served colliding executables across engine
# instances (tp-parity and quant-parity tests got all-zero frames).  That
# was an older JAX; re-enable only with a green parity run.
os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")

import jax  # noqa: E402

# also as a config update: JAX_PLATFORMS is only read at import, and the
# Pallas interpret-mode rule reads the platform request from the config
jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture
def rng():
    return np.random.default_rng(0)


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: full-geometry tests (minutes on the 1-core CPU box)"
    )
