"""Entry-point device policy: where the program runs, and where it keeps
compiled code.

The program runs where ``JAX_PLATFORMS`` says.  When that is unset it was
written for a TPU, and JAX's silent fallback to the CPU (float32, kernels
off, minutes per frame) must not pass for a working deployment: the process
entry points (agent ``main``, ``assets/build_engines``, ``bench.py``, the
children of ``chip_smoke.py``) call :func:`require_device` first and exit
non-zero instead.  CPU work — the tests, the verify recipe — says
``JAX_PLATFORMS=cpu``.

Called from entry points, never at package import: the test suite imports
the package and must engage no persistent cache.
"""

from __future__ import annotations

import logging
import os

import jax

from .env import REPO_ROOT, get_str

logger = logging.getLogger(__name__)

# one fixed path: the directory is part of what a cached executable is found
# by, so a cache that moves (a pid, a timestamp, /tmp) never hits
COMPILE_CACHE_DIR = os.path.join(REPO_ROOT, ".jax_cache")


def configure_compile_cache() -> str:
    """Point XLA's persistent compile cache at the checkout unless
    ``JAX_COMPILATION_CACHE_DIR`` already places it (then nothing is set
    here: JAX reads the variable itself).  Returns the directory in use."""
    placed = get_str("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    return COMPILE_CACHE_DIR


def device_info() -> dict:
    """The device as JAX reports it — the identity every printed result
    and ``/health`` carries."""
    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
    }


def require_device() -> dict:
    """Entry-point guard: initialise the backend, refuse a CPU nobody asked
    for, place the compile cache, and log the one start-up line that names
    the device.  Returns :func:`device_info`."""
    requested = jax.config.jax_platforms  # JAX_PLATFORMS or a config update
    info = device_info()  # raises when the requested platform cannot start
    if not requested and info["platform"] != "tpu":
        raise SystemExit(
            f"no TPU found: JAX fell back to {info['platform']!r} "
            f"({info['device_kind']}). This program serves from a TPU; set "
            "JAX_PLATFORMS=cpu to run on the CPU on purpose."
        )
    cache_dir = configure_compile_cache()
    logger.info(
        "device: platform=%s device_kind=%s count=%d (JAX_PLATFORMS=%s); "
        "compile cache: %s",
        info["platform"], info["device_kind"], info["device_count"],
        requested or "unset", cache_dir,
    )
    return info
