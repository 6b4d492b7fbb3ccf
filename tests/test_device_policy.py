"""utils/device.py: where the program runs and where it keeps compiled code.

The program runs where JAX_PLATFORMS says; unset means the TPU, and a silent
CPU fallback is an exit, not a deployment.  The persistent compile cache is
placed from outside when JAX_COMPILATION_CACHE_DIR is set and at one fixed
git-ignored path in the checkout otherwise — by entry points only.
"""

import os
import subprocess
import sys
from types import SimpleNamespace

import pytest

from ai_rtc_agent_tpu.utils import device, env

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _fake_jax(platform, requested, updates):
    dev = SimpleNamespace(platform=platform, device_kind=f"{platform}-kind")
    return SimpleNamespace(
        devices=lambda: [dev],
        config=SimpleNamespace(
            jax_platforms=requested,
            update=lambda key, value: updates.append((key, value)),
        ),
    )


def test_unset_platform_requires_a_tpu(monkeypatch):
    updates = []
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(device, "jax", _fake_jax("cpu", None, updates))
    with pytest.raises(SystemExit, match="no TPU found"):
        device.require_device()
    assert updates == []  # nothing was configured on the way out

    monkeypatch.setattr(device, "jax", _fake_jax("tpu", None, updates))
    assert device.require_device() == {
        "platform": "tpu", "device_kind": "tpu-kind", "device_count": 1,
    }


def test_a_cpu_asked_for_by_name_is_served(monkeypatch):
    updates = []
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(device, "jax", _fake_jax("cpu", "cpu", updates))
    assert device.require_device()["platform"] == "cpu"
    # ... and the cache went to the one fixed path inside the checkout
    assert updates == [("jax_compilation_cache_dir", device.COMPILE_CACHE_DIR)]
    assert device.COMPILE_CACHE_DIR == os.path.join(REPO, ".jax_cache")


def test_a_cache_placed_from_outside_is_left_alone(monkeypatch, tmp_path):
    updates = []
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    monkeypatch.setattr(device, "jax", _fake_jax("tpu", None, updates))
    assert device.configure_compile_cache() == str(tmp_path)
    assert updates == []  # JAX reads the variable itself; the code sets nothing


def test_engine_cache_default_is_anchored_to_the_checkout(monkeypatch, tmp_path):
    monkeypatch.delenv("XLA_ENGINES_CACHE", raising=False)
    monkeypatch.chdir(tmp_path)  # the working directory does not matter
    assert env.engines_cache() == os.path.join(REPO, "models", "engines")
    monkeypatch.setenv("XLA_ENGINES_CACHE", str(tmp_path))
    assert env.engines_cache() == str(tmp_path)


def test_generated_paths_are_ignored_and_the_suite_engages_no_cache():
    import jax

    assert not jax.config.jax_compilation_cache_dir
    ignored = open(os.path.join(REPO, ".gitignore")).read().split()
    for path in (".jax_cache/", "native/*.so", "models/engines/", "chiprun_out/"):
        assert path in ignored


def test_agent_exits_non_zero_on_a_silent_cpu_fallback():
    """The real entry point, with JAX_PLATFORMS unset on a box with no TPU:
    it must exit before building a model, let alone serving."""
    child_env = dict(os.environ)
    for key in ("JAX_PLATFORMS", "PYTHONPATH", "XLA_FLAGS"):
        child_env.pop(key, None)
    r = subprocess.run(
        [sys.executable, "-m", "ai_rtc_agent_tpu.server.agent",
         "--model-id", "tiny-test", "--port", "0"],
        env=child_env, capture_output=True, text=True, timeout=120, cwd=REPO,
    )
    assert r.returncode != 0
    assert "no TPU found" in r.stderr
