"""The Pallas paths are selected by config and env, never by try/except.

The fused epilogue and flash attention default ON for TPU serving.  The env
switches (``FUSED_EPILOGUE=0`` / ``ATTN_IMPL=xla``) are the explicit way to
serve without a kernel; a kernel that fails at the pipeline's one warm-up
step fails the build with its own error — there is no rebuild on another
graph (stream/pipeline._warm_up).
"""

from types import SimpleNamespace

import numpy as np
import pytest

import jax

from ai_rtc_agent_tpu.models import registry
from ai_rtc_agent_tpu.ops import pallas
from ai_rtc_agent_tpu.stream.engine import StreamEngine
from ai_rtc_agent_tpu.stream.pipeline import StreamDiffusionPipeline


def test_fused_epilogue_env_killswitch(monkeypatch):
    # simulate a TPU backend: fused epilogue defaults ON ...
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert registry.default_stream_config("tiny-test").use_fused_epilogue
    # ... and FUSED_EPILOGUE=0 turns it off without a code change
    monkeypatch.setenv("FUSED_EPILOGUE", "0")
    assert not registry.default_stream_config("tiny-test").use_fused_epilogue


def test_fused_epilogue_env_force_on(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert not registry.default_stream_config("tiny-test").use_fused_epilogue
    monkeypatch.setenv("FUSED_EPILOGUE", "1")
    assert registry.default_stream_config("tiny-test").use_fused_epilogue


def test_explicit_override_beats_env(monkeypatch):
    monkeypatch.setenv("FUSED_EPILOGUE", "0")
    cfg = registry.default_stream_config("tiny-test", use_fused_epilogue=True)
    assert cfg.use_fused_epilogue


def test_kernel_failure_at_warm_up_fails_the_build(monkeypatch):
    """A synthetic kernel failure during the warm-up step surfaces as THE
    build error: no pipeline comes back on a composed-XLA graph."""
    calls = []

    def failing(self, frame):
        calls.append(self.cfg)
        raise RuntimeError("synthetic pallas miscompile")

    monkeypatch.setattr(StreamEngine, "__call__", failing)
    cfg = registry.default_stream_config("tiny-test", use_fused_epilogue=True)
    with pytest.raises(RuntimeError, match="synthetic pallas miscompile"):
        StreamDiffusionPipeline("tiny-test", config=cfg)
    # one attempt, on the configured graph — no second build was tried
    assert len(calls) == 1 and calls[0].use_fused_epilogue


def test_warm_up_runs_the_configured_graph():
    """With a kernel in the graph the build runs one step, and the pipeline
    that comes back still carries the config it was asked for."""
    cfg = registry.default_stream_config("tiny-test", use_fused_epilogue=True)
    pipe = StreamDiffusionPipeline("tiny-test", config=cfg)
    assert pipe.config.use_fused_epilogue is True
    out = pipe(np.zeros((64, 64, 3), np.uint8))
    assert out.shape == (64, 64, 3) and out.dtype == np.uint8


def test_warm_up_skipped_when_no_pallas_path(monkeypatch):
    """CPU default config (fused off, xla attention) must not pay a
    warm-up step at pipeline build (the suite builds many pipelines)."""
    calls = []
    orig_call = StreamEngine.__call__

    def counting(self, frame):
        calls.append(1)
        return orig_call(self, frame)

    monkeypatch.setattr(StreamEngine, "__call__", counting)
    StreamDiffusionPipeline("tiny-test")
    assert calls == []


def test_interpret_mode_only_on_an_explicit_cpu(monkeypatch):
    """Interpret mode is for a CPU that was asked for by name; a backend
    that is not the CPU compiles, and a CPU nobody asked for (JAX_PLATFORMS
    unset, no TPU found) is an error instead of a silent interpreter."""
    assert pallas.interpret_default() is True  # conftest: jax_platforms=cpu
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert pallas.interpret_default() is False
    silent_fallback = SimpleNamespace(
        default_backend=lambda: "cpu",
        config=SimpleNamespace(jax_platforms=None),
    )
    monkeypatch.setattr(pallas, "jax", silent_fallback)
    with pytest.raises(RuntimeError, match="JAX_PLATFORMS=cpu"):
        pallas.interpret_default()
