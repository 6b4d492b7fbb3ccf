"""The model names its own parts in the profiler's trace (ISSUE 25, device
side): every part of the stream step runs under a ``jax.named_scope``, the
names reach the lowered program's op names, none of them hides a Mosaic
kernel's name, and naming changes nothing the program computes."""

import contextlib
import re

import jax
import numpy as np
import pytest

from ai_rtc_agent_tpu.models import registry
from ai_rtc_agent_tpu.ops.pallas import KERNEL_NAMES, mosaic_kernel_counts
from ai_rtc_agent_tpu.stream.scheduler import BatchScheduler

# ISSUE 25's list: the step's parts, the bucket's gather/scatter, the UNet's
# blocks, a block's members, a transformer's members
STEP_SCOPES = (
    "preprocess", "vae_encode", "add_noise", "unet", "epilogue", "vae_decode",
    "postprocess", "gather", "scatter",
)
UNET_SCOPES = (
    "time_embed", "conv_in", "down_0", "down_1", "mid", "up_0", "up_1",
    "conv_out", "resnet_0", "transformer_0", "downsample", "upsample",
    "self_attn", "cross_attn", "ff", "proj", "norm",
)
_WRAPPED = re.compile(r"^[a-z_]+\((.*)\)$")


def _scheduler(**cfg_overrides):
    # the kernels ride along in interpret mode, the 4-stage R-CFG default:
    # the graph with every scope in it
    bundle = registry.load_model_bundle("tiny-test", attn_impl="pallas")
    cfg = registry.default_stream_config(
        "tiny-test", attn_impl="pallas", use_fused_epilogue=True,
        height=32, width=32, **cfg_overrides,
    )
    return BatchScheduler(
        bundle.stream_models, bundle.params, cfg, bundle.encode_prompt,
        max_sessions=2, prewarm=False,
    )


@pytest.fixture(scope="module")
def scope_components():
    """Every component of every op name of the lowered k=2 bucket step,
    transformations unwrapped (``vmap(unet)`` -> ``unet``)."""
    s = _scheduler()
    try:
        text = s._bucket_step(2, "full").lower(*s._bucket_specs(2)).as_text(
            debug_info=True
        )
    finally:
        s.close()
    comps = set()
    for op_name in re.findall(r'loc\("(jit\(bucket\)[^"]*)"', text):
        for c in op_name.split("/"):
            while (m := _WRAPPED.match(c)) is not None:
                c = m.group(1)
            comps.add(c)
    return comps


@pytest.mark.parametrize("scope", STEP_SCOPES + UNET_SCOPES)
def test_lowered_bucket_step_carries_the_scope(scope_components, scope):
    assert scope in scope_components


def test_two_tower_family_names_its_addition_embedding():
    """The ``text_time`` branch of ``time_cond_embedding`` (the ``sdxl`` and
    ``tinyxl`` families) is the scope ``add_embedding`` under ``time_embed``:
    its two linear layers and the ``time_ids`` sinusoid."""
    bundle = registry.load_model_bundle("tinyxl-test")
    cfg = registry.default_stream_config("tinyxl-test")
    s = BatchScheduler(
        bundle.stream_models, bundle.params, cfg, bundle.encode_prompt,
        max_sessions=1, prewarm=False,
    )
    try:
        text = s._bucket_step(1, "full").lower(*s._bucket_specs(1)).as_text(
            debug_info=True
        )
    finally:
        s.close()
    inside = re.findall(r'loc\("jit\(bucket\)[^"]*time_embed/add_embedding/([a-z_]+)', text)
    assert "dot_general" in inside and "cos" in inside, sorted(set(inside))


def test_no_scope_contains_a_mosaic_kernels_name(scope_components):
    """benchmark/trace_reduce.py and mosaic_kernel_counts find a kernel by
    that substring of an op's name: a scope named after one would add its
    wrapper's casts and transposes to the kernel's time.  The kernel's own
    ``pallas_call(name=...)`` is the only component that may carry it."""
    for kernel in KERNEL_NAMES:
        assert kernel in scope_components  # the pallas_call's own name
        assert [c for c in scope_components if kernel in c and c != kernel] == []
    assert "bucket" in scope_components  # the readers match the program by it


def test_scoped_step_is_bit_identical_to_the_unscoped_one(monkeypatch):
    """Named scopes are op metadata only: same outputs, same kernels."""

    def run():
        s = _scheduler()
        try:
            compiled = s._bucket_step(1, "full").lower(*s._bucket_specs(1)).compile()
            a = s.claim("a", prompt="p", seed=3)
            rng = np.random.default_rng(11)
            outs = [
                a(rng.integers(0, 256, (32, 32, 3), np.uint8)) for _ in range(5)
            ]
            return outs, mosaic_kernel_counts(compiled.as_text()), compiled.as_text()
        finally:
            s.close()

    scoped, kernels, text = run()
    monkeypatch.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
    plain, plain_kernels, plain_text = run()
    assert "self_attn" in text and "self_attn" not in plain_text
    assert kernels == plain_kernels
    for a, b in zip(scoped, plain):
        assert a.dtype == np.uint8 and np.array_equal(a, b)
