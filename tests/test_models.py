"""Tiny-config model tests on CPU (SURVEY.md section 4 'Integration' tier)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ai_rtc_agent_tpu.models import clip as C
from ai_rtc_agent_tpu.models import controlnet as CN
from ai_rtc_agent_tpu.models import taesd as T
from ai_rtc_agent_tpu.models import unet as U


def test_taesd_shapes_and_range(rng):
    cfg = T.TAESDConfig.tiny()
    params = T.init_taesd(jax.random.PRNGKey(0), cfg)
    x = jnp.asarray(rng.random((2, 32, 32, 3)).astype(np.float32))
    z = T.encode(params["encoder"], x, cfg)
    assert z.shape == (2, 8, 8, 4)  # 2 stages -> /4
    y = T.decode(params["decoder"], z, cfg)
    assert y.shape == (2, 32, 32, 3)
    assert float(y.min()) >= 0.0 and float(y.max()) <= 1.0


def test_taesd_jit_compiles(rng):
    cfg = T.TAESDConfig.tiny()
    params = T.init_taesd(jax.random.PRNGKey(0), cfg)
    f = jax.jit(lambda p, x: T.decode(p["decoder"], T.encode(p["encoder"], x, cfg), cfg))
    y = f(params, jnp.zeros((1, 16, 16, 3)))
    assert y.shape == (1, 16, 16, 3)


def test_unet_tiny_forward(rng):
    cfg = U.UNetConfig.tiny()
    params = U.init_unet(jax.random.PRNGKey(1), cfg)
    x = jnp.asarray(rng.standard_normal((2, 8, 8, 4)).astype(np.float32))
    t = jnp.array([999, 10])
    ctx = jnp.asarray(rng.standard_normal((2, 7, 32)).astype(np.float32))
    out = U.apply_unet(params, x, t, ctx, cfg)
    assert out.shape == (2, 8, 8, 4)
    assert np.isfinite(np.asarray(out)).all()


def test_unet_sdxl_style_added_cond(rng):
    cfg = U.UNetConfig.tiny_xl()
    params = U.init_unet(jax.random.PRNGKey(2), cfg)
    x = jnp.zeros((1, 8, 8, 4))
    ctx = jnp.zeros((1, 7, 32))
    added = {
        "time_ids": jnp.asarray(np.array([[32, 32, 0, 0, 32, 32]], np.float32)),
        "text_embeds": jnp.zeros((1, 16)),
    }
    out = U.apply_unet(params, x, jnp.array([999]), ctx, cfg, added_cond=added)
    assert out.shape == (1, 8, 8, 4)
    # missing added_cond must raise for text_time configs
    import pytest

    with pytest.raises(ValueError):
        U.apply_unet(params, x, jnp.array([999]), ctx, cfg)


def test_unet_timestep_sensitivity(rng):
    cfg = U.UNetConfig.tiny()
    params = U.init_unet(jax.random.PRNGKey(3), cfg)
    x = jnp.asarray(rng.standard_normal((1, 8, 8, 4)).astype(np.float32))
    ctx = jnp.asarray(rng.standard_normal((1, 7, 32)).astype(np.float32))
    o1 = U.apply_unet(params, x, jnp.array([10]), ctx, cfg)
    o2 = U.apply_unet(params, x, jnp.array([900]), ctx, cfg)
    assert not np.allclose(np.asarray(o1), np.asarray(o2))


def test_controlnet_zero_init_is_noop(rng):
    cfg = U.UNetConfig.tiny()
    unet_p = U.init_unet(jax.random.PRNGKey(4), cfg)
    cn_p = CN.init_controlnet(jax.random.PRNGKey(5), cfg)
    x = jnp.asarray(rng.standard_normal((1, 8, 8, 4)).astype(np.float32))
    t = jnp.array([500])
    ctx = jnp.asarray(rng.standard_normal((1, 7, 32)).astype(np.float32))
    cond = jnp.asarray(rng.random((1, 64, 64, 3)).astype(np.float32))

    down_res, mid_res = CN.apply_controlnet(cn_p, x, t, ctx, cond, cfg)
    # zero convs: every residual must be exactly zero at init
    for r in down_res + [mid_res]:
        assert float(jnp.abs(r).max()) == 0.0

    base = U.apply_unet(unet_p, x, t, ctx, cfg)
    controlled = U.apply_unet(
        unet_p, x, t, ctx, cfg, down_residuals=down_res, mid_residual=mid_res
    )
    np.testing.assert_allclose(np.asarray(base), np.asarray(controlled), atol=0)


def test_canny_soft_edges(rng):
    img = np.zeros((1, 32, 32, 3), np.float32)
    img[:, :, 16:] = 1.0  # vertical step edge
    e = np.asarray(CN.canny_soft(jnp.asarray(img)))
    assert e.shape == (1, 32, 32, 3)
    assert e[0, 16, 16, 0] > 0.9  # strong response at the edge
    assert e[0, 16, 4, 0] < 0.1  # flat region quiet


def test_clip_text_shapes_and_pooled(rng):
    cfg = C.CLIPTextConfig.tiny()
    params = C.init_clip_text(jax.random.PRNGKey(6), cfg)
    ids = np.zeros((2, 16), np.int32)
    ids[0, :5] = [10, 40, 30, 20, 255]  # eot = argmax = position 4
    ids[1, :3] = [7, 255, 9]
    out = C.apply_clip_text(params, jnp.asarray(ids), cfg)
    assert out["hidden"].shape == (2, 16, 32)
    assert out["pooled"].shape == (2, 32)


def test_clip_causality(rng):
    """Changing a later token must not affect earlier hidden states."""
    cfg = C.CLIPTextConfig.tiny()
    params = C.init_clip_text(jax.random.PRNGKey(7), cfg)
    ids1 = np.ones((1, 8), np.int32) * 3
    ids2 = ids1.copy()
    ids2[0, 6] = 99
    h1 = np.asarray(C.apply_clip_text(params, jnp.asarray(ids1), cfg)["hidden"])
    h2 = np.asarray(C.apply_clip_text(params, jnp.asarray(ids2), cfg)["hidden"])
    np.testing.assert_allclose(h1[0, :6], h2[0, :6], atol=1e-5)
    assert not np.allclose(h1[0, 6:], h2[0, 6:])


def test_clip_skip_penultimate():
    cfg0 = C.CLIPTextConfig.tiny()
    cfg1 = C.CLIPTextConfig(
        vocab_size=256, max_length=16, width=32, layers=2, heads=4, clip_skip=1
    )
    params = C.init_clip_text(jax.random.PRNGKey(8), cfg0)
    ids = jnp.asarray(np.ones((1, 8), np.int32))
    h0 = np.asarray(C.apply_clip_text(params, ids, cfg0)["hidden"])
    h1 = np.asarray(C.apply_clip_text(params, ids, cfg1)["hidden"])
    assert not np.allclose(h0, h1)


def test_default_stream_config_families():
    """Config routing: turbo ids get the 1-step turbo schedule; UNDISTILLED
    SD2.x gets the stream-batch LCM schedule (a 1-step schedule on a
    non-distilled checkpoint produces noise), with 768/v-prediction for
    stable-diffusion-2-1 and 512/epsilon for -base."""
    from ai_rtc_agent_tpu.models import registry

    turbo = registry.default_stream_config("stabilityai/sd-turbo")
    assert turbo.scheduler == "turbo" and turbo.t_index_list == (0,)

    sd21 = registry.default_stream_config("stabilityai/stable-diffusion-2-1")
    assert sd21.scheduler == "lcm" and len(sd21.t_index_list) == 4
    assert sd21.prediction_type == "v_prediction"
    assert sd21.height == 768

    sd21b = registry.default_stream_config("stabilityai/stable-diffusion-2-1-base")
    assert sd21b.prediction_type == "epsilon" and sd21b.height == 512

    xl = registry.default_stream_config("stabilityai/sdxl-turbo")
    assert (xl.height, xl.width) == (512, 512) and xl.use_added_cond
    xl_base = registry.default_stream_config("stabilityai/sdxl-base-1.0")
    assert (xl_base.height, xl_base.width) == (1024, 1024) and xl_base.use_added_cond

    sd15 = registry.default_stream_config("lykon/dreamshaper-8")
    assert sd15.scheduler == "lcm" and sd15.cfg_type == "self"


def test_sdxl_turbo_is_served_as_published():
    """The model card's stream (512x512, one step, no guidance) and the
    pipeline's text path: ``StableDiffusionXLPipeline.encode_prompt`` takes
    ``hidden_states[-2]`` of BOTH towers (no final norm); the text embedding
    is the second tower's projection."""
    from ai_rtc_agent_tpu.models import registry

    cfg = registry.default_stream_config("stabilityai/sdxl-turbo")
    assert (cfg.height, cfg.width) == (512, 512)
    assert cfg.t_index_list == (0,) and cfg.num_inference_steps == 1
    assert cfg.scheduler == "turbo" and cfg.timestep_spacing == "trailing"
    assert cfg.cfg_type == "none" and cfg.use_added_cond
    unet_cfg, tower1, _ = registry._model_configs("sdxl")
    tower2 = C.CLIPTextConfig.sdxl_g()
    assert (tower1.width, tower1.layers, tower1.clip_skip) == (768, 12, 1)
    assert (tower2.width, tower2.layers, tower2.clip_skip) == (1280, 32, 1)
    assert tower2.use_text_projection and not tower1.use_text_projection
    assert unet_cfg.cross_attention_dim == tower1.width + tower2.width == 2048


@pytest.mark.parametrize("clip_skip", [0, 1])
def test_clip_text_agrees_with_the_plain_reference(clip_skip):
    """``apply_clip_text`` against ``benchmark/reference/models.py`` on one
    seeded tree at a tiny width: the hidden states cross attention is fed
    (last layer final-normed at ``clip_skip`` 0, the layer before it raw at
    1) and the projected end-of-text embedding.  Tolerance 2e-5 absolute on
    values of order one: both sides are float32 on the CPU and differ in
    the order of their sums only; the two ``clip_skip`` readings are 0.1
    and more apart, so taking the wrong layer fails it."""
    from benchmark.reference import models as ref_models

    cfg = C.CLIPTextConfig(
        vocab_size=256, max_length=16, width=32, layers=3, heads=4,
        clip_skip=clip_skip, use_text_projection=True, projection_dim=24,
    )
    params = C.init_clip_text(jax.random.PRNGKey(4), cfg)
    # init's norms are exactly (1, 0): move them, or a dropped norm hides
    params = jax.tree.map(
        lambda a: a + 0.1 * jax.random.normal(jax.random.PRNGKey(a.size), a.shape),
        params,
    )
    ids = jnp.asarray([[254, 7, 99, 3, 255] + [255] * 11, [254, 1, 2, 255] + [255] * 12])
    t = {"hidden_act": cfg.activation, "num_attention_heads": cfg.heads,
         "clip_skip": clip_skip}
    ours = C.apply_clip_text(params, ids, cfg)
    with jax.default_matmul_precision("highest"):
        hidden, text = ref_models.clip_text_projected(params, ids, t)
        other = ref_models.clip_text(params, ids, dict(t, clip_skip=1 - clip_skip))
    np.testing.assert_allclose(ours["hidden"], hidden, atol=2e-5, rtol=0)
    np.testing.assert_allclose(ours["projected"], text, atol=2e-5, rtol=0)
    assert np.abs(np.asarray(ours["hidden"]) - np.asarray(other)).max() > 0.1


def test_sdxl_turbo_tree_is_the_benchmark_files():
    """The program's tree for ``stabilityai/sdxl-turbo``, as shapes, against
    what ``benchmark/configs/sdxlturbo512.json`` lays out: the same leaves,
    3,387,629,067 parameters, so the file and the program cannot drift
    apart.  Nothing is materialised (``eval_shape``)."""
    import json
    import math
    import os

    from ai_rtc_agent_tpu.models import registry
    from benchmark.reference import sdxl_stream
    from benchmark.weights import same_layout

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs", "sdxlturbo512.json")) as f:
        cfg = json.load(f)
    assert cfg["program_model_id"] == "stabilityai/sdxl-turbo"
    shapes = jax.eval_shape(
        lambda: registry.load_model_bundle(cfg["program_model_id"]).params
    )
    assert same_layout(sdxl_stream.weight_shapes(cfg), shapes) is None
    assert sorted(shapes) == sorted(cfg["program_text_subtrees"] + ["unet", "taesd"])
    n = sum(math.prod(a.shape) for a in jax.tree.leaves(shapes))
    assert n == 3_387_629_067
    stream = registry.default_stream_config(cfg["program_model_id"])
    assert (stream.height, stream.width) == (cfg["stream"]["height"], cfg["stream"]["width"])
    assert cfg["text_encoder"]["clip_skip"] == registry._model_configs("sdxl")[1].clip_skip == 1


def test_v_prediction_stream_end_to_end(rng):
    """The v-prediction path (SD2.1-768 family) streams end to end."""
    from ai_rtc_agent_tpu.models import registry
    from ai_rtc_agent_tpu.stream.engine import StreamEngine

    bundle = registry.load_model_bundle("tiny-test")
    cfg = registry.default_stream_config(
        "tiny-test", prediction_type="v_prediction"
    )
    eng = StreamEngine(
        bundle.stream_models, bundle.params, cfg, bundle.encode_prompt
    ).prepare("v-pred stream", seed=4)
    frame = rng.integers(0, 256, (cfg.height, cfg.width, 3), dtype=np.uint8)
    for _ in range(3):
        out = eng(frame)
    assert out.shape == frame.shape and out.dtype == np.uint8
