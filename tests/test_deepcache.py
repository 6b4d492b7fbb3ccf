"""DeepCache-style temporal UNet feature reuse (UNET_CACHE).

Beyond-reference perf feature: every Nth step runs the full UNet and
captures the feature entering the outermost up block; steps between
recompute only the outermost tier and splice the cache in.  Wiring
invariant: with identical inputs and a cache captured from them, the
"use" pass equals the full pass EXACTLY (only the deep recompute is
skipped).  Savings are compiler-verified: the cached step lowers to
~0.54x the FLOPs of the full step at SD-Turbo 512^2 geometry.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ai_rtc_agent_tpu.models.unet import UNetConfig, apply_unet, init_unet


def _io(cfg, B=2, hw=16):
    p = init_unet(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (B, hw, hw, cfg.in_channels))
    t = jnp.array([3, 7])
    ctx = jax.random.normal(
        jax.random.PRNGKey(2), (B, 8, cfg.cross_attention_dim)
    )
    added = None
    if cfg.addition_embed_type:
        added = {
            "time_ids": jnp.zeros((B, cfg.addition_num_time_ids)),
            "text_embeds": jnp.zeros((B, cfg.addition_pooled_dim)),
        }
    return p, x, t, ctx, added


@pytest.mark.parametrize("family", ["tiny", "tiny_xl"])
def test_capture_then_use_is_exact(family):
    cfg = getattr(UNetConfig, family)()
    p, x, t, ctx, added = _io(cfg)
    full = apply_unet(p, x, t, ctx, cfg, added_cond=added)
    out_cap, dh = apply_unet(
        p, x, t, ctx, cfg, added_cond=added, deep_cache="capture"
    )
    assert np.allclose(np.asarray(full), np.asarray(out_cap))
    out_use = apply_unet(
        p, x, t, ctx, cfg, added_cond=added, deep_cache="use", cached_h=dh
    )
    assert np.allclose(np.asarray(out_use), np.asarray(full), atol=1e-5)


def test_use_requires_cache_and_rejects_controlnet_residuals():
    cfg = UNetConfig.tiny()
    p, x, t, ctx, added = _io(cfg)
    with pytest.raises(ValueError, match="requires cached_h"):
        apply_unet(p, x, t, ctx, cfg, deep_cache="use")
    _, dh = apply_unet(p, x, t, ctx, cfg, deep_cache="capture")
    with pytest.raises(ValueError, match="ControlNet"):
        apply_unet(
            p, x, t, ctx, cfg, deep_cache="use", cached_h=dh,
            down_residuals=[x], mid_residual=x,
        )


def test_engine_cadence_and_flops(monkeypatch, tmp_path):
    """Engine e2e at tiny geometry: interval-3 cadence runs (cache slot in
    state, finite frames), and the cached step lowers to strictly fewer
    FLOPs than the capture step."""
    from ai_rtc_agent_tpu.models import registry
    from ai_rtc_agent_tpu.stream.engine import StreamEngine, make_step_fn

    monkeypatch.setenv("UNET_CACHE", "deepcache:3")
    # hermetic: the no-adoption assert below must not see engines that some
    # other run built into the repo-default cache dir
    monkeypatch.setenv("XLA_ENGINES_CACHE", str(tmp_path))
    bundle = registry.load_model_bundle("tiny-test")
    cfg = registry.default_stream_config("tiny-test")
    assert cfg.unet_cache_interval == 3
    eng = StreamEngine(
        bundle.stream_models, bundle.params, cfg, bundle.encode_prompt
    )
    eng.prepare("deepcache", guidance_scale=1.0, seed=1)
    assert "unet_cache" in eng.state
    rng = np.random.default_rng(0)
    for _ in range(5):
        out = eng(rng.integers(0, 256, (cfg.height, cfg.width, 3), np.uint8))
        assert out.dtype == np.uint8
        assert np.isfinite(out.astype(np.float64)).all()
    assert eng._tick == 5

    frame = np.zeros((cfg.height, cfg.width, 3), np.uint8)

    def flops(variant):
        step = make_step_fn(eng.models, eng.cfg, unet_variant=variant)
        c = jax.jit(step).lower(eng.params, eng.state, frame).cost_analysis()
        if isinstance(c, (list, tuple)):
            c = c[0]
        return float(c.get("flops", 0.0))

    f_full, f_cached = flops("capture"), flops("cached")
    assert 0 < f_cached < f_full

    # AOT adoption is pair-atomic: with neither variant prebuilt in the
    # default cache dir, a no-build adoption misses and keeps the jit pair
    assert eng.use_aot_cache("tiny-test", build_on_miss=False) is False


def test_incompatible_modes_raise(monkeypatch):
    from ai_rtc_agent_tpu.models import registry
    from ai_rtc_agent_tpu.stream.engine import StreamEngine, make_step_fn

    bundle = registry.load_model_bundle("tiny-test")

    # sequential (non-stream-batch) mode
    cfg = registry.default_stream_config(
        "tiny-test", unet_cache_interval=2, use_denoising_batch=False
    )
    with pytest.raises(ValueError, match="denoising-batch"):
        make_step_fn(bundle.stream_models, cfg, unet_variant="cached")

    # controlnet + cache rejected at config time
    monkeypatch.setenv("UNET_CACHE", "2")
    with pytest.raises(ValueError, match="ControlNet"):
        registry.default_stream_config("tiny-test", use_controlnet=True)


@pytest.mark.slow
def test_sd_turbo_cached_step_flop_ratio():
    """Compiler-pinned savings at the flagship geometry: the cached step
    must stay well under the full step (measured 0.542x; band to 0.70)."""
    from ai_rtc_agent_tpu.models import registry
    from ai_rtc_agent_tpu.stream.engine import StreamEngine, make_step_fn

    bundle = registry.load_model_bundle("stabilityai/sd-turbo")
    cfg = registry.default_stream_config(
        "stabilityai/sd-turbo", unet_cache_interval=3
    )
    eng = StreamEngine(
        bundle.stream_models, bundle.params, cfg, bundle.encode_prompt,
        jit_compile=False,
    )
    eng.prepare("flops probe", guidance_scale=1.0)
    frame = np.zeros((cfg.height, cfg.width, 3), np.uint8)

    def flops(variant):
        step = make_step_fn(eng.models, eng.cfg, unet_variant=variant)
        c = jax.jit(step).lower(eng.params, eng.state, frame).cost_analysis()
        if isinstance(c, (list, tuple)):
            c = c[0]
        return float(c.get("flops", 0.0))

    ratio = flops("cached") / flops("capture")
    assert ratio < 0.70, f"cached/full FLOP ratio regressed: {ratio:.3f}"


def test_control_plane_updates_force_recapture(monkeypatch):
    """Prompt/t-index updates must make the next step a full capture —
    deep cross-attention features from the OLD conditioning would
    otherwise serve for up to N-1 frames."""
    from ai_rtc_agent_tpu.models import registry
    from ai_rtc_agent_tpu.stream.engine import StreamEngine

    bundle = registry.load_model_bundle("tiny-test")
    cfg = registry.default_stream_config("tiny-test", unet_cache_interval=4)
    eng = StreamEngine(
        bundle.stream_models, bundle.params, cfg, bundle.encode_prompt
    )
    eng.prepare("first prompt", guidance_scale=1.0, seed=1)
    rng = np.random.default_rng(0)
    for _ in range(2):
        eng(rng.integers(0, 256, (cfg.height, cfg.width, 3), np.uint8))
    assert eng._tick == 2  # mid-cadence
    eng.update_prompt("second prompt")
    assert eng._tick == 0  # next step recaptures
    eng(rng.integers(0, 256, (cfg.height, cfg.width, 3), np.uint8))
    assert eng._tick == 1
    eng.update_t_index_list(list(cfg.t_index_list))
    assert eng._tick == 0
    eng.reset_cache_cadence()
    assert eng._tick == 0


@pytest.mark.slow  # fbs x deepcache composition compile (~10s); the
# cadence itself stays tier-1 via test_engine_cadence_and_flops and the
# fbs step shape via test_stream's frame-batching tests (ISSUE 11 shave)
def test_cadence_with_frame_batching():
    """fbs>1: the cache rides the batched step (slots = n_stages*fbs) —
    shapes line up and the cadence alternates per step (not per frame)."""
    from ai_rtc_agent_tpu.models import registry
    from ai_rtc_agent_tpu.stream.engine import StreamEngine

    bundle = registry.load_model_bundle("tiny-test")
    cfg = registry.default_stream_config(
        "tiny-test", unet_cache_interval=2, frame_buffer_size=2
    )
    eng = StreamEngine(
        bundle.stream_models, bundle.params, cfg, bundle.encode_prompt
    )
    eng.prepare("fbs deepcache", guidance_scale=1.0, seed=1)
    assert eng.state["unet_cache"].shape[0] == cfg.batch_size
    rng = np.random.default_rng(0)
    for _ in range(4):
        out = eng(rng.integers(0, 256, (2, cfg.height, cfg.width, 3), np.uint8))
        assert out.shape == (2, cfg.height, cfg.width, 3)
        assert np.isfinite(out.astype(np.float64)).all()
    assert eng._tick == 4


@pytest.mark.slow  # AOT pair build x fresh-adoption composition (~8s;
# ISSUE 15 budget pairing): test_engine_cadence_and_flops keeps the
# cadence pin in tier-1, the scheduler's pair-key discipline rides
# test_refuses_incompatible_configs, and
# test_build_engines_sched_buckets_flag keeps an AOT build+adopt
# roundtrip in tier-1
def test_aot_pair_build_and_fresh_adoption(tmp_path):
    """The TRT-engine-cache analog covers DeepCache: build_engines-style
    pair build (capture + cached executables, distinct keys), then a fresh
    engine adopts BOTH without compiling and serves the cadence."""
    from ai_rtc_agent_tpu.models import registry
    from ai_rtc_agent_tpu.stream.engine import StreamEngine

    bundle = registry.load_model_bundle("tiny-test")
    cfg = registry.default_stream_config("tiny-test", unet_cache_interval=2)

    eng = StreamEngine(
        bundle.stream_models, bundle.params, cfg, bundle.encode_prompt,
        jit_compile=False,
    )
    eng.prepare("aot deepcache", guidance_scale=1.0, seed=1)
    assert eng.use_aot_cache("tiny-test", cache_dir=str(tmp_path)) is True

    eng2 = StreamEngine(
        bundle.stream_models, bundle.params, cfg, bundle.encode_prompt,
        jit_compile=False,
    )
    eng2.prepare("aot deepcache", guidance_scale=1.0, seed=1)
    assert eng2.use_aot_cache(
        "tiny-test", cache_dir=str(tmp_path), build_on_miss=False
    ) is True
    rng = np.random.default_rng(0)
    for _ in range(3):
        out = eng2(rng.integers(0, 256, (cfg.height, cfg.width, 3), np.uint8))
        assert np.isfinite(out.astype(np.float64)).all()
    assert eng2._tick == 3


@pytest.mark.slow  # two sharded-mesh x deepcache composition compiles
# (~28s); each side keeps a lighter tier-1 sibling — cadence via
# test_engine_cadence_and_flops, tp/sp serving via test_parallel /
# test_stream (ISSUE 11 shave)
@pytest.mark.parametrize("kind,mesh_kw", [("tp", {"tp": 2}), ("sp", {"sp": 2})])
def test_cache_composes_with_sharded_serving(kind, mesh_kw):
    """UNET_CACHE under --tp/--sp: both cadence variants compile and run
    under the sharded mesh (the capture/cached pair are ordinary jitted
    steps; pjit shards them like the full graph) — pinned so a future
    engine change cannot silently break the combination."""
    from ai_rtc_agent_tpu.models import registry
    from ai_rtc_agent_tpu.parallel import mesh as M
    from ai_rtc_agent_tpu.stream.engine import StreamEngine

    bundle = registry.load_model_bundle(
        "tiny-test", attn_impl="ring" if kind == "sp" else None
    )
    cfg = registry.default_stream_config("tiny-test", unet_cache_interval=3)
    eng = StreamEngine(
        bundle.stream_models, bundle.params, cfg, bundle.encode_prompt,
        mesh=M.make_mesh(**mesh_kw),
    )
    eng.prepare("cache x mesh", seed=1)
    rng = np.random.default_rng(0)
    for _ in range(4):  # spans a capture tick and cached ticks
        out = eng(rng.integers(0, 256, (64, 64, 3), np.uint8))
        assert np.isfinite(out.astype(np.float64)).all()
    assert eng._tick == 4
