"""The rectified-flow transformer family (SD3's MMDiT, ``models/mmdit.py``)
at a tiny size: the model against the plain reference
(``benchmark/reference/sd3_stream.py``) on seeded weights, the flow ladder
and coefficients against hand values, ``joint_attention`` against a plain
softmax at lengths no block divides, what the registry refuses for the
family, and pins that hold the UNet families' kernel blocks and state rows
where they were."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ai_rtc_agent_tpu.models import mmdit as M
from ai_rtc_agent_tpu.models import registry
from ai_rtc_agent_tpu.ops import lcm as L
from ai_rtc_agent_tpu.ops import schedule as S
from ai_rtc_agent_tpu.ops.pallas import attention as PA
from ai_rtc_agent_tpu.ops.pallas import count_attention_paths
from ai_rtc_agent_tpu.stream.scheduler import BatchScheduler

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_TINY_FILE = os.path.join(
    ROOT, "benchmark", "tests", "data", "configs", "tinysd3_64.json"
)


@pytest.fixture(scope="module")
def tiny():
    """(the tiny file, the program's config, seeded float32 weights of the
    reference's layout)."""
    from benchmark.reference import sd3_stream as R
    from benchmark.weights import make_weights

    with open(_TINY_FILE) as f:
        cfg = json.load(f)
    w = make_weights(R.weight_shapes(cfg), 5, jnp.float32, cfg["weights"]["rules"])
    return cfg, M.MMDiTConfig.tiny(), w


def _normal(seed, shape):
    return jax.random.normal(jax.random.PRNGKey(seed), shape, jnp.float32)


# -- the model against the reference ------------------------------------------

@pytest.mark.parametrize("last", [False, True])
def test_one_joint_block_matches_the_reference(tiny, last):
    """A joint block (AdaLN-zero on both streams, joint attention, gated
    residuals, both feed-forwards) and the last, pre-only one (its text
    stream AdaLN-continuous, keys and values alone)."""
    from benchmark.reference import sd3_stream as R

    _, mc, w = tiny
    p = w["mmdit"]["blocks"][-1 if last else 0]
    x, c = _normal(1, (2, 16, mc.width)), _normal(2, (2, 9, mc.width))
    temb = _normal(3, (2, mc.width))
    with jax.default_matmul_precision("highest"):
        got_x, got_c = M._joint_block(p, x, c, temb, mc.heads, "xla", last=last)
        want_x, want_c = R._joint_block(p, x, c, temb, mc.heads, last=last)
    np.testing.assert_allclose(got_x, want_x, rtol=1e-5, atol=1e-5)
    if last:
        assert want_c is None
    else:
        np.testing.assert_allclose(got_c, want_c, rtol=1e-5, atol=1e-5)


def test_position_embedding_is_the_centre_crop_of_the_grid(tiny):
    cfg, mc, _ = tiny
    from benchmark.reference import sd3_stream as R

    m = mc.pos_embed_max_size
    full = M.pos_embed(mc, m, m).reshape(m, m, mc.width)
    for gh, gw in ((8, 8), (6, 4), (m, m)):
        top, left = (m - gh) // 2, (m - gw) // 2
        got = M.pos_embed(mc, gh, gw)
        np.testing.assert_array_equal(
            got, full[top:top + gh, left:left + gw].reshape(gh * gw, -1)
        )
        np.testing.assert_allclose(
            got, R.position_embedding(cfg["transformer"], gh, gw), atol=1e-6
        )
    # token 0 at (row 2, column 2) of the 12-grid, in units of 8 / 12 cells:
    # the first half of the width encodes the column, the second the row
    coord = 2 * 8 / 12
    half = mc.width // 2
    want = np.sin(coord * 1e-4 ** (np.arange(half // 2) / (half / 2)))
    np.testing.assert_allclose(M.pos_embed(mc, 8, 8)[0, : half // 2], want, atol=1e-6)
    with pytest.raises(ValueError, match="pos_embed_max_size"):
        M.pos_embed(mc, m + 2, m)


def test_the_tiny_mmdit_matches_the_reference(tiny):
    """The whole pass: patch embedding, the time and pooled-text embeddings,
    both blocks, AdaLN-continuous out, unpatchify, four rows at their own
    levels."""
    from benchmark.reference import sd3_stream as R

    cfg, mc, w = tiny
    x = _normal(1, (4, 16, 16, 16))
    t = jnp.asarray([838.48, 727.58, 548.05, 216.59], jnp.float32)
    ctx = _normal(2, (4, 32, mc.joint_attention_dim))
    pooled = _normal(3, (4, mc.pooled_projection_dim))
    with jax.default_matmul_precision("highest"):
        got = M.apply_mmdit(w["mmdit"], x, t, ctx, mc, added_cond={"text_embeds": pooled})
        want = R.transformer(w["mmdit"], x, t, ctx, pooled, cfg["transformer"])
    assert got.shape == (4, 16, 16, 16)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=2e-5)


def test_the_program_tree_is_the_reference_layout():
    from benchmark.reference import sd3_stream as R
    from benchmark.weights import same_layout

    with open(_TINY_FILE) as f:
        cfg = json.load(f)
    shapes = jax.eval_shape(lambda: registry.load_model_bundle("tinysd3-test").params)
    assert same_layout(R.weight_shapes(cfg), shapes) is None


# -- the flow ladder and coefficients ------------------------------------------

# FlowMatchEulerDiscreteScheduler(shift=3.0).set_timesteps(50) at indices
# 18, 26, 35, 45, by hand: u = 1 - i / 49 * (1 - s(1/1000)), sigma = s(u),
# s(u) = 3u / (1 + 2u)
_SIGMAS = (0.838480194017785, 0.7275816499905606, 0.5480456026058632, 0.21659347553324962)


def test_the_flow_ladder_against_hand_values():
    assert registry.default_stream_config("jasperai/flash-sd3").flow_shift == 3.0
    ladder = S.flow_sigmas(50, 3.0)
    assert ladder.shape == (50,) and ladder[0] == 1.0
    np.testing.assert_allclose(ladder[-1], 3 * 0.002994012 / (1 + 2 * 0.002994012), rtol=1e-6)
    assert (np.diff(ladder) < 0).all()
    np.testing.assert_allclose(
        S.flow_sub_sigmas((18, 26, 35, 45), 50, 3.0), _SIGMAS, rtol=1e-12
    )
    with pytest.raises(ValueError, match="strictly increasing"):
        S.flow_sub_sigmas((26, 18), 50, 3.0)


def test_the_flow_coefficients_against_hand_values():
    s = np.asarray(_SIGMAS)
    c = L.make_flow_coeffs(s)
    np.testing.assert_allclose(c.timesteps, 1000 * s, rtol=1e-6)
    assert c.timesteps.dtype == np.float32
    np.testing.assert_allclose(c.alpha, 1 - s, rtol=1e-6)
    np.testing.assert_allclose(c.sigma, s, rtol=1e-6)
    np.testing.assert_array_equal(c.c_skip, 0.0)
    np.testing.assert_array_equal(c.c_out, 1.0)
    # each row moves to the next stage's level; the last leaves clean
    np.testing.assert_allclose(c.next_sigma, [s[1], s[2], s[3], 0.0], rtol=1e-6)
    np.testing.assert_allclose(c.next_alpha, [1 - s[1], 1 - s[2], 1 - s[3], 1.0], rtol=1e-6)
    # two frames a step: each level twice, the last two rows exit
    c2 = L.make_flow_coeffs(np.repeat(s, 2), frame_buffer_size=2)
    np.testing.assert_allclose(c2.next_sigma, [s[1], s[1], s[2], s[2], s[3], s[3], 0, 0], rtol=1e-6)
    with pytest.raises(ValueError, match="divisible"):
        L.make_flow_coeffs(s[:3], frame_buffer_size=2)


def test_a_true_velocity_gives_the_clean_latent_back():
    """x_t = (1 - s) x0 + s n and v = n - x0: ``pred_x0`` under ``flow``
    returns x0 at every level, and so does the stream batch's consistency
    step (``lcm_denoise``: c_skip 0, c_out 1), which the flow rows take."""
    s = np.asarray(_SIGMAS)
    c = L.make_flow_coeffs(s).as_jnp()
    assert c.timesteps.dtype == jnp.float32
    x0, n = _normal(4, (4, 8, 8, 16)), _normal(5, (4, 8, 8, 16))
    col = jnp.asarray(s, jnp.float32).reshape(-1, 1, 1, 1)
    x_t = (1 - col) * x0 + col * n
    np.testing.assert_allclose(L.pred_x0(x_t, n - x0, c, "flow"), x0, atol=1e-5)
    np.testing.assert_array_equal(
        L.lcm_denoise(x_t, n - x0, c, "flow"), L.pred_x0(x_t, n - x0, c, "flow")
    )
    # the UNet families keep integer timesteps
    sched = S.make_schedule()
    lcm = L.make_step_coeffs(sched, S.batched_sub_timesteps([18, 26, 35, 45], 50, 1), 1)
    assert lcm.as_jnp().timesteps.dtype == jnp.int32


# -- joint_attention -------------------------------------------------------------

@pytest.mark.parametrize(
    "lq,lk,heads,head_dim,dtype,atol",
    [
        (77, 77, 2, 16, jnp.float32, 2e-5),     # 64 + 13 tokens, no block divides
        (1178, 1178, 2, 64, jnp.bfloat16, 2e-2),  # SD3's joint length: two q blocks of 592
        (1024, 1178, 2, 64, jnp.bfloat16, 2e-2),  # SD3's last block: image queries alone
    ],
)
def test_joint_attention_matches_a_plain_softmax(lq, lk, heads, head_dim, dtype, atol):
    keys = jax.random.split(jax.random.PRNGKey(lq + lk), 3)
    q = jax.random.normal(keys[0], (2, lq, heads, head_dim), dtype)
    k = jax.random.normal(keys[1], (2, lk, heads, head_dim), dtype)
    v = jax.random.normal(keys[2], (2, lk, heads, head_dim), dtype)
    with count_attention_paths() as paths:
        got = PA.joint_attention(q, k, v, interpret=True)
    # 77 keys are one block; SD3's 1178 are met in blocks and a ragged rest
    assert dict(paths) == {"joint" if lk == 77 else "joint_kloop": 1}
    assert got.shape == q.shape and got.dtype == dtype
    want = PA._xla_attention(*(a.astype(jnp.float32) for a in (q, k, v)))
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want), atol=atol)


@pytest.mark.parametrize("vmap_k", [0, 2])
@pytest.mark.parametrize(
    "lq,lk,heads,head_dim,block_k,dtype,atol,path",
    [
        # SD3's two calls with the blocks ``_joint_blocks`` gives them
        (1178, 1178, 2, 64, None, jnp.bfloat16, 2e-2, "joint_kloop"),
        (1024, 1178, 2, 64, None, jnp.bfloat16, 2e-2, "joint_kloop"),
        (200, 1024, 2, 64, 256, jnp.bfloat16, 2e-2, "joint_kloop"),  # the block divides K
        (64, 96, 2, 64, None, jnp.bfloat16, 2e-2, "joint"),  # K shorter than a block
        (40, 513, 2, 64, None, jnp.bfloat16, 2e-2, "joint_kloop"),  # a ragged block of one key
        (64, 600, 4, 32, None, jnp.bfloat16, 2e-2, "joint_kloop"),  # four heads a lane tile
        (77, 77, 2, 16, 32, jnp.float32, 2e-5, "joint_kloop"),  # float32: 2 x 32 + 13 keys
    ],
)
def test_joint_attention_k_loop_matches_a_plain_softmax(
    lq, lk, heads, head_dim, block_k, dtype, atol, path, vmap_k
):
    """The K loop against a plain softmax: every key weighed once, whether
    the block divides K, K is shorter than a block, or the ragged last block
    holds one key; alone and under ``vmap`` as the bucket step runs it."""
    lead = (vmap_k,) if vmap_k else ()
    keys = jax.random.split(jax.random.PRNGKey(lq * lk + vmap_k), 3)
    q = jax.random.normal(keys[0], lead + (1, lq, heads, head_dim), dtype)
    k = jax.random.normal(keys[1], lead + (1, lk, heads, head_dim), dtype)
    v = jax.random.normal(keys[2], lead + (1, lk, heads, head_dim), dtype)
    kernel = lambda q, k, v: PA.joint_attention(q, k, v, block_k=block_k, interpret=True)  # noqa: E731
    ref = PA._xla_attention
    if vmap_k:
        kernel, ref = jax.vmap(kernel), jax.vmap(ref)
    with count_attention_paths() as paths:
        got = kernel(q, k, v)
    assert dict(paths) == {path: 1}
    assert got.shape == q.shape and got.dtype == dtype
    want = ref(*(a.astype(jnp.float32) for a in (q, k, v)))
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want), atol=atol)


def test_joint_attention_query_blocks():
    """``joint_attention`` meets SD3's 1178 keys in blocks of
    ``_JOINT_BLOCK_K``, all its queries in one block."""
    assert PA._joint_blocks(1178, 1178, 2) == (1178, 512)
    assert PA._joint_blocks(1024, 1178, 2) == (1024, 512)
    assert PA._joint_blocks(96, 96, 2) == (96, 96)
    assert PA._joint_blocks(77, 77, 4) == (77, 77)


# every (lq, lk, heads, head_dim) the four UNet configurations reach (the
# tiers x self / 77-key cross of SD2.1, SD1.5 and SDXL; ControlNet's are
# SD1.5's), bf16 -> what ``_choose_blocks`` gives: it is not joint
# attention's, and a change to it moves every cell
_SERVED_BLOCKS = {
    (4096, 4096, 5, 64): (0, 512, 1024), (4096, 77, 5, 64): (0, 4096, 77),
    (1024, 1024, 10, 64): (2, 1024, 1024), (1024, 77, 10, 64): (2, 1024, 77),
    (256, 256, 20, 64): (2, 256, 256), (256, 77, 20, 64): (2, 256, 77),
    (64, 64, 20, 64): (2, 64, 64), (64, 77, 20, 64): (2, 64, 77),
    (4096, 4096, 8, 40): (0, 512, 1024), (4096, 77, 8, 40): (0, 4096, 77),
    (1024, 1024, 8, 80): (8, 256, 1024), (1024, 77, 8, 80): (0, 1024, 77),
    (256, 256, 8, 160): (4, 256, 256), (256, 77, 8, 160): (4, 256, 77),
    (64, 64, 8, 160): (4, 64, 64), (64, 77, 8, 160): (4, 64, 77),
}


@pytest.mark.parametrize("shape", sorted(_SERVED_BLOCKS))
def test_choose_blocks_is_pinned_for_the_unet_configurations(shape):
    assert PA._choose_blocks(*shape, 2) == _SERVED_BLOCKS[shape]


# -- the registry ----------------------------------------------------------------

def test_the_model_id_resolves_the_flow_family():
    assert registry.family_of("jasperai/flash-sd3") == "sd3"
    assert registry.family_of("stabilityai/stable-diffusion-3-medium-diffusers") == "sd3"
    assert registry.family_of("tinysd3-test") == "tinysd3"
    cfg = registry.default_stream_config("jasperai/flash-sd3", dtype="bfloat16")
    assert (cfg.scheduler, cfg.prediction_type, cfg.cfg_type) == ("flow", "flow", "none")
    assert cfg.t_index_list == (18, 26, 35, 45) and cfg.num_inference_steps == 50
    assert (cfg.latent_channels, cfg.height) == (16, 512)
    assert not cfg.use_fused_epilogue


def test_the_unet_families_resolve_as_before():
    for mid, fam in (("stabilityai/sd-turbo", "sd21"), ("Lykon/dreamshaper-8", "sd15"),
                     ("stabilityai/sdxl-turbo", "sdxl"), ("tiny-test", "tiny")):
        assert registry.family_of(mid) == fam
        cfg = registry.default_stream_config(mid)
        assert cfg.latent_channels == 4 and cfg.scheduler in ("lcm", "turbo")


@pytest.mark.parametrize(
    "model_id,kw,env,conflict",
    [
        ("jasperai/flash-sd3", {}, "3", "DeepCache"),
        ("jasperai/flash-sd3", {"frame_buffer_size": 2}, "", "frame_buffer_size 2"),
        ("jasperai/flash-sd3+lllyasviel/control_v11p_sd15_canny", {}, "", "side network"),
    ],
)
def test_the_flow_family_refuses_what_does_not_compose(monkeypatch, model_id, kw, env, conflict):
    monkeypatch.setenv("UNET_CACHE", env)
    with pytest.raises(ValueError, match=conflict) as e:
        registry.default_stream_config(model_id, **kw)
    assert "rectified-flow" in str(e.value)


def test_the_flow_bundle_refuses_a_side_network():
    with pytest.raises(ValueError, match="side network"):
        registry.load_model_bundle("tinysd3-test", controlnet="tiny-cnet")


def test_the_flow_prompt_is_the_joint_text_and_the_pooled_pair():
    b = registry.load_model_bundle("tinysd3-test")
    cond, uncond, extras = b.encode_prompt("a neon cat")
    mc = b.unet_cfg
    # two towers' [-2] states zero-padded to the T5 width, then T5's zeros
    assert cond.shape == uncond.shape == (1, 32, mc.joint_attention_dim)
    assert not np.asarray(cond[:, 16:]).any() and not np.asarray(cond[:, :, 32:]).any()
    assert np.asarray(cond[:, :16, :32]).any()
    assert extras["pooled"].shape == (1, mc.pooled_projection_dim)


# -- the scheduler -----------------------------------------------------------------

def test_the_scheduler_serves_the_flow_family_and_names_what_it_compiled():
    b = registry.load_model_bundle("tinysd3-test", attn_impl="pallas")
    cfg = registry.default_stream_config("tinysd3-test", use_fused_epilogue=True)
    s = BatchScheduler(
        b.stream_models, b.params, cfg, b.encode_prompt, max_sessions=1,
        prewarm=True, dp=1,
    )
    try:
        snap = s.snapshot()
        assert snap["batchsched_denoiser"] == {
            "kind": "mmdit", "latent_channels": 16, "joint_tokens": 8 * 8 + 2 * 16,
        }
        # asked for, the fused epilogue computes epsilon alone: XLA it is
        assert set(snap["batchsched_epilogue"].values()) == {"xla"}
        assert set(map(dict.__repr__, snap["batchsched_attention_paths"].values())) == {
            repr({"joint": 2})
        }
        sess = s.claim("a", prompt="neon", seed=3)
        frame = np.random.default_rng(0).integers(0, 256, (64, 64, 3), np.uint8)
        outs = [np.asarray(sess.fetch(sess.submit(frame))) for _ in range(5)]
        assert outs[-1].shape == (64, 64, 3) and outs[-1].dtype == np.uint8
        assert s.states["noise"].shape[-1] == 16
        sess.release()
    finally:
        s.close()


def _leaves(extra=(), stages=4, latent=16, ring=3):
    base = {
        "cond": ((1, 1, 16, 32), "float32"), "uncond": ((1, 1, 16, 32), "float32"),
        "delta": ((1,), "float32"), "guidance": ((1,), "float32"),
        "noise": ((1, stages, latent, latent, 4), "float32"),
        "stock": ((1, stages, latent, latent, 4), "float32"),
        "x_buf": ((1, ring, latent, latent, 4), "float32"),
    }
    for name in ("alpha", "c_out", "c_skip", "next_alpha", "next_sigma", "sigma"):
        base[f"coeffs/{name}"] = ((1, stages), "float32")
    base["coeffs/timesteps"] = ((1, stages), "int32")
    base.update(extra)
    return base


# the template state row of every UNet family's tiny model, leaf by leaf: a
# generalisation that adds a leaf (host work at every launch) fails here
_STATE_ROWS = {
    "tiny-test": _leaves(),
    "tinyxl-test": _leaves({
        "added_text": ((1, 1, 16), "float32"), "added_time_ids": ((1, 1, 6), "float32"),
    }),
    "tiny-turbo-test": _leaves(stages=1, latent=64, ring=0),
    "tiny-test+tiny-cnet": _leaves({
        "cnet_cond": ((1, 3, 64, 64, 3), "float32"), "cnet_scale": ((1,), "float32"),
    }),
}


@pytest.mark.parametrize("model_id", sorted(_STATE_ROWS))
def test_the_unet_families_state_rows_are_pinned(model_id):
    b = registry.load_model_bundle(model_id)
    cfg = registry.default_stream_config(model_id)
    s = BatchScheduler(
        b.stream_models, b.params, cfg, b.encode_prompt, max_sessions=1,
        prewarm=False, dp=1,
    )
    try:
        got = {
            "/".join(str(k.key) for k in path): (tuple(x.shape), str(x.dtype))
            for path, x in jax.tree_util.tree_flatten_with_path(s.states)[0]
        }
        assert s.denoiser == {"kind": "unet", "latent_channels": 4}
    finally:
        s.close()
    assert got == _STATE_ROWS[model_id]


def test_the_flow_familys_state_row_is_pinned():
    """The flow row: the joint text, the pooled pair, float timesteps and a
    16-channel ring; no SDXL ``time_ids`` (the MMDiT does not read them)."""
    b = registry.load_model_bundle("tinysd3-test")
    cfg = registry.default_stream_config("tinysd3-test")
    s = BatchScheduler(
        b.stream_models, b.params, cfg, b.encode_prompt, max_sessions=1,
        prewarm=False, dp=1,
    )
    try:
        got = {
            "/".join(str(k.key) for k in path): (tuple(x.shape), str(x.dtype))
            for path, x in jax.tree_util.tree_flatten_with_path(s.states)[0]
        }
    finally:
        s.close()
    want = _leaves({"added_text": ((1, 1, 32), "float32")}, latent=16)
    for name in ("noise", "stock", "x_buf"):
        want[name] = want[name][0][:-1] + (16,), "float32"
    want["cond"] = want["uncond"] = ((1, 1, 32, 64), "float32")
    want["coeffs/timesteps"] = ((1, 4), "float32")
    assert got == want


@pytest.mark.parametrize("compiled", [{}, {"fused_stream_epilogue": 1}])
def test_the_epilogue_named_is_the_one_the_bucket_compiled(monkeypatch, compiled):
    """``batchsched_epilogue`` reads the compiled step's own kernel counts,
    not the configuration: the fused kernel only where the HLO calls it
    (interpret-mode kernels on the CPU compose XLA ops)."""
    from ai_rtc_agent_tpu.stream import scheduler as SC

    seen = []
    real = SC.mosaic_kernel_counts

    def counts(text):
        seen.append(real(text))
        return dict(compiled)

    monkeypatch.setattr(SC, "mosaic_kernel_counts", counts)
    b = registry.load_model_bundle("tiny-test")
    cfg = registry.default_stream_config(
        "tiny-test", use_fused_epilogue=True, height=32, width=32
    )
    s = BatchScheduler(
        b.stream_models, b.params, cfg, b.encode_prompt, max_sessions=1,
        prewarm=True, dp=1,
    )
    try:
        snap = s.snapshot()
    finally:
        s.close()
    assert seen == [{}]  # the CPU's interpret-mode step: no Mosaic call
    want = "fused_stream_epilogue" if compiled else "xla"
    assert set(snap["batchsched_epilogue"].values()) == {want}
