"""FLUX's double- and single-stream transformer (``models/flux.py``) at a
tiny size: the model against the plain reference
(``benchmark/reference/flux_stream.py``) on seeded weights, block by block,
whole, and as the scheduler steps it; the rotary positions; ``linear1``'s
split; ``rope_attention`` against a rotation and a plain softmax; what the
registry resolves and refuses for the family; and pins that hold the
other families' flow ladder, state rows and attention paths where they
were."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ai_rtc_agent_tpu.models import flux as F
from ai_rtc_agent_tpu.models import registry
from ai_rtc_agent_tpu.ops import schedule as S
from ai_rtc_agent_tpu.ops.pallas import attention as PA
from ai_rtc_agent_tpu.ops.pallas import count_attention_paths
from ai_rtc_agent_tpu.stream.scheduler import BatchScheduler

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_TINY_FILE = os.path.join(ROOT, "benchmark", "tests", "data", "configs", "tinyflux_64.json")
_CONFIGS = os.path.join(ROOT, "benchmark", "configs")

# float32 against the float32 reference: the two differ in the order of
# their sums (the program's fused q/k/v split, its rotation on pairs, XLA's
# dot against the reference's ``highest`` matmul), a few ulps of values of
# order one after three blocks.  A bfloat16 pass of the same blocks is off
# by 1e-2 and more (``test_the_tolerance_fails_a_bfloat16_pass``)
RTOL, ATOL = 1e-5, 2e-5


@pytest.fixture(scope="module")
def tiny():
    """(the tiny file, the program's config, seeded float32 weights of the
    reference's layout)."""
    from benchmark.reference import flux_stream as R
    from benchmark.weights import make_weights

    with open(_TINY_FILE) as f:
        cfg = json.load(f)
    w = make_weights(R.weight_shapes(cfg), 5, jnp.float32, cfg["weights"]["rules"])
    return cfg, F.FluxConfig.tiny(), w


def _normal(seed, shape):
    return jax.random.normal(jax.random.PRNGKey(seed), shape, jnp.float32)


def _tables(fc, gh=4, gw=4, lt=5):
    """Program (cos, sin) and the reference's rotation matrices of one
    joint sequence."""
    from benchmark.reference import flux_stream as R

    cos, sin = F.rope_tables(fc, gh, gw, lt)
    pe = jnp.asarray(R.rope(R.positions(lt, gh, gw), list(fc.axes_dims_rope), fc.theta),
                     jnp.float32)
    return (cos, sin), pe


def _inputs(fc, lt=5, li=16, seed=1):
    img = _normal(seed, (2, li, fc.width))
    txt = _normal(seed + 1, (2, lt, fc.width))
    vec = _normal(seed + 2, (2, fc.width))
    return img, txt, vec


# -- the model against the reference ------------------------------------------------

def test_one_double_block_matches_the_reference(tiny):
    """Both streams' AdaLN-zero modulations, fused q/k/v, q/k RMS norms, the
    rotated joint attention (text first), gated residuals, both MLPs."""
    from benchmark.reference import flux_stream as R

    _, fc, w = tiny
    rope, pe = _tables(fc)
    img, txt, vec = _inputs(fc)
    p = w["flux"]["double_blocks"][0]
    with jax.default_matmul_precision("highest"):
        got_i, got_t = F._double_block(p, img, txt, vec, rope, fc.heads, "xla")
        want_i, want_t = R.double_block(p, img, txt, vec, pe, fc.heads)
    np.testing.assert_allclose(got_i, want_i, rtol=RTOL, atol=ATOL)
    np.testing.assert_allclose(got_t, want_t, rtol=RTOL, atol=ATOL)


def test_one_single_block_matches_the_reference(tiny):
    """One modulation, ``linear1`` into q/k/v and the MLP's input, attention
    and the MLP in parallel, ``linear2`` over both, one gated residual."""
    from benchmark.reference import flux_stream as R

    _, fc, w = tiny
    rope, pe = _tables(fc)
    img, txt, vec = _inputs(fc)
    h = jnp.concatenate([txt, img], axis=1)
    p = w["flux"]["single_blocks"][1]
    with jax.default_matmul_precision("highest"):
        got = F._single_block(p, h, vec, rope, fc.heads, "xla")
        want = R.single_block(p, h, vec, pe, fc.heads)
    np.testing.assert_allclose(got, want, rtol=RTOL, atol=ATOL)


def _whole(tiny, dtype=jnp.float32, attn_impl="xla"):
    from benchmark.reference import flux_stream as R

    cfg, fc, w = tiny
    x = _normal(1, (2, 16, 16, 16))
    t = jnp.asarray([490.31, 184.49], jnp.float32)
    ctx = _normal(2, (2, fc.text_tokens, fc.joint_attention_dim))
    pooled = _normal(3, (2, fc.pooled_projection_dim))
    cast = lambda a: jax.tree.map(lambda b: b.astype(dtype), a)  # noqa: E731
    with jax.default_matmul_precision("highest"):
        got = F.apply_flux(
            cast(w["flux"]), cast(x), t, cast(ctx), fc,
            added_cond={"text_embeds": cast(pooled)}, attn_impl=attn_impl,
        )
        want = R.transformer(w["flux"], x, t, ctx, pooled, cfg["transformer"])
    return np.asarray(got, np.float32), np.asarray(want)


@pytest.mark.parametrize("attn_impl", ["xla", "pallas"])
def test_the_tiny_flux_matches_the_reference(tiny, attn_impl):
    """The whole pass: packing, the embedders, the double block, both single
    blocks on the text-first joint sequence, the last layer, unpacking; two
    rows at their own levels; attention by XLA or by ``rope_attention``."""
    got, want = _whole(tiny, attn_impl=attn_impl)
    assert got.shape == (2, 16, 16, 16)
    np.testing.assert_allclose(got, want, rtol=RTOL, atol=ATOL)


def test_the_tolerance_fails_a_bfloat16_pass(tiny):
    """The same pass with weights and activations in bfloat16 (the precision
    below the reference's) is far outside the tolerance the float32 pass
    keeps."""
    got, want = _whole(tiny, dtype=jnp.bfloat16)
    err = np.abs(got - want) - RTOL * np.abs(want)
    assert err.max() > 100 * ATOL


def test_the_program_tree_is_the_reference_layout():
    from benchmark.reference import flux_stream as R
    from benchmark.weights import same_layout

    with open(_TINY_FILE) as f:
        cfg = json.load(f)
    shapes = jax.eval_shape(lambda: registry.load_model_bundle("tinyflux-test").params)
    assert same_layout(R.weight_shapes(cfg), shapes) is None


def test_pack_and_unpack_are_bfls_layout():
    """``c (h 2) (w 2) -> (h w) (c 2 2)`` on an NHWC latent, and back."""
    x = _normal(4, (1, 4, 6, 3))
    z = F.pack(x)
    assert z.shape == (1, 6, 12)
    # token (row 1, column 2), feature (channel 2, row 1 in the cell, column 0)
    np.testing.assert_array_equal(z[0, 1 * 3 + 2, 2 * 4 + 1 * 2 + 0], x[0, 3, 4, 2])
    np.testing.assert_array_equal(F.unpack(z, 4, 6), x)


# -- the rotary positions ------------------------------------------------------------

def test_text_tokens_and_axis_zero_are_not_rotated():
    """Text tokens sit at (0, 0, 0) and every token's first axis at 0: their
    pairs keep cosine 1 and sine 0, and the rotation leaves those lanes as
    they are."""
    fc = F.FluxConfig.schnell()
    cos, sin = F.rope_tables(fc, 32, 32, 256)
    assert cos.shape == sin.shape == (1280, 64)
    np.testing.assert_array_equal(cos[:256], 1.0)
    np.testing.assert_array_equal(sin[:256], 0.0)
    np.testing.assert_array_equal(cos[:, :8], 1.0)  # axis 0: 16 lanes, 8 pairs
    np.testing.assert_array_equal(sin[:, :8], 0.0)
    x = _normal(5, (1, 1280, 2, 128))
    y = F.apply_rope(x, cos, sin)
    np.testing.assert_array_equal(y[:, :256], x[:, :256])
    np.testing.assert_array_equal(y[..., :16], x[..., :16])
    assert not np.allclose(y[:, 300:, :, 16:], x[:, 300:, :, 16:])


def test_rotation_is_an_explicit_2x2_on_interleaved_pairs():
    """Lanes (2j, 2j+1) of a head turn by the pair's angle as
    [[cos, -sin], [sin, cos]] @ (a, b); no other lane moves into them."""
    rng = np.random.default_rng(0)
    x = rng.standard_normal((1, 3, 1, 8)).astype(np.float32)
    ang = rng.uniform(-3, 3, (3, 4)).astype(np.float32)
    y = np.asarray(F.apply_rope(jnp.asarray(x), np.cos(ang), np.sin(ang)))
    for t in range(3):
        for j in range(4):
            c, s = np.cos(ang[t, j]), np.sin(ang[t, j])
            want = np.array([[c, -s], [s, c]]) @ x[0, t, 0, 2 * j: 2 * j + 2]
            np.testing.assert_allclose(y[0, t, 0, 2 * j: 2 * j + 2], want, rtol=1e-6, atol=1e-6)


def test_the_axes_split_the_head_16_56_56():
    """Pairs 0-7 are axis 0, 8-35 the row, 36-63 the column, each axis
    pair j at ``theta ** (-2j / d)`` of its own width d; the image token of
    row r and column c (text first) turns by r and c times those."""
    fc = F.FluxConfig.schnell()
    lt, gw = 256, 32
    cos, sin = F.rope_tables(fc, 32, gw, lt)
    angles = np.arctan2(sin, cos)
    r, c = 5, 3
    tok = lt + r * gw + c
    row = r * 10000.0 ** (-np.arange(0, 56, 2) / 56)
    col = c * 10000.0 ** (-np.arange(0, 56, 2) / 56)
    want = np.concatenate([np.zeros(8), row, col])
    np.testing.assert_allclose(angles[tok], np.arctan2(np.sin(want), np.cos(want)), atol=1e-5)


def test_the_tables_are_the_references_rotation_matrices():
    from benchmark.reference import flux_stream as R

    fc = F.FluxConfig.tiny()
    (cos, sin), pe = _tables(fc, 3, 5, 4)
    np.testing.assert_allclose(pe[..., 0, 0], cos, atol=1e-7)
    np.testing.assert_allclose(pe[..., 1, 0], sin, atol=1e-7)
    np.testing.assert_allclose(pe[..., 0, 1], -sin, atol=1e-7)
    x = _normal(6, (1, 19, 2, fc.head_dim))
    np.testing.assert_allclose(
        F.apply_rope(x, cos, sin),
        R.apply_rope(x.transpose(0, 2, 1, 3), pe).transpose(0, 2, 1, 3),
        atol=1e-6,
    )


def test_linear1_splits_into_separate_qkv_and_mlp_weights():
    """``linear1`` is q, k, v and the MLP's input side by side, and
    ``linear2`` reads the attention and the MLP side by side: the single
    block with its fused kernels equals the block computed by hand with
    four separate projections in and two out (the reference's norm,
    rotation and attention)."""
    from benchmark.reference import flux_stream as R

    fc = F.FluxConfig.tiny()
    w, m = fc.width, fc.mlp_width
    ks = jax.random.split(jax.random.PRNGKey(7), 8)
    wq, wk, wv, wm = (jax.random.normal(k, (w, n)) / np.sqrt(w)
                      for k, n in zip(ks, (w, w, w, m)))
    bq, bk, bv, bm = (0.1 * jax.random.normal(k, (n,)) for k, n in zip(ks[4:], (w, w, w, m)))
    wo, wp = jax.random.normal(ks[0], (w, w)) / np.sqrt(w), jax.random.normal(ks[1], (m, w)) / np.sqrt(m)
    norm = {n: {"scale": 1.0 + 0.1 * _normal(9 + i, (fc.head_dim,))}
            for i, n in enumerate(("query_norm", "key_norm"))}
    p = {
        "modulation": {"lin": {"kernel": 0.1 * _normal(3, (w, 3 * w)), "bias": jnp.zeros(3 * w)}},
        "linear1": {"kernel": jnp.concatenate([wq, wk, wv, wm], 1),
                    "bias": jnp.concatenate([bq, bk, bv, bm])},
        "norm": norm,
        "linear2": {"kernel": jnp.concatenate([wo, wp], 0), "bias": jnp.zeros(w)},
    }
    rope, pe = _tables(fc, 2, 2, 3)
    h, vec = _normal(8, (1, 7, w)), _normal(2, (1, w))
    with jax.default_matmul_precision("highest"):
        got = F._single_block(p, h, vec, rope, fc.heads, "xla")
        sh, sc, g = jnp.split(R.nn.silu(vec) @ p["modulation"]["lin"]["kernel"], 3, axis=-1)
        hn = R._norm(h) * (1 + sc[:, None]) + sh[:, None]
        split = lambda a: a.reshape(1, 7, fc.heads, -1).transpose(0, 2, 1, 3)  # noqa: E731
        q = R._rms(norm["query_norm"], split(hn @ wq + bq))
        k = R._rms(norm["key_norm"], split(hn @ wk + bk))
        a = R.attention(q, k, split(hn @ wv + bv), pe)
        want = h + g[:, None] * (a @ wo + R._gelu_tanh(hn @ wm + bm) @ wp)
    np.testing.assert_allclose(got, want, rtol=RTOL, atol=ATOL)


# -- rope_attention ----------------------------------------------------------------------

def _rope_case(length, heads, head_dim, extra, dtype):
    """(qkv, (cos, sin), (q_scale, k_scale)) of one seeded call."""
    ks = jax.random.split(jax.random.PRNGKey(length), 4)
    inner = heads * head_dim
    qkv = jax.random.normal(ks[0], (2, length, 3 * inner + extra), dtype)
    ang = jax.random.uniform(ks[1], (length, head_dim // 2), minval=-40.0, maxval=40.0)
    scales = tuple(1.0 + 0.2 * jax.random.normal(k, (2, head_dim)) for k in ks[2:])
    return qkv, (jnp.cos(ang), jnp.sin(ang)), scales


def _rope_reference(qkv, rope, scales, heads, text):
    """The model's XLA path (per-head RMS norm with the text and the image
    tokens' scales, rotation, softmax) in float32."""
    with jax.default_matmul_precision("highest"):
        return F._attend(qkv.astype(jnp.float32), scales, rope, heads, text, "xla")


@pytest.mark.parametrize(
    "length,heads,head_dim,extra,text,dtype,atol",
    [
        # the tiny family's double block: two heads a program, 8 text tokens
        (72, 2, 16, 0, 8, jnp.float32, 2e-5),
        # a single block's layout: the MLP's input after v, which is not read
        (300, 2, 128, 512, 0, jnp.float32, 2e-5),
        # fluxschnell2x512's joint length: every query in one block, K in
        # 640 + 640, 256 text tokens
        (1280, 1, 128, 0, 256, jnp.bfloat16, 2e-2),
    ],
)
def test_rope_attention_matches_norm_rotation_then_a_plain_softmax(
    length, heads, head_dim, extra, text, dtype, atol
):
    """The kernel against the model's XLA path in float32.  In bfloat16 the
    normed and rotated q and k are rounded to the operands' dtype before the
    scores, in the kernel as in the reference, so the tolerance is
    bfloat16's on outputs of order one.  A K longer than ``_ROPE_BLOCK_K``
    is met in blocks (``rope_kloop``), a shorter one in one (``rope``)."""
    qkv, rope, scales = _rope_case(length, heads, head_dim, extra, dtype)
    with count_attention_paths() as paths:
        got = PA.rope_attention(qkv, *rope, *scales, heads, text, interpret=True)
    assert dict(paths) == {"rope_kloop" if length > PA._ROPE_BLOCK_K else "rope": 1}
    assert got.shape == (2, length, heads * head_dim) and got.dtype == dtype
    want = _rope_reference(qkv, rope, scales, heads, text)
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want), atol=atol)


@pytest.mark.parametrize(
    "length,heads,head_dim,text,block_q,block_k",
    [
        # K of 700 in 640 + a ragged 60; 200 text tokens, inside the first
        # block and off the 256-row preparation chunks
        (700, 1, 128, 200, None, None),
        # two heads a program, K in 64 + 64 + 64 + a ragged 8, the text
        # tokens (40) ending inside the first block
        (200, 2, 16, 40, None, 64),
        # three query blocks (72 + 72 + 56 of a last block that runs past
        # L): the first one's program prepares K for the other two
        (200, 2, 16, 40, 72, 64),
        (1280, 1, 128, 256, 640, 512),
    ],
)
def test_rope_attention_meets_a_ragged_k_in_blocks(length, heads, head_dim, text,
                                                   block_q, block_k):
    """K met in blocks with a ragged last one, the text/image scale boundary
    off every block edge, and K prepared once for several query blocks:
    each against the plain float32 reference."""
    qkv, rope, scales = _rope_case(length, heads, head_dim, 0, jnp.float32)
    with count_attention_paths() as paths:
        got = PA.rope_attention(qkv, *rope, *scales, heads, text, block_q=block_q,
                                block_k=block_k, interpret=True)
    assert dict(paths) == {"rope_kloop": 1}
    want = _rope_reference(qkv, rope, scales, heads, text)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_flux_at_512_meets_its_keys_in_blocks_in_every_call():
    """A pass of ``fluxschnell2x512``'s stage (5 double and 10 single
    blocks, 256 + 1024 joint tokens) calls ``rope_attention`` 15 times, each
    meeting its 1280 keys in blocks; the tiny family's 72 keys are one
    block (``test_each_family_traces_its_own_attention_path``)."""
    fc = F.FluxConfig.schnell_stage()
    params = jax.eval_shape(lambda: F.init_flux(jax.random.PRNGKey(0), fc))
    x = jax.ShapeDtypeStruct((2, 64, 64, 16), jnp.bfloat16)
    ctx = jax.ShapeDtypeStruct((2, fc.text_tokens, fc.joint_attention_dim), jnp.bfloat16)
    pooled = jax.ShapeDtypeStruct((2, fc.pooled_projection_dim), jnp.bfloat16)
    t = jax.ShapeDtypeStruct((2,), jnp.float32)
    with count_attention_paths() as paths:
        jax.eval_shape(
            lambda p, x, t, c, v: F.apply_flux(p, x, t, c, fc, {"text_embeds": v}, "pallas"),
            params, x, t, ctx, pooled,
        )
    assert dict(paths) == {"rope_kloop": 15}


def test_rope_attention_refuses_tables_of_another_sequence():
    qkv = jnp.zeros((1, 16, 3 * 128))
    scale = jnp.ones((2, 128))
    with pytest.raises(ValueError, match="joint sequence"):
        PA.rope_attention(qkv, jnp.zeros((8, 64)), jnp.zeros((8, 64)), scale, scale, 1,
                          interpret=True)


def test_rope_attention_query_blocks():
    """1280 joint tokens: every query in one block over K in blocks of
    ``_ROPE_BLOCK_K`` (``_joint_blocks``, ``joint_attention``'s chooser); the
    tiny family's 72 in one block over one block of K."""
    assert PA._joint_blocks(1280, 1280, 2, PA._ROPE_BLOCK_K) == (1280, 640)
    assert PA._joint_blocks(72, 72, 4, PA._ROPE_BLOCK_K) == (72, 72)


# -- the registry ----------------------------------------------------------------------

@pytest.mark.parametrize(
    "model_id,family",
    [
        ("black-forest-labs/FLUX.1-schnell", "flux"),
        ("black-forest-labs/flux.1-dev", "flux"),
        ("tinyflux-test", "tinyflux"),      # not "tiny": the flux test is first
        ("tinysd3-test", "tinysd3"),
        ("tiny-test", "tiny"),
        ("stabilityai/sd-turbo", "sd21"),
    ],
)
def test_the_model_id_resolves_the_family(model_id, family):
    assert registry.family_of(model_id) == family


def test_the_stream_defaults_are_schnells():
    """Two stages of the unshifted ladder (sigma 0.49 / 0.18), no guidance,
    16-channel latents, pooled text in the state row, no fused epilogue."""
    cfg = registry.default_stream_config("black-forest-labs/FLUX.1-schnell", dtype="bfloat16")
    assert (cfg.scheduler, cfg.prediction_type, cfg.cfg_type) == ("flow", "flow", "none")
    assert cfg.t_index_list == (25, 40) and cfg.num_inference_steps == 50
    assert cfg.flow_shift == 1.0
    assert (cfg.latent_channels, cfg.height, cfg.latent_scale) == (16, 512, 8)
    assert cfg.use_added_cond and not cfg.use_fused_epilogue
    sig = S.flow_sub_sigmas(cfg.t_index_list, 50, cfg.flow_shift)
    np.testing.assert_allclose(sig, [1 - 25 / 49 * 0.999, 1 - 40 / 49 * 0.999], rtol=1e-12)
    assert np.prod(1 - sig) == pytest.approx(0.416, abs=1e-3)


@pytest.mark.parametrize(
    "model_id,kw,env,conflict",
    [
        ("black-forest-labs/FLUX.1-schnell", {}, "3", "DeepCache"),
        ("black-forest-labs/FLUX.1-schnell", {"frame_buffer_size": 2}, "", "frame_buffer_size 2"),
        ("black-forest-labs/FLUX.1-schnell+lllyasviel/control_v11p_sd15_canny", {}, "",
         "side network"),
    ],
)
def test_the_family_refuses_what_does_not_compose(monkeypatch, model_id, kw, env, conflict):
    monkeypatch.setenv("UNET_CACHE", env)
    with pytest.raises(ValueError, match=conflict) as e:
        registry.default_stream_config(model_id, **kw)
    assert "rectified-flow denoiser (flux)" in str(e.value)


@pytest.mark.parametrize("kw", [{"controlnet": "tiny-cnet"}, {"lora_dict": {"x.safetensors": 1.0}}])
def test_the_bundle_refuses_a_side_network_and_lora(kw):
    with pytest.raises(ValueError, match="denoiser \\(flux\\) takes no"):
        registry.load_model_bundle("tinyflux-test", **kw)


def test_the_prompt_is_t5s_stand_in_and_clips_pooled_output():
    """T5's states are the seeded stand-in the reference draws, per prompt;
    the pooled vector is CLIP's final-normed end-of-text state."""
    from benchmark.reference import flux_stream as R

    b = registry.load_model_bundle("tinyflux-test")
    fc = b.unet_cfg
    cond, uncond, extras = b.encode_prompt("a neon cat")
    assert cond.shape == uncond.shape == (1, fc.text_tokens, fc.joint_attention_dim)
    np.testing.assert_array_equal(cond, R.t5_stand_in("a neon cat", 8, 64))
    np.testing.assert_array_equal(uncond, R.t5_stand_in("", 8, 64))
    assert not np.array_equal(cond, b.encode_prompt("a calm sea")[0])
    assert np.unique(cond.reshape(fc.text_tokens, -1), axis=0).shape[0] == fc.text_tokens
    assert extras["pooled"].shape == (1, fc.pooled_projection_dim)


# -- the scheduler ------------------------------------------------------------------------

def test_the_scheduler_serves_flux_and_names_it():
    b = registry.load_model_bundle("tinyflux-test", attn_impl="pallas")
    cfg = registry.default_stream_config("tinyflux-test")
    s = BatchScheduler(
        b.stream_models, b.params, cfg, b.encode_prompt, max_sessions=1,
        prewarm=True, dp=1,
    )
    try:
        snap = s.snapshot()
        assert snap["batchsched_denoiser"] == {
            "kind": "flux", "latent_channels": 16, "joint_tokens": 8 + 8 * 8,
        }
        assert set(snap["batchsched_epilogue"].values()) == {"xla"}
        assert [dict(p) for p in snap["batchsched_attention_paths"].values()] == [{"rope": 3}]
        sess = s.claim("a", prompt="neon", seed=3)
        frame = np.random.default_rng(0).integers(0, 256, (64, 64, 3), np.uint8)
        outs = [np.asarray(sess.fetch(sess.submit(frame))) for _ in range(4)]
        assert outs[-1].shape == (64, 64, 3) and outs[-1].dtype == np.uint8
        assert s.states["noise"].shape[-1] == 16
        sess.release()
    finally:
        s.close()


def test_the_step_through_the_scheduler_matches_the_reference(tiny):
    """Frame after frame through ``BatchScheduler`` (TAESD, the two-stage
    ring, the flow coefficients, the transformer, float32) on the weights
    the reference reads: every returned frame is the reference's rounded to
    uint8 (0.5 of a level; the two differ by 1e-3 of a level before it)."""
    from benchmark.reference import flux_stream as R

    cfg, _, w = tiny
    b = registry.load_model_bundle("tinyflux-test")
    b.params.clear()
    b.params["clip"] = w["clip"]
    sc = registry.default_stream_config("tinyflux-test")
    s = BatchScheduler(
        b.stream_models, w, sc, b.encode_prompt, max_sessions=1, prewarm=False, dp=1,
    )
    ref = R.Reference(cfg, w).session("a red kite", 11)
    rng = np.random.default_rng(1)
    try:
        sess = s.claim("a", prompt="a red kite", seed=11)
        for _ in range(5):
            frame = rng.integers(0, 256, (64, 64, 3), np.uint8)
            got = np.asarray(sess.fetch(sess.submit(frame)), np.float32)
            want = ref.step(frame)
            assert np.abs(got - want).max() <= 0.51
        sess.release()
    finally:
        s.close()


def test_the_flux_state_row_is_pinned():
    """The row: T5's states, CLIP's pooled vector, float timesteps, a
    16-channel two-stage ring; no SDXL ``time_ids``."""
    b = registry.load_model_bundle("tinyflux-test")
    cfg = registry.default_stream_config("tinyflux-test")
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
    want = {
        "cond": ((1, 1, 8, 64), "float32"), "uncond": ((1, 1, 8, 64), "float32"),
        "added_text": ((1, 1, 32), "float32"),
        "delta": ((1,), "float32"), "guidance": ((1,), "float32"),
        "noise": ((1, 2, 16, 16, 16), "float32"), "stock": ((1, 2, 16, 16, 16), "float32"),
        "x_buf": ((1, 1, 16, 16, 16), "float32"),
        "coeffs/timesteps": ((1, 2), "float32"),
    }
    for name in ("alpha", "c_out", "c_skip", "next_alpha", "next_sigma", "sigma"):
        want[f"coeffs/{name}"] = ((1, 2), "float32")
    assert got == want


# -- pins: what the other families keep ---------------------------------------------------

def test_sd3s_sigmas_are_unchanged_to_the_bit():
    """The flow ladder's shift is the family's now: SD3's stays 3.0, and its
    four levels are the ones ``sd3flash4x512.json`` states, bit for bit."""
    with open(os.path.join(_CONFIGS, "sd3flash4x512.json")) as f:
        want = json.load(f)["stream"]["sigmas"]
    cfg = registry.default_stream_config("jasperai/flash-sd3")
    got = S.flow_sub_sigmas(cfg.t_index_list, cfg.num_inference_steps, cfg.flow_shift)
    np.testing.assert_array_equal(got, np.asarray(want))
    with open(os.path.join(_CONFIGS, "fluxschnell2x512.json")) as f:
        flux = json.load(f)["stream"]
    cfg = registry.default_stream_config("black-forest-labs/FLUX.1-schnell")
    got = S.flow_sub_sigmas(cfg.t_index_list, cfg.num_inference_steps, cfg.flow_shift)
    np.testing.assert_array_equal(got, np.asarray(flux["sigmas"]))


@pytest.mark.parametrize(
    "model_id,paths",
    [
        ("tiny-test", {"packed": 8}),
        ("tinysd3-test", {"joint": 2}),
        ("tinyflux-test", {"rope": 3}),
    ],
)
def test_each_family_traces_its_own_attention_path(model_id, paths):
    """A pass of each family's denoiser counts its kernel's calls alone:
    ``flash_attention`` and ``joint_attention`` as before, ``rope_attention``
    for FLUX only."""
    b = registry.load_model_bundle(model_id, attn_impl="pallas")
    cfg = registry.default_stream_config(model_id)
    h, w = cfg.latent_hw
    x = jnp.zeros((cfg.batch_size, h, w, cfg.latent_channels), jnp.float32)
    cond, *rest = b.encode_prompt("p")  # the one-tower UNet family gives no extras
    extras = rest[1] if len(rest) > 1 else {}
    rows = lambda a: jnp.broadcast_to(jnp.asarray(a), (cfg.batch_size,) + a.shape[1:])  # noqa: E731
    added = {"text_embeds": rows(extras["pooled"])} if cfg.use_added_cond else None
    t = jnp.zeros((cfg.batch_size,), jnp.float32 if cfg.scheduler == "flow" else jnp.int32)
    with count_attention_paths() as counted:
        jax.eval_shape(
            lambda p: b.stream_models.unet(p, x, t, rows(cond), added), b.params
        )
    assert dict(counted) == paths
