"""Pallas kernels vs XLA references (interpret mode on CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ai_rtc_agent_tpu.ops import lcm as L
from ai_rtc_agent_tpu.ops import rcfg as R
from ai_rtc_agent_tpu.ops import schedule as S
from ai_rtc_agent_tpu.ops.pallas import attention as PA
from ai_rtc_agent_tpu.ops.pallas import fused_scheduler as FS


def _coeffs():
    sch = S.make_schedule()
    bt = S.batched_sub_timesteps([18, 26, 35, 45], 50)
    return L.make_step_coeffs(sch, bt).as_jnp()


@pytest.mark.parametrize("cfg_type", ["self", "none"])
def test_fused_epilogue_matches_composed_ops(rng, cfg_type):
    c = _coeffs()
    shape = (4, 8, 8, 4)
    x = jnp.asarray(rng.standard_normal(shape).astype(np.float32))
    eps_c = jnp.asarray(rng.standard_normal(shape).astype(np.float32))
    stock = jnp.asarray(rng.standard_normal(shape).astype(np.float32))
    noise = jnp.asarray(rng.standard_normal(shape).astype(np.float32))
    g, d = 1.5, 0.9

    den, adv, stock_new = FS.fused_stream_epilogue(
        x, eps_c, stock, noise, c, g, d, cfg_type, interpret=True
    )

    # composed reference path (ops/lcm + ops/rcfg)
    if cfg_type == "self":
        eps = R.combine_residual(eps_c, stock, g, d)
    else:
        eps = eps_c
    den_ref = L.lcm_denoise(x, eps, c)
    adv_ref = L.renoise_next(den_ref, noise, c)
    np.testing.assert_allclose(np.asarray(den), np.asarray(den_ref), rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(adv), np.asarray(adv_ref), rtol=1e-4, atol=1e-5)
    if cfg_type == "self":
        stock_ref = R.update_stock_noise(stock, eps_c, c.alpha, c.sigma)
        np.testing.assert_allclose(
            np.asarray(stock_new), np.asarray(stock_ref), rtol=1e-4, atol=1e-5
        )
    else:
        np.testing.assert_allclose(np.asarray(stock_new), np.asarray(stock))


def test_flash_attention_matches_dense(rng):
    B, L_, H, D = 2, 64, 2, 16
    q = jnp.asarray(rng.standard_normal((B, L_, H, D)).astype(np.float32))
    k = jnp.asarray(rng.standard_normal((B, L_, H, D)).astype(np.float32))
    v = jnp.asarray(rng.standard_normal((B, L_, H, D)).astype(np.float32))
    got = np.asarray(PA.flash_attention(q, k, v, block_q=32, block_k=32, interpret=True))
    want = np.asarray(PA._xla_attention(q, k, v))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


# scripts/tpu_numerics_check.py's tolerance for bf16 in and out on the chip
ATTN_ATOL = 2e-2


@pytest.mark.parametrize("blocks", [None, (32, 32)], ids=["auto", "32x32"])
@pytest.mark.parametrize("batch", [1, 4])
@pytest.mark.parametrize("lk", [64, 7], ids=["self", "cross7"])
@pytest.mark.parametrize("head_dim", [16, 40, 64, 80, 160])
def test_flash_attention_bf16_matches_f32_dense(head_dim, lk, batch, blocks):
    """bf16 in and out, as the served graphs call it: operands reach the
    contractions in bf16, statistics and accumulator in f32.  Against the
    dense reference on the same values in f32, at the chip script's
    tolerance, with the blocks the shapes choose and with an explicit pair
    (which loops over two key blocks of 32 in self-attention)."""
    keys = jax.random.split(jax.random.PRNGKey(head_dim + lk + batch), 3)
    q = jax.random.normal(keys[0], (batch, 64, 2, head_dim), jnp.bfloat16)
    k = jax.random.normal(keys[1], (batch, lk, 2, head_dim), jnp.bfloat16)
    v = jax.random.normal(keys[2], (batch, lk, 2, head_dim), jnp.bfloat16)
    kw = {} if blocks is None else {"block_q": blocks[0], "block_k": blocks[1]}
    got = PA.flash_attention(q, k, v, interpret=True, **kw)
    assert got.dtype == jnp.bfloat16 and got.shape == q.shape
    want = PA._xla_attention(*(a.astype(jnp.float32) for a in (q, k, v)))
    diff = np.max(np.abs(np.asarray(got.astype(jnp.float32)) - np.asarray(want)))
    assert diff < ATTN_ATOL, diff


@pytest.mark.parametrize("lk", [4096, 77], ids=["loop", "single_pass"])
def test_flash_attention_f32_caller_keeps_f32(rng, lk):
    """The dtype decides, nothing else: float32 inputs meet the dense
    reference at float32 tolerance through the shape-chosen blocks, in the
    unrolled K loop (4096 keys in four blocks) and in the single pass."""
    q = jnp.asarray(rng.standard_normal((1, 256, 1, 16)).astype(np.float32))
    k = jnp.asarray(rng.standard_normal((1, lk, 1, 16)).astype(np.float32))
    v = jnp.asarray(rng.standard_normal((1, lk, 1, 16)).astype(np.float32))
    got = PA.flash_attention(q, k, v, interpret=True)
    assert got.dtype == jnp.float32
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(PA._xla_attention(q, k, v)), rtol=2e-4, atol=2e-5
    )


@pytest.mark.parametrize(
    "lq,lk,head_dim,itemsize,want",
    [
        # what the chip sweep of PR 26 picked, per served shape
        (4096, 4096, 64, 2, (512, 1024)),
        (4096, 4096, 40, 2, (512, 1024)),
        (1024, 1024, 80, 2, (1024, 1024)),
        (256, 256, 160, 2, (256, 256)),
        (64, 64, 160, 2, (64, 64)),
        (4096, 77, 64, 2, (4096, 77)),
        (64, 77, 160, 2, (64, 77)),
        # float32 operands: half the queries a tile
        (4096, 4096, 64, 4, (256, 1024)),
        # 3 x 512 keys: the largest power of two that divides them
        (4096, 1536, 64, 2, (1024, 512)),
    ],
)
def test_choose_blocks(lq, lk, head_dim, itemsize, want):
    assert PA._choose_blocks(lq, lk, head_dim, itemsize) == want


def test_flash_attention_ragged_falls_back(rng):
    B, Lq, Lk, H, D = 1, 10, 7, 2, 8  # not divisible by blocks
    q = jnp.asarray(rng.standard_normal((B, Lq, H, D)).astype(np.float32))
    k = jnp.asarray(rng.standard_normal((B, Lk, H, D)).astype(np.float32))
    v = jnp.asarray(rng.standard_normal((B, Lk, H, D)).astype(np.float32))
    got = np.asarray(PA.flash_attention(q, k, v, block_q=8, block_k=8, interpret=True))
    want = np.asarray(PA._xla_attention(q, k, v))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


def test_flash_attention_rejects_mask(rng):
    q = jnp.zeros((1, 8, 1, 4))
    with pytest.raises(NotImplementedError):
        PA.flash_attention(q, q, q, mask=jnp.zeros((8, 8)))
