"""Pallas kernels vs XLA references (interpret mode on CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ai_rtc_agent_tpu.ops import lcm as L
from ai_rtc_agent_tpu.ops import rcfg as R
from ai_rtc_agent_tpu.ops import schedule as S
from ai_rtc_agent_tpu.ops.pallas import attention as PA
from ai_rtc_agent_tpu.ops.pallas import count_attention_paths
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
    "lq,lk,heads,head_dim,itemsize,want",
    [
        # what the chip sweep of PR 26 picked, per served shape, for a head
        # a program: kept (group 0) for 5 heads, which no pair divides,
        (4096, 4096, 5, 64, 2, (0, 512, 1024)),
        (4096, 77, 5, 64, 2, (0, 4096, 77)),
        # where all 8 heads' K and V do not fit a program,
        (4096, 4096, 8, 40, 2, (0, 512, 1024)),
        # and for heads astride lane tiles with over four queries a key
        (4096, 77, 8, 40, 2, (0, 4096, 77)),
        (1024, 77, 8, 80, 2, (0, 1024, 77)),
        # packed (PR 32): the fewest heads that fill whole 128-lane tiles
        (1024, 1024, 10, 64, 2, (2, 1024, 1024)),
        (1024, 77, 10, 64, 2, (2, 1024, 77)),
        (256, 256, 20, 64, 2, (2, 256, 256)),
        (256, 77, 20, 64, 2, (2, 256, 77)),
        (64, 77, 20, 64, 2, (2, 64, 77)),
        (1024, 1024, 8, 80, 2, (8, 256, 1024)),
        (256, 256, 8, 160, 2, (4, 256, 256)),
        (64, 77, 8, 160, 2, (4, 64, 77)),
        # two heads' unrolled K loops: half the queries a tile
        (4096, 4096, 10, 64, 2, (2, 256, 1024)),
        # one head of 128 is a group of its own
        (1024, 1024, 4, 128, 2, (1, 1024, 1024)),
        # float32 operands: half the queries a tile
        (4096, 4096, 1, 64, 4, (1, 256, 1024)),
        # 3 x 512 keys: the largest power of two that divides them
        (4096, 1536, 1, 64, 2, (1, 1024, 512)),
    ],
)
def test_choose_blocks(lq, lk, heads, head_dim, itemsize, want):
    assert PA._choose_blocks(lq, lk, heads, head_dim, itemsize) == want


@pytest.mark.parametrize("vmapped", [False, True], ids=["plain", "vmap"])
@pytest.mark.parametrize("batch", [1, 4])
@pytest.mark.parametrize("lk", [77, None], ids=["cross77", "self"])
@pytest.mark.parametrize(
    "heads,head_dim,lq,group",
    [(10, 64, 256, 2), (20, 64, 64, 2), (10, 64, 100, 2), (8, 160, 64, 4),
     (8, 80, 128, 8), (8, 40, 128, 8), (5, 64, 256, 5)],
)
def test_packed_path_is_bit_equal_to_per_head(
    monkeypatch, heads, head_dim, lq, group, lk, batch, vmapped
):
    """ISSUE 32: the same arithmetic on the same values, met in another
    layout.  Operands as ``[B, L, H*D]``, a group of heads a program (a
    pair at head dim 64; 4 at 160; all 8 at 80 and 40), against a head a
    program on transposed operands: equal bit for bit, in bf16 as served,
    under ``vmap`` as the bucket step runs it, and with an ``lq`` no block
    divides (100 rows in blocks of 64).  Five heads of 64 stay a head a
    program by the rule; asked for all five together, they agree too."""
    lk = lk or lq
    keys = jax.random.split(jax.random.PRNGKey(heads * head_dim + lq + lk), 3)
    q = jax.random.normal(keys[0], (batch, lq, heads, head_dim), jnp.bfloat16)
    k = jax.random.normal(keys[1], (batch, lk, heads, head_dim), jnp.bfloat16)
    v = jax.random.normal(keys[2], (batch, lk, heads, head_dim), jnp.bfloat16)
    kw = {"block_q": 64} if lq == 100 else {}
    rule = PA._choose_blocks
    assert rule(lq, lk, heads, head_dim, 2)[0] == (group if heads != 5 else 0)

    def run(group):
        # the same key blocks both ways; rows of q are independent
        monkeypatch.setattr(
            PA, "_choose_blocks", lambda *shape: (group,) + rule(*shape)[1:]
        )
        fn = lambda q, k, v: PA.flash_attention(q, k, v, interpret=True, **kw)
        with count_attention_paths() as paths:
            if vmapped:
                out = jax.vmap(fn)(q[None], k[None], v[None])[0]
            else:
                out = fn(q, k, v)
        return out, dict(paths)

    packed, paths = run(group)
    assert paths == {"packed": 1}
    per_head, paths = run(0)
    assert paths == {"per_head": 1}
    assert packed.shape == q.shape and packed.dtype == jnp.bfloat16
    np.testing.assert_array_equal(
        np.asarray(packed.astype(jnp.float32)),
        np.asarray(per_head.astype(jnp.float32)),
    )
    want = PA._xla_attention(*(a.astype(jnp.float32) for a in (q, k, v)))
    diff = np.max(np.abs(np.asarray(packed.astype(jnp.float32)) - np.asarray(want)))
    assert diff < ATTN_ATOL, diff


def test_attention_path_counter_counts_traced_calls_by_layout():
    """The counter ``BatchScheduler`` reports per bucket: one count a call
    site traced inside the block, by the path the call's shapes chose;
    nothing outside a block, nothing for a cached trace, nothing for the
    ragged-``lk`` fall-back (no Mosaic call)."""
    def attend(heads, head_dim, lk, **kw):
        q = jnp.zeros((1, 64, heads, head_dim), jnp.bfloat16)
        kv = jnp.zeros((1, lk, heads, head_dim), jnp.bfloat16)
        return PA.flash_attention(q, kv, kv, interpret=True, **kw)

    step = jax.jit(
        lambda: (attend(20, 64, 64), attend(8, 160, 77), attend(5, 64, 77))
    )
    with count_attention_paths() as paths:
        step.lower()
        attend(2, 16, 10, block_k=8)  # 10 keys in blocks of 8: plain XLA
    assert dict(paths) == {"packed": 2, "per_head": 1}
    with count_attention_paths() as again:
        step.lower()  # the trace is cached: the Python body does not run
    assert dict(again) == {}
    attend(20, 64, 64)  # nobody counting
    assert dict(paths) == {"packed": 2, "per_head": 1}


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
