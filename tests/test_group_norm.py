"""``models/layers.py group_norm`` against two references it shares no code
with: ``benchmark/reference/nn.py``'s float32 one and a NumPy two-pass in
float64, at every ``(n, h, w, c)`` the four benchmark configurations reach.

What is held (ISSUE 35): float32 statistics and float32 application whatever
the activation's dtype, biased variance, ``eps`` 1e-5, 32 groups; the same
answer at one row and at several (which put an optimization barrier in
front of the sums), under ``vmap``, for an input whose mean is far from zero
(what ``E[x^2] - mean^2`` loses) and for a constant one.  What the formula
compiles to on the chip is ``tests/test_pallas_aot_v5e.py``'s.  Parity, not
speeds.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ai_rtc_agent_tpu.models.layers import group_norm
from benchmark.reference import nn

GROUPS = 32
# (square, channels): SD1.5 / SD2.1 (320/640/1280/1280, skip concats in the
# up blocks) and SDXL (320/640/1280) at 512x512, the side network's among them
_TIERS = [
    (64, 320), (64, 640), (64, 960),
    (32, 320), (32, 640), (32, 960), (32, 1280), (32, 1920),
    (16, 640), (16, 1280), (16, 1920), (16, 2560),
    (8, 1280), (8, 2560),
]
# |y - ref| <= TOL * (1 + |ref|): bfloat16 keeps 8 bits, so the result's own
# rounding is 2**-9 of it; float32 sums of 4e4 to 4e6 terms
TOL = {jnp.bfloat16: 4e-3, jnp.float32: 1e-4}


def _numpy_two_pass(scale, bias, x, groups, eps=1e-5, act=None):
    x = np.asarray(x, np.float64)
    n, h, w, c = x.shape
    g = x.reshape(n, h * w, groups, c // groups)
    mean = g.mean(axis=(1, 3), keepdims=True)
    var = ((g - mean) ** 2).mean(axis=(1, 3), keepdims=True)
    y = ((g - mean) / np.sqrt(var + eps)).reshape(n, h, w, c)
    y = y * np.asarray(scale, np.float64) + np.asarray(bias, np.float64)
    return y / (1.0 + np.exp(-y)) if act == "silu" else y


def _case(rng, shape, dtype, mean=0.3, std=1.5):
    c = shape[-1]
    x = jnp.asarray(rng.normal(mean, std, shape), dtype)
    p = {
        "scale": jnp.asarray(rng.normal(1.0, 0.2, (c,)), jnp.float32),
        "bias": jnp.asarray(rng.normal(0.0, 0.2, (c,)), jnp.float32),
    }
    return p, x


def _assert_close(y, ref, tol):
    y = np.asarray(y.astype(jnp.float32), np.float64)
    err = np.abs(y - ref) / (1.0 + np.abs(ref))
    assert err.max() <= tol, (err.max(), tol)


@pytest.mark.parametrize("n", [1, 4])
@pytest.mark.parametrize("side,c", _TIERS)
def test_served_shapes_match_both_references(rng, side, c, n):
    p, x = _case(rng, (n, side, side, c), jnp.bfloat16)
    y = jax.jit(lambda p, x: group_norm(p, x, GROUPS, act="silu"))(p, x)
    assert y.dtype == x.dtype and y.shape == x.shape
    tol = TOL[jnp.bfloat16]
    _assert_close(y, _numpy_two_pass(p["scale"], p["bias"], x, GROUPS, act="silu"), tol)
    plain = nn.silu(nn.group_norm(p, x.astype(jnp.float32), GROUPS))
    _assert_close(y, np.asarray(plain, np.float64), tol)


# one row and several (the barrier's side), small enough to run often
_ONE_ROW = (1, 32, 32, 320)
_FOUR_ROWS = (4, 16, 16, 640)


@pytest.mark.parametrize("vmap_k", [0, 1, 2])
@pytest.mark.parametrize("act", [None, "silu"])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("shape", [_ONE_ROW, _FOUR_ROWS])
def test_dtypes_activation_and_vmap(rng, shape, dtype, act, vmap_k):
    lead = (vmap_k,) if vmap_k else ()
    p, x = _case(rng, lead + shape, dtype)
    fn = lambda x: group_norm(p, x, GROUPS, act=act)
    y = jax.jit(jax.vmap(fn) if vmap_k else fn)(x)
    assert y.dtype == x.dtype
    ref = _numpy_two_pass(
        p["scale"], p["bias"], x.reshape((-1,) + shape[1:]), GROUPS, act=act
    ).reshape(x.shape)
    _assert_close(y, ref, TOL[dtype])


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("shape", [_ONE_ROW, _FOUR_ROWS])
def test_a_mean_far_from_zero_keeps_its_variance(rng, shape, dtype):
    """Mean 50, deviation 1: ``E[x^2] - mean^2`` from float32 sums is 2501 -
    2500 with the sums' rounding on both, several per cent of the variance;
    the two-pass form holds the tolerance of any other input."""
    p, x = _case(rng, shape, dtype, mean=50.0, std=1.0)
    y = jax.jit(lambda x: group_norm(p, x, GROUPS, act="silu"))(x)
    ref = _numpy_two_pass(p["scale"], p["bias"], x, GROUPS, act="silu")
    _assert_close(y, ref, TOL[dtype])


@pytest.mark.parametrize("shape", [_ONE_ROW, _FOUR_ROWS])
def test_a_constant_input_returns_the_bias(rng, shape):
    p, _ = _case(rng, shape, jnp.bfloat16)
    x = jnp.full(shape, 3.0, jnp.bfloat16)
    y = jax.jit(lambda x: group_norm(p, x, GROUPS))(x)
    ref = np.broadcast_to(np.asarray(p["bias"], np.float64), shape)
    _assert_close(y, ref, TOL[jnp.bfloat16])


@pytest.mark.parametrize("shape", [_ONE_ROW, _FOUR_ROWS])
def test_gradients_match_the_reference(rng, shape):
    """The trainer differentiates the UNet: the barrier has to let the
    cotangents through."""
    p, x = _case(rng, shape, jnp.float32)
    loss = lambda fn: lambda p, x: jnp.sum(jnp.sin(fn(p, x)))
    ours = jax.grad(
        loss(lambda p, x: group_norm(p, x, GROUPS, act="silu")), argnums=(0, 1)
    )(p, x)
    plain = jax.grad(
        loss(lambda p, x: nn.silu(nn.group_norm(p, x, GROUPS))), argnums=(0, 1)
    )(p, x)
    for a, b in zip(jax.tree.leaves(ours), jax.tree.leaves(plain)):
        np.testing.assert_allclose(a, b, rtol=2e-3, atol=2e-4)


def test_groups_fall_back_to_a_divisor_of_the_channels(rng):
    # the tiny test models have fewer channels than 32 groups
    p, x = _case(rng, (1, 8, 8, 24), jnp.float32)
    y = group_norm(p, x, GROUPS)
    _assert_close(y, _numpy_two_pass(p["scale"], p["bias"], x, 24), 1e-4)
