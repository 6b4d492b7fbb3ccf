"""Plain float32 building blocks of the reference.

Straightforward ``jax.numpy``, every contraction at ``highest`` precision
(on a TPU a float32 matmul otherwise runs in bfloat16 passes).  No kernels,
no batching tricks, no state.  The weight tree is held in the dtype it was
served in and each leaf is widened to float32 where it is used (``f32``):
bfloat16 -> float32 is exact, so every operand is the number a float32 copy
of the tree would hold, and the tree is held once, at 2 bytes a parameter.
Imports nothing of the program: the only thing shared with it is the layout
of the weight tree (nested dicts of ``kernel`` [in..., out] / ``bias`` /
``scale`` leaves, NHWC activations, HWIO convolution kernels), which
``benchmark/weights.py`` fills from the seed for both sides.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST


def f32(leaf):
    """A weight leaf as float32, at the place that reads it."""
    return leaf.astype(jnp.float32)


def dense(p, x):
    y = jnp.matmul(x, f32(p["kernel"]), precision=HI)
    return y + f32(p["bias"]) if "bias" in p else y


def conv(p, x, stride: int = 1):
    """NHWC convolution with torch-style symmetric padding k//2."""
    k = p["kernel"].shape[0]
    pad = k // 2
    y = jax.lax.conv_general_dilated(
        x, f32(p["kernel"]), (stride, stride), ((pad, pad), (pad, pad)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HI,
    )
    return y + f32(p["bias"]) if "bias" in p else y


def group_norm(p, x, groups: int, eps: float = 1e-5):
    n, h, w, c = x.shape
    g = x.reshape(n, h * w, groups, c // groups)
    mean = g.mean(axis=(1, 3), keepdims=True)
    var = ((g - mean) ** 2).mean(axis=(1, 3), keepdims=True)
    g = (g - mean) / jnp.sqrt(var + eps)
    return g.reshape(n, h, w, c) * f32(p["scale"]) + f32(p["bias"])


def layer_norm(p, x, eps: float = 1e-5):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * f32(p["scale"]) + f32(p["bias"])


def silu(x):
    return x / (1.0 + jnp.exp(-x))


def gelu(x):
    return 0.5 * x * (1.0 + jax.lax.erf(x / math.sqrt(2.0)))


def quick_gelu(x):
    return x / (1.0 + jnp.exp(-1.702 * x))


def softmax_attention(q, k, v, mask=None):
    """q [B,Lq,H,D], k/v [B,Lk,H,D] -> [B,Lq,H,D]; whole score matrix."""
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HI) / math.sqrt(q.shape[-1])
    if mask is not None:
        s = s + mask
    s = s - s.max(-1, keepdims=True)
    w = jnp.exp(s)
    w = w / w.sum(-1, keepdims=True)
    return jnp.einsum("bhqk,bkhd->bqhd", w, v, precision=HI)


def multi_head(p, x, ctx, heads: int):
    """UNet attention: bias-free q/k/v projections, biased output."""
    ctx = x if ctx is None else ctx
    b, lq, _ = x.shape
    q = dense(p["to_q"], x)
    k = dense(p["to_k"], ctx)
    v = dense(p["to_v"], ctx)
    d = q.shape[-1] // heads
    o = softmax_attention(
        q.reshape(b, lq, heads, d),
        k.reshape(b, ctx.shape[1], heads, d),
        v.reshape(b, ctx.shape[1], heads, d),
    )
    return dense(p["to_out"], o.reshape(b, lq, heads * d))


def upsample2x(x):
    return jnp.repeat(jnp.repeat(x, 2, axis=1), 2, axis=2)


def sinusoid(t, dim: int, max_period: float = 10000.0):
    """diffusers' timestep embedding with flip_sin_to_cos: [cos | sin]."""
    half = dim // 2
    freqs = jnp.exp(-math.log(max_period) * jnp.arange(half, dtype=jnp.float32) / half)
    a = jnp.asarray(t, jnp.float32)[:, None] * freqs[None, :]
    return jnp.concatenate([jnp.cos(a), jnp.sin(a)], axis=-1)
