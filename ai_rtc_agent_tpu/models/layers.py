"""Foundational pure-function layers for the param-pytree model zoo.

Design (TPU-first, replaces the diffusers/torch module classes the reference
leans on at lib/wrapper.py:12-17):

* A "module" is a pair of plain functions: ``init_*(key, cfg) -> params`` and
  ``apply(params, x, ...) -> y``.  Params are nested dicts of jnp arrays —
  a pytree that jit/pjit/shard_map/optax all consume natively, and that maps
  1:1 onto HF safetensors key paths (see models/loader.py).
* Layout is NHWC everywhere; conv kernels are HWIO (see ops/image.py for the
  rationale).  Matmul-heavy ops keep the contracted dimension minor so XLA
  tiles them straight onto the MXU.
* Compute dtype follows the activation dtype; params are cast at use (XLA
  fuses the casts).  Normalization statistics are always fp32 for bf16
  stability.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


# --------------------------------------------------------------------------
# initializers
# --------------------------------------------------------------------------

def _fan_in_normal(key, shape, fan_in, scale=1.0, dtype=jnp.float32):
    std = scale / math.sqrt(max(fan_in, 1))
    return jax.random.normal(key, shape, dtype) * std


def init_linear(key, in_dim: int, out_dim: int, bias: bool = True, scale: float = 1.0):
    kw, _ = jax.random.split(key)
    p = {"kernel": _fan_in_normal(kw, (in_dim, out_dim), in_dim, scale)}
    if bias:
        p["bias"] = jnp.zeros((out_dim,), jnp.float32)
    return p


def init_conv(key, in_ch: int, out_ch: int, k: int = 3, bias: bool = True, scale: float = 1.0):
    kw, _ = jax.random.split(key)
    p = {"kernel": _fan_in_normal(kw, (k, k, in_ch, out_ch), in_ch * k * k, scale)}
    if bias:
        p["bias"] = jnp.zeros((out_ch,), jnp.float32)
    return p


def init_norm(ch: int):
    return {"scale": jnp.ones((ch,), jnp.float32), "bias": jnp.zeros((ch,), jnp.float32)}


def zeros_like_params(params):
    """Zero-init a param pytree (ControlNet zero-convs, LoRA B matrices)."""
    return jax.tree.map(jnp.zeros_like, params)


# --------------------------------------------------------------------------
# primitives
# --------------------------------------------------------------------------

def _kernel(p, dtype):
    """Dense or w8-quantized kernel (models/quant.py): the dequant multiply
    fuses into the consuming matmul/conv, so int8 storage halves weight HBM
    reads with bf16 MXU compute."""
    if "kernel_q" in p:
        return p["kernel_q"].astype(dtype) * p["scale"].astype(dtype)
    return p["kernel"].astype(dtype)


def linear(p, x):
    w = _kernel(p, x.dtype)
    y = x @ w
    if "lora_down" in p:
        # per-session LoRA factor rows grafted by adapters/bank.py: the
        # low-rank residual (x @ down.T) @ up.T with scale*alpha/r folded
        # into up at load.  Zero factors contribute exactly 0.0 (empty
        # slots stay bit-identical to base); composes with the w8 branch
        # above because the residual reads the factors, not the kernel.
        down = p["lora_down"].astype(x.dtype)
        up = p["lora_up"].astype(x.dtype)
        y = y + (x @ down.T) @ up.T
    if "bias" in p:
        y = y + p["bias"].astype(x.dtype)
    return y


def conv2d(p, x, stride: int = 1, padding="SAME"):
    """NHWC conv, HWIO kernel.

    ``padding`` accepts an int for torch-style SYMMETRIC padding.  This
    matters at stride 2: XLA's "SAME" pads asymmetrically (bottom/right
    only for a 3x3), while the HF checkpoints' torch convs pad 1 on every
    edge — the two produce different values on every downsample, so
    stride-2 call sites must pass the torch number, not "SAME" (pinned by
    tests/test_loader_value_pin.py::test_conv_strided_values_match_torch).
    At stride 1 with odd kernels the two agree."""
    w = _kernel(p, x.dtype)
    if isinstance(padding, int):
        padding = ((padding, padding), (padding, padding))
    y = jax.lax.conv_general_dilated(
        x,
        w,
        window_strides=(stride, stride),
        padding=padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )
    if "bias" in p:
        y = y + p["bias"].astype(x.dtype)
    return y


def group_norm(
    p, x, groups: int = 32, eps: float = 1e-5, act: str | None = None
):
    """GroupNorm over NHWC, then ``act`` (a key of ``ACTIVATIONS``) if given:
    float32 statistics (two-pass, biased variance) and float32 application
    whatever the activation's dtype, which the result keeps.

    The statistics are summed a channel first: XLA fuses the convert into
    the two reductions over ``(h, w)`` and the groups meet on the tiny
    ``[n, g, c // g]``, so the activation itself is never reshaped and never
    float32 in HBM; the application is one multiply-add a channel.  Written
    as ``x.astype(f32).reshape(n, h*w, g, c//g)`` the compiled step wrote
    the activation out in float32 and transposed that copy so that a
    group's channels lay together (PERF.md section 6, PR 35)."""
    n, h, w, c = x.shape
    g = min(groups, c)
    while c % g:
        g -= 1
    count = h * w * (c // g)
    if n > 1:
        # a convolution over several rows hands its consumer float32 in its
        # own windowed layout when the consumer's convert fuses into it, and
        # the reductions below then re-lay that out twice; behind the
        # barrier it writes the activation once, in its own dtype (on the
        # chip a [4,64,64,320] resnet 0.84 -> 0.67 ms; at n=1 there is no
        # such layout and the barrier costs 2-9 %: the same section)
        x = jax.lax.optimization_barrier(x)

    def over_group(per_channel):  # [n, c] sums -> [n, 1, 1, c] group means
        grouped = per_channel.reshape(n, g, c // g).sum(-1, keepdims=True)
        grouped = jnp.broadcast_to(grouped / count, (n, g, c // g))
        return grouped.reshape(n, 1, 1, c)

    mean = over_group(jnp.sum(x, axis=(1, 2), dtype=jnp.float32))
    centred = x.astype(jnp.float32) - mean
    var = over_group(jnp.sum(centred * centred, axis=(1, 2)))
    a = jax.lax.rsqrt(var + eps) * p["scale"].astype(jnp.float32)
    b = p["bias"].astype(jnp.float32) - mean * a
    y = x.astype(jnp.float32) * a + b
    if act is not None:
        y = ACTIVATIONS[act](y)
    return y.astype(x.dtype)


def layer_norm(p, x, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    mean = xf.mean(-1, keepdims=True)
    var = xf.var(-1, keepdims=True)
    y = (xf - mean) * jax.lax.rsqrt(var + eps)
    return (y * p["scale"] + p["bias"]).astype(x.dtype)


def modulated_norm(x, shift, scale, eps: float = 1e-6):
    """LayerNorm without affine (float32 statistics), then ``* (1 + scale)
    + shift``: the adaptive norm of the transformer denoisers (MMDiT,
    FLUX); shift / scale [B, 1, D]."""
    xf = x.astype(jnp.float32)
    mean = xf.mean(-1, keepdims=True)
    var = jnp.square(xf - mean).mean(-1, keepdims=True)
    y = (xf - mean) * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + scale.astype(jnp.float32)) + shift.astype(jnp.float32)).astype(x.dtype)


def gelu_tanh_ff(p, x):
    """``out(GELU-tanh(proj(x)))``: the transformer denoisers' feed-forward."""
    return linear(p["out"], jax.nn.gelu(linear(p["proj"], x), approximate=True))


def silu(x):
    return x * jax.nn.sigmoid(x)


def gelu(x):
    return jax.nn.gelu(x, approximate=False)


def quick_gelu(x):
    """CLIP ViT-L activation: x * sigmoid(1.702 x)."""
    return x * jax.nn.sigmoid(1.702 * x)


ACTIVATIONS = {"silu": silu, "gelu": gelu, "quick_gelu": quick_gelu}


def timestep_embedding(timesteps, dim: int, max_period: int = 10000, dtype=jnp.float32):
    """Sinusoidal timestep embedding [B] -> [B, dim] (diffusers convention:
    flip_sin_to_cos=True, downscale_freq_shift=0, i.e. [cos | sin])."""
    half = dim // 2
    freqs = jnp.exp(
        -math.log(max_period) * jnp.arange(half, dtype=jnp.float32) / half
    )
    args = jnp.asarray(timesteps, jnp.float32)[:, None] * freqs[None, :]
    emb = jnp.concatenate([jnp.cos(args), jnp.sin(args)], axis=-1)
    if dim % 2:
        emb = jnp.pad(emb, ((0, 0), (0, 1)))
    return emb.astype(dtype)


# --------------------------------------------------------------------------
# attention
# --------------------------------------------------------------------------

# Ambient sequence-parallel context for attn_impl="ring"/"ulysses": the
# engine/trainer activates a mesh around tracing, and every attention call in
# the model routes its token axis over the `sp` mesh axis.  Trace-time state
# (meshes are static under jit), not runtime state.
_SP_CTX: list = []  # stack of (mesh, axis, batch_axis)


from contextlib import contextmanager  # noqa: E402


@contextmanager
def sp_attention_mesh(mesh, axis: str = "sp", batch_axis: str | None = None):
    """Activate sequence-parallel attention for model applies traced inside
    (SURVEY.md section 2c SP row; VERDICT r1: 'sp>1 must change the
    attention code path').  ``batch_axis`` co-shards the batch dim so the
    sp attention composes with dp under one jit."""
    _SP_CTX.append((mesh, axis, batch_axis))
    try:
        yield
    finally:
        _SP_CTX.pop()


def current_sp_mesh():
    return _SP_CTX[-1] if _SP_CTX else (None, "sp", None)


def init_attention(key, query_dim: int, context_dim: int | None, heads: int, head_dim: int):
    context_dim = context_dim or query_dim
    inner = heads * head_dim
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {
        "to_q": init_linear(k1, query_dim, inner, bias=False),
        "to_k": init_linear(k2, context_dim, inner, bias=False),
        "to_v": init_linear(k3, context_dim, inner, bias=False),
        "to_out": init_linear(k4, inner, query_dim),
    }


def attention(p, x, context=None, heads: int = 8, mask=None, attn_impl: str = "xla"):
    """Multi-head attention. x: [B, Lq, D], context: [B, Lk, Dc] or None.

    ``attn_impl``:
      "xla"     einsum softmax, XLA-fused (default)
      "pallas"  flash kernel from ops/pallas (long token counts on real TPU)
      "ring"    sequence-parallel over the active ``sp_attention_mesh``:
                self-attention streams K/V shards around the ICI ring
                (parallel/ring_attention.ring_attention); cross-attention
                keeps queries sharded with the short text context replicated
      "ulysses" same dispatch but head-parallel all_to_all for self-attn
    """
    is_self = context is None
    context = x if context is None else context
    q = linear(p["to_q"], x)
    k = linear(p["to_k"], context)
    v = linear(p["to_v"], context)
    b, lq, inner = q.shape
    hd = inner // heads
    q = q.reshape(b, lq, heads, hd)
    k = k.reshape(b, context.shape[1], heads, hd)
    v = v.reshape(b, context.shape[1], heads, hd)

    if attn_impl in ("ring", "ulysses"):
        o = _sdpa_sp(q, k, v, is_self, attn_impl, mask)
    elif attn_impl == "pallas":
        from ..ops.pallas import attention as pattn  # lazy; TPU paths only

        o = pattn.flash_attention(q, k, v, mask=mask)
    else:
        o = _sdpa_xla(q, k, v, mask)
    o = o.reshape(b, lq, inner)
    return linear(p["to_out"], o)


def _sdpa_sp(q, k, v, is_self: bool, kind: str, mask=None):
    """Sequence-parallel dispatch; falls back to the dense XLA path when no
    sp mesh is active or the token count doesn't tile over it (e.g. the
    8x8=64-token bottom level with sp=8 still divides; a 7-token CLIP
    context does not — it goes through the replicated-KV cross path)."""
    mesh, axis, batch_axis = current_sp_mesh()
    n = mesh.shape.get(axis, 1) if mesh is not None else 1
    if mesh is None or n == 1 or mask is not None:
        return _sdpa_xla(q, k, v, mask)
    from ..parallel import ring_attention as RA

    lq, heads = q.shape[1], q.shape[2]
    if lq % n:
        return _sdpa_xla(q, k, v, mask)
    if not is_self:
        return RA.sp_cross_attention(q, k, v, mesh, axis, batch_axis)
    if kind == "ulysses" and heads % n == 0:
        return RA.ulysses_attention(q, k, v, mesh, axis, batch_axis)
    return RA.ring_attention(q, k, v, mesh, axis, batch_axis)


def _sdpa_xla(q, k, v, mask=None):
    """[B,L,H,Dh] scaled dot-product attention with fp32 softmax."""
    scale = 1.0 / math.sqrt(q.shape[-1])
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    if mask is not None:
        logits = logits + mask
    w = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", w, v)


def causal_mask(length: int, dtype=jnp.float32):
    """[1,1,L,L] additive causal mask (large negative above diagonal)."""
    m = jnp.tril(jnp.ones((length, length), bool))
    return jnp.where(m, 0.0, -1e9).astype(dtype)[None, None]


# --------------------------------------------------------------------------
# feed-forward (GEGLU, the diffusers transformer FF)
# --------------------------------------------------------------------------

def init_geglu_ff(key, dim: int, mult: int = 4):
    k1, k2 = jax.random.split(key)
    return {
        "proj": init_linear(k1, dim, dim * mult * 2),
        "out": init_linear(k2, dim * mult, dim),
    }


def geglu_ff(p, x):
    h = linear(p["proj"], x)
    a, g = jnp.split(h, 2, axis=-1)
    return linear(p["out"], a * gelu(g))
