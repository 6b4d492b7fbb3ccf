"""FLUX — the double- and single-stream rectified-flow transformer of
Black Forest Labs' FLUX.1 models.

TPU-native counterpart of ``flux.model.Flux`` (github.com/black-forest-labs/
flux, ``src/flux/model.py``, ``modules/layers.py``, ``math.py``), as
``StreamModels.unet`` drives any denoiser: ``apply_flux(params, x, t,
context, cfg, added_cond)`` maps a latent ``x`` [B,h,w,16] at the float
timestep ``t`` = 1000 * sigma to the velocity ``v`` [B,h,w,16].

* Input: the latent packed 2x2 into tokens (``c (h 2) (w 2) -> (h w)
  (c 2 2)``), ``img_in`` to ``width``; the text tokens (T5's states
  [L, 4096]) through ``txt_in``; ``vec`` = MLP(sinusoid_256(t)) + MLP(pooled
  CLIP text [768]), each MLP Linear, SiLU, Linear.
* Rotary positions on three axes (``axes_dims_rope``: 16 / 56 / 56 of a
  head of 128): an image token is at (0, row, column), a text token at
  (0, 0, 0); each axis rotates adjacent pairs of its slice of the head by
  ``pos * theta ** (-2j / d_axis)``.  Text tokens and axis 0 stay as they
  are.
* ``num_layers`` double-stream blocks: per stream an AdaLN-zero modulation
  ``Linear(SiLU(vec)) -> (shift, scale, gate) x 2``, LayerNorm without
  affine (eps 1e-6), a fused q/k/v projection, q and k RMS-normed per head
  (learned scale, float32 statistics); both streams attend as one joint
  sequence, text first, rotated (``rope_attention`` under
  ``attn_impl="pallas"``); gated residuals; a 4x GELU-tanh MLP per stream.
* ``num_single_layers`` single-stream blocks on the joint sequence: one
  modulation, ``linear1`` giving q/k/v and the MLP's input side by side,
  attention and the MLP in parallel, ``linear2`` over both, one gated
  residual.
* Output: the image tokens, an AdaLN (shift, then scale) and
  ``Linear(width -> 64)``, unpacked.

Every linear carries a bias.  Named scopes (``flux`` > ``embed``,
``double_i`` > ``adaln`` / ``rope_attn`` / ``ff`` / ``context_ff``,
``single_i`` > ``adaln`` / ``linear1`` / ``rope_attn`` / ``linear2``,
``norm_out``) let a profiler trace say where a step's time goes.  Random
init here (fan-in normal, as ``layers.init_linear``); served weights come
from the registry or the benchmark's seed.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from .layers import (
    _kernel,
    gelu_tanh_ff,
    init_linear,
    linear,
    modulated_norm,
    silu,
    timestep_embedding,
)

TIME_PROJ_DIM = 256  # the timestep sinusoid's width
RMS_EPS = 1e-6


@dataclass(frozen=True)
class FluxConfig:
    in_channels: int = 64            # a 16-channel latent packed 2x2
    num_layers: int = 19             # double-stream blocks
    num_single_layers: int = 38
    heads: int = 24
    head_dim: int = 128
    mlp_ratio: float = 4.0
    joint_attention_dim: int = 4096  # the text tokens' width (T5-XXL)
    pooled_projection_dim: int = 768  # CLIP-L's pooled output
    axes_dims_rope: tuple = (16, 56, 56)
    theta: int = 10000
    text_tokens: int = 256           # T5's max_sequence_length for schnell

    @property
    def width(self) -> int:
        return self.heads * self.head_dim

    @property
    def mlp_width(self) -> int:
        return int(self.width * self.mlp_ratio)

    @staticmethod
    def schnell() -> "FluxConfig":
        """black-forest-labs/FLUX.1-schnell, ``transformer/config.json``:
        11.9e9 parameters, 23.8 GB in bf16, more than one chip holds."""
        return FluxConfig()

    @staticmethod
    def schnell_stage() -> "FluxConfig":
        """What one chip holds of FLUX.1-schnell served as a four-stage
        pipeline: 5 of the 19 double and 10 of the 38 single blocks (the
        published 1:2 ratio, in their order) and the embedders and output
        layer, 3.17e9 parameters; every width as published."""
        return dataclasses.replace(FluxConfig.schnell(), num_layers=5, num_single_layers=10)

    @staticmethod
    def tiny() -> "FluxConfig":
        """Hermetic CPU-test size: one double and two single blocks."""
        return FluxConfig(
            num_layers=1, num_single_layers=2, heads=2, head_dim=16,
            joint_attention_dim=64, pooled_projection_dim=32,
            axes_dims_rope=(4, 6, 6), text_tokens=8,
        )


def _init_mlp(key, d_in: int, d: int):
    k1, k2 = jax.random.split(key)
    return {"in_layer": init_linear(k1, d_in, d), "out_layer": init_linear(k2, d, d)}


def _init_qk_norm(cfg: FluxConfig):
    return {n: {"scale": jnp.ones((cfg.head_dim,), jnp.float32)}
            for n in ("query_norm", "key_norm")}


def _init_double(key, cfg: FluxConfig):
    w, m = cfg.width, cfg.mlp_width
    ks = iter(jax.random.split(key, 8))
    p = {}
    for s in ("img", "txt"):
        p[f"{s}_mod"] = {"lin": init_linear(next(ks), w, 6 * w)}
        p[f"{s}_attn"] = {
            "qkv": init_linear(next(ks), w, 3 * w),
            "norm": _init_qk_norm(cfg),
            "proj": init_linear(next(ks), w, w),
        }
        k = next(ks)
        p[f"{s}_mlp"] = {
            "proj": init_linear(jax.random.fold_in(k, 0), w, m),
            "out": init_linear(jax.random.fold_in(k, 1), m, w),
        }
    return p


def _init_single(key, cfg: FluxConfig):
    w, m = cfg.width, cfg.mlp_width
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "modulation": {"lin": init_linear(k1, w, 3 * w)},
        "linear1": init_linear(k2, w, 3 * w + m),
        "norm": _init_qk_norm(cfg),
        "linear2": init_linear(k3, w + m, w),
    }


def init_flux(key, cfg: FluxConfig):
    w = cfg.width
    ks = jax.random.split(key, 6 + cfg.num_layers + cfg.num_single_layers)
    blocks = ks[6:]
    return {
        "img_in": init_linear(ks[0], cfg.in_channels, w),
        "txt_in": init_linear(ks[1], cfg.joint_attention_dim, w),
        "time_in": _init_mlp(ks[2], TIME_PROJ_DIM, w),
        "vector_in": _init_mlp(ks[3], cfg.pooled_projection_dim, w),
        "double_blocks": [_init_double(k, cfg) for k in blocks[: cfg.num_layers]],
        "single_blocks": [_init_single(k, cfg) for k in blocks[cfg.num_layers:]],
        "final_layer": {
            "adaLN_modulation": init_linear(ks[4], w, 2 * w),
            "linear": init_linear(ks[5], w, cfg.in_channels),
        },
    }


# -- positions ------------------------------------------------------------------

def rope_tables(cfg: FluxConfig, gh: int, gw: int, text_tokens: int):
    """(cos, sin) [text_tokens + gh*gw, head_dim/2] float32: each joint
    token's angle for each adjacent pair of a head.  Positions as BFL's
    ``prepare``: text tokens at (0, 0, 0), the image token of row r and
    column c at (0, r, c); axis i holds ``axes_dims_rope[i] / 2`` pairs of
    the head, in order, pair j at frequency ``theta ** (-2j / d_i)``
    (``flux.math.rope``, ``EmbedND``)."""
    rows, cols = np.meshgrid(np.arange(gh), np.arange(gw), indexing="ij")
    ids = np.zeros((text_tokens + gh * gw, 3), np.float64)
    ids[text_tokens:, 1] = rows.reshape(-1)
    ids[text_tokens:, 2] = cols.reshape(-1)
    angles = np.concatenate(
        [
            np.outer(ids[:, i], 1.0 / cfg.theta ** (np.arange(0, d, 2, dtype=np.float64) / d))
            for i, d in enumerate(cfg.axes_dims_rope)
        ],
        axis=1,
    )
    return np.cos(angles).astype(np.float32), np.sin(angles).astype(np.float32)


def apply_rope(x, cos, sin):
    """x [B, L, H, D]: each adjacent pair (2j, 2j+1) of a head rotated by its
    token's angle, (a, b) -> (a cos - b sin, a sin + b cos), in float32 and
    back in ``x``'s dtype (``flux.math.apply_rope``)."""
    xf = x.astype(jnp.float32).reshape(*x.shape[:-1], -1, 2)
    a, b = xf[..., 0], xf[..., 1]
    c, s = (jnp.asarray(t)[None, :, None, :] for t in (cos, sin))
    return jnp.stack([a * c - b * s, a * s + b * c], axis=-1).reshape(x.shape).astype(x.dtype)


# -- blocks ---------------------------------------------------------------------

def _rms_norm(x, scale):
    """RMSNorm over a head (float32 statistics, eps 1e-6, the learned scale
    applied in ``x``'s dtype, as ``flux.modules.layers.RMSNorm``)."""
    xf = x.astype(jnp.float32)
    y = (xf * jax.lax.rsqrt(jnp.mean(jnp.square(xf), -1, keepdims=True) + RMS_EPS)).astype(x.dtype)
    return y * scale.astype(x.dtype)


def _scales(*norms):
    """(q, k) scales [2, D] of the joint sequence's two parts, text then
    image, from each part's ``QKNorm`` (one norm for both parts in a single
    block)."""
    if len(norms) == 1:
        norms = norms * 2
    return tuple(jnp.stack([n[key]["scale"] for n in norms]) for key in ("query_norm", "key_norm"))


def _attend(qkv, scales, rope, heads: int, text_tokens: int, attn_impl: str):
    """``qkv`` [B, L, >= 3*H*D] of the joint sequence (q, k, v side by side,
    then whatever follows them) -> [B, L, H*D]: q and k RMS-normed by head
    (the first ``text_tokens`` with row 0 of ``scales``, the rest with row
    1), rotated, softmax(q k^T / sqrt(D)) v by head."""
    (q_scale, k_scale), (cos, sin) = scales, rope
    if attn_impl == "pallas":
        from ..ops.pallas.attention import rope_attention

        return rope_attention(qkv, cos, sin, q_scale, k_scale, heads, text_tokens)
    b, l, _ = qkv.shape
    d = q_scale.shape[-1]
    q, k, v = (qkv[..., i * heads * d:(i + 1) * heads * d].reshape(b, l, heads, d)
               for i in range(3))
    text = (jnp.arange(l) < text_tokens)[None, :, None, None]
    q, k = (apply_rope(_rms_norm(x, jnp.where(text, s[0], s[1])), cos, sin)
            for x, s in ((q, q_scale), (k, k_scale)))
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) / np.sqrt(d)
    w = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", w, v).reshape(b, l, heads * d)


def _double_block(p, img, txt, vec, rope, heads: int, attn_impl: str):
    e = silu(vec)[:, None, :]
    with jax.named_scope("adaln"):
        ish1, isc1, ig1, ish2, isc2, ig2 = jnp.split(linear(p["img_mod"]["lin"], e), 6, axis=-1)
        tsh1, tsc1, tg1, tsh2, tsc2, tg2 = jnp.split(linear(p["txt_mod"]["lin"], e), 6, axis=-1)
        xi = modulated_norm(img, ish1, isc1)
        xt = modulated_norm(txt, tsh1, tsc1)
    lt = txt.shape[1]
    with jax.named_scope("rope_attn"):
        ia, ta = p["img_attn"], p["txt_attn"]
        qkv = jnp.concatenate([linear(ta["qkv"], xt), linear(ia["qkv"], xi)], axis=1)
        o = _attend(qkv, _scales(ta["norm"], ia["norm"]), rope, heads, lt, attn_impl)
        img = img + ig1 * linear(ia["proj"], o[:, lt:])
        txt = txt + tg1 * linear(ta["proj"], o[:, :lt])
    with jax.named_scope("ff"):
        img = img + ig2 * gelu_tanh_ff(p["img_mlp"], modulated_norm(img, ish2, isc2))
    with jax.named_scope("context_ff"):
        txt = txt + tg2 * gelu_tanh_ff(p["txt_mlp"], modulated_norm(txt, tsh2, tsc2))
    return img, txt


def _single_block(p, h, vec, rope, heads: int, attn_impl: str):
    with jax.named_scope("adaln"):
        sh, sc, g = jnp.split(linear(p["modulation"]["lin"], silu(vec)[:, None, :]), 3, axis=-1)
        hn = modulated_norm(h, sh, sc)
    with jax.named_scope("linear1"):
        y = linear(p["linear1"], hn)  # q, k, v, then the MLP's input
    with jax.named_scope("rope_attn"):
        o = _attend(y, _scales(p["norm"]), rope, heads, 0, attn_impl)
    with jax.named_scope("linear2"):
        width = h.shape[-1]
        m = jax.nn.gelu(y[..., 3 * width:], approximate=True)
        # linear2 over [attention ; MLP] as two products summed: the
        # concatenation is never written
        w2 = _kernel(p["linear2"], h.dtype)
        out = o @ w2[:width] + m @ w2[width:] + p["linear2"]["bias"].astype(h.dtype)
    return h + g * out


def pack(x):
    """[B, h, w, C] -> [B, (h/2)(w/2), C*4], a token's features ordered
    (c, row in the cell, column in the cell)."""
    n, h, w, c = x.shape
    x = x.reshape(n, h // 2, 2, w // 2, 2, c).transpose(0, 1, 3, 5, 2, 4)
    return x.reshape(n, (h // 2) * (w // 2), 4 * c)


def unpack(z, h: int, w: int):
    """Inverse of :func:`pack`."""
    n, _, d = z.shape
    c = d // 4
    z = z.reshape(n, h // 2, w // 2, c, 2, 2).transpose(0, 1, 4, 2, 5, 3)
    return z.reshape(n, h, w, c)


def apply_flux(p, x, timesteps, context, cfg: FluxConfig, added_cond=None,
               attn_impl: str = "xla"):
    """x [B,h,w,16] at ``timesteps`` [B] (float 1000*sigma), context
    [B,L,joint_attention_dim] (T5's states), ``added_cond["text_embeds"]``
    the pooled CLIP text [B,pooled_projection_dim] -> the velocity
    [B,h,w,16]."""
    dt = x.dtype
    n, h, w, _ = x.shape
    with jax.named_scope("flux"):
        with jax.named_scope("embed"):
            img = linear(p["img_in"], pack(x))
            t = timestep_embedding(timesteps, TIME_PROJ_DIM, dtype=dt)
            ti, vi = p["time_in"], p["vector_in"]
            vec = linear(ti["out_layer"], silu(linear(ti["in_layer"], t)))
            pooled = added_cond["text_embeds"].astype(dt)
            vec = vec + linear(vi["out_layer"], silu(linear(vi["in_layer"], pooled)))
            txt = linear(p["txt_in"], context.astype(dt))
        lt = txt.shape[1]
        rope = rope_tables(cfg, h // 2, w // 2, lt)
        for i, blk in enumerate(p["double_blocks"]):
            with jax.named_scope(f"double_{i}"):
                img, txt = _double_block(blk, img, txt, vec, rope, cfg.heads, attn_impl)
        hs = jnp.concatenate([txt, img], axis=1)
        for i, blk in enumerate(p["single_blocks"]):
            with jax.named_scope(f"single_{i}"):
                hs = _single_block(blk, hs, vec, rope, cfg.heads, attn_impl)
        with jax.named_scope("norm_out"):
            fl = p["final_layer"]
            shift, scale = jnp.split(
                linear(fl["adaLN_modulation"], silu(vec)[:, None, :]), 2, axis=-1
            )
            z = linear(fl["linear"], modulated_norm(hs[:, lt:], shift, scale))
    return unpack(z, h, w)
