"""MMDiT — the rectified-flow transformer denoiser of Stable Diffusion 3.

TPU-native counterpart of diffusers' ``SD3Transformer2DModel`` (Esser et
al., "Scaling Rectified Flow Transformers", arXiv:2403.03206), as
``StreamModels.unet`` drives any denoiser: ``apply_mmdit(params, x, t,
context, cfg, added_cond)`` maps a latent ``x`` [B,h,w,16] at the float
timestep ``t`` = 1000 * sigma to the velocity ``v`` [B,h,w,16].

* Patch embedding: a 2x2 stride-2 convolution to ``width`` channels, the
  tokens in row-major order, plus a fixed 2-D sin-cos position embedding
  cropped at the centre of a ``pos_embed_max_size`` grid.
* Conditioning: ``temb`` = MLP(sinusoid_256(t)) + MLP(pooled text [2048]);
  the text tokens [154, 4096] through ``context_embedder``.
* ``num_layers`` joint blocks: per stream (image x, text c) an AdaLN-zero
  modulation ``Linear(SiLU(temb)) -> (shift, scale, gate) x 2``, LayerNorm
  without affine (eps 1e-6); q/k/v of both streams attend as one joint
  sequence, image first (``joint_attention`` under ``attn_impl="pallas"``),
  the output split back; gated residuals; a 4x GELU-tanh feed-forward per
  stream.  The last block's text stream is "pre-only": AdaLN-continuous,
  keys and values alone (its queries' outputs would be discarded).
* Output: AdaLN-continuous, ``Linear(width -> p*p*16)``, unpatchify.

Every linear carries a bias.  Named scopes (``mmdit`` > ``patch_embed``,
``time_text_embed``, ``block_i`` > ``adaln`` / ``joint_attn`` / ``ff`` /
``context_ff``, ``norm_out``) let a profiler trace say where a step's time
goes.  Random init here (fan-in normal, as ``layers.init_linear``); served
weights come from the registry or the benchmark's seed.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from .layers import (
    conv2d,
    gelu_tanh_ff,
    init_conv,
    init_linear,
    linear,
    modulated_norm,
    silu,
    timestep_embedding,
)

TIME_PROJ_DIM = 256  # the timestep sinusoid's width (diffusers' Timesteps(256))


@dataclass(frozen=True)
class MMDiTConfig:
    sample_size: int = 128          # latent side the position grid is based on
    patch_size: int = 2
    in_channels: int = 16
    out_channels: int = 16
    num_layers: int = 24
    heads: int = 24
    head_dim: int = 64
    joint_attention_dim: int = 4096  # the text tokens' width
    caption_projection_dim: int = 1536
    pooled_projection_dim: int = 2048
    pos_embed_max_size: int = 192

    @property
    def width(self) -> int:
        return self.heads * self.head_dim

    @staticmethod
    def sd3_medium() -> "MMDiTConfig":
        """stabilityai/stable-diffusion-3-medium-diffusers,
        ``transformer/config.json``."""
        return MMDiTConfig()

    @staticmethod
    def tiny() -> "MMDiTConfig":
        """Hermetic CPU-test size: a joint block and the pre-only last one."""
        return MMDiTConfig(
            sample_size=16, num_layers=2, heads=2, head_dim=16,
            joint_attention_dim=64, caption_projection_dim=32,
            pooled_projection_dim=32, pos_embed_max_size=12,
        )


def _init_mlp(key, d_in: int, d: int):
    k1, k2 = jax.random.split(key)
    return {"linear_1": init_linear(k1, d_in, d), "linear_2": init_linear(k2, d, d)}


def _init_block(key, cfg: MMDiTConfig, last: bool):
    w = cfg.width
    ks = iter(jax.random.split(key, 14))
    attn = {
        name: init_linear(next(ks), w, w)
        for name in ("to_q", "to_k", "to_v", "to_out", "add_q_proj", "add_k_proj", "add_v_proj")
    }
    ff = lambda k: {  # noqa: E731
        "proj": init_linear(jax.random.fold_in(k, 0), w, 4 * w),
        "out": init_linear(jax.random.fold_in(k, 1), 4 * w, w),
    }
    p = {
        "norm1": {"linear": init_linear(next(ks), w, 6 * w)},
        "norm1_context": {"linear": init_linear(next(ks), w, (2 if last else 6) * w)},
        "attn": attn,
        "ff": ff(next(ks)),
    }
    if not last:
        attn["to_add_out"] = init_linear(next(ks), w, w)
        p["ff_context"] = ff(next(ks))
    return p


def init_mmdit(key, cfg: MMDiTConfig):
    w, pch = cfg.width, cfg.patch_size
    ks = jax.random.split(key, 6 + cfg.num_layers)
    return {
        "pos_embed": {"proj": init_conv(ks[0], cfg.in_channels, w, pch)},
        "time_text_embed": {
            "timestep_embedder": _init_mlp(ks[1], TIME_PROJ_DIM, w),
            "text_embedder": _init_mlp(ks[2], cfg.pooled_projection_dim, w),
        },
        "context_embedder": init_linear(ks[3], cfg.joint_attention_dim, cfg.caption_projection_dim),
        "blocks": [
            _init_block(ks[6 + i], cfg, last=i == cfg.num_layers - 1)
            for i in range(cfg.num_layers)
        ],
        "norm_out": {"linear": init_linear(ks[4], w, 2 * w)},
        "proj_out": init_linear(ks[5], w, pch * pch * cfg.out_channels),
    }


def _sincos_1d(dim: int, pos: np.ndarray) -> np.ndarray:
    omega = 1.0 / 10000 ** (np.arange(dim // 2, dtype=np.float64) / (dim / 2.0))
    out = np.outer(pos, omega)
    return np.concatenate([np.sin(out), np.cos(out)], axis=1)


def pos_embed(cfg: MMDiTConfig, gh: int, gw: int) -> np.ndarray:
    """[gh*gw, width] float32: the 2-D sin-cos embedding of the
    ``pos_embed_max_size`` grid (coordinates in units of ``sample_size /
    patch_size`` cells, as diffusers' ``get_2d_sincos_pos_embed``: the
    first half of the width encodes the column, the second the row),
    cropped at its centre to the token grid."""
    m, base = cfg.pos_embed_max_size, cfg.sample_size // cfg.patch_size
    if gh > m or gw > m:
        raise ValueError(f"token grid {gh}x{gw} exceeds pos_embed_max_size {m}")
    coord = np.arange(m, dtype=np.float64) / (m / base)
    top, left = (m - gh) // 2, (m - gw) // 2
    rows, cols = coord[top:top + gh], coord[left:left + gw]
    half = cfg.width // 2
    emb = np.concatenate(
        [_sincos_1d(half, np.tile(cols, gh)), _sincos_1d(half, np.repeat(rows, gw))],
        axis=1,
    )
    return emb.astype(np.float32)


def _attend(q, k, v, heads: int, attn_impl: str):
    """[B, Lq, D] x [B, Lk, D] -> [B, Lq, D], softmax(q k^T / sqrt(d)) v by head."""
    b, lq, inner = q.shape
    d = inner // heads
    q, k, v = (a.reshape(b, a.shape[1], heads, d) for a in (q, k, v))
    if attn_impl == "pallas":
        from ..ops.pallas.attention import joint_attention

        o = joint_attention(q, k, v)
    else:
        logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) / np.sqrt(d)
        w = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
        o = jnp.einsum("bhqk,bkhd->bqhd", w, v)
    return o.reshape(b, lq, inner)


def _joint_block(p, x, c, temb, heads: int, attn_impl: str, last: bool):
    e = silu(temb)[:, None, :]
    with jax.named_scope("adaln"):
        sh1, sc1, g1, sh2, sc2, g2 = jnp.split(linear(p["norm1"]["linear"], e), 6, axis=-1)
        xn = modulated_norm(x, sh1, sc1)
        mc = linear(p["norm1_context"]["linear"], e)
        if last:  # AdaLN-continuous: scale first, then shift
            csc1, csh1 = jnp.split(mc, 2, axis=-1)
        else:
            csh1, csc1, cg1, csh2, csc2, cg2 = jnp.split(mc, 6, axis=-1)
        cn = modulated_norm(c, csh1, csc1)
    lx = x.shape[1]
    with jax.named_scope("joint_attn"):
        a = p["attn"]
        q = linear(a["to_q"], xn)
        if not last:
            q = jnp.concatenate([q, linear(a["add_q_proj"], cn)], axis=1)
        k = jnp.concatenate([linear(a["to_k"], xn), linear(a["add_k_proj"], cn)], axis=1)
        v = jnp.concatenate([linear(a["to_v"], xn), linear(a["add_v_proj"], cn)], axis=1)
        o = _attend(q, k, v, heads, attn_impl)
        x = x + g1 * linear(a["to_out"], o[:, :lx])
        if not last:
            c = c + cg1 * linear(a["to_add_out"], o[:, lx:])
    with jax.named_scope("ff"):
        x = x + g2 * gelu_tanh_ff(p["ff"], modulated_norm(x, sh2, sc2))
    if not last:
        with jax.named_scope("context_ff"):
            c = c + cg2 * gelu_tanh_ff(p["ff_context"], modulated_norm(c, csh2, csc2))
    return x, c


def apply_mmdit(p, x, timesteps, context, cfg: MMDiTConfig, added_cond=None,
                attn_impl: str = "xla"):
    """x [B,h,w,in] at ``timesteps`` [B] (float 1000*sigma), context
    [B,L,joint_attention_dim], ``added_cond["text_embeds"]`` the pooled text
    [B,pooled_projection_dim] -> the velocity [B,h,w,out]."""
    dt = x.dtype
    n, h, w, _ = x.shape
    pch = cfg.patch_size
    gh, gw = h // pch, w // pch
    with jax.named_scope("mmdit"):
        with jax.named_scope("patch_embed"):
            z = conv2d(p["pos_embed"]["proj"], x, stride=pch, padding="VALID")
            z = z.reshape(n, gh * gw, cfg.width) + jnp.asarray(pos_embed(cfg, gh, gw), dt)
        with jax.named_scope("time_text_embed"):
            tt = p["time_text_embed"]
            t = timestep_embedding(timesteps, TIME_PROJ_DIM, dtype=dt)
            te, xe = tt["timestep_embedder"], tt["text_embedder"]
            temb = linear(te["linear_2"], silu(linear(te["linear_1"], t)))
            pooled = added_cond["text_embeds"].astype(dt)
            temb = temb + linear(xe["linear_2"], silu(linear(xe["linear_1"], pooled)))
            c = linear(p["context_embedder"], context.astype(dt))
        blocks = p["blocks"]
        for i, blk in enumerate(blocks):
            with jax.named_scope(f"block_{i}"):
                z, c = _joint_block(
                    blk, z, c, temb, cfg.heads, attn_impl, last=i == len(blocks) - 1
                )
        with jax.named_scope("norm_out"):
            scale, shift = jnp.split(
                linear(p["norm_out"]["linear"], silu(temb)[:, None, :]), 2, axis=-1
            )
            z = linear(p["proj_out"], modulated_norm(z, shift, scale))
            z = z.reshape(n, gh, gw, pch, pch, cfg.out_channels)
            z = z.transpose(0, 1, 3, 2, 4, 5).reshape(n, h, w, cfg.out_channels)
    return z

