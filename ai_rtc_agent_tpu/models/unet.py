"""Conditional diffusion UNet — generic over SD1.5 / SD2.1(-Turbo) / SDXL.

TPU-native replacement for ``diffusers.UNet2DConditionModel`` (config-only
shells at reference lib/wrapper.py:439-466; full loads at :645-669).  One
config-driven implementation covers the whole model family the reference
serves (dreamshaper-8/SD1.5 default at reference agent.py:442, SD-Turbo flag
at lib/wrapper.py:133, SDXL via BASELINE.json configs).

TPU-first choices:
* NHWC activations + HWIO kernels (MXU-friendly; see ops/image.py).
* Static python loops over blocks — the graph is traced once and AOT-cached
  (aot/cache.py), so unrolled structure beats lax control flow here.
* fp32 normalization statistics inside bf16 graphs.
* Attention can route to the Pallas flash kernel (`attn_impl="pallas"`) for
  the long token counts of SDXL@1024 (16k latent tokens).
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from .layers import (
    attention,
    conv2d,
    geglu_ff,
    group_norm,
    init_attention,
    init_conv,
    init_geglu_ff,
    init_linear,
    init_norm,
    layer_norm,
    linear,
    silu,
    timestep_embedding,
)


@dataclass(frozen=True)
class UNetConfig:
    in_channels: int = 4
    out_channels: int = 4
    block_out_channels: tuple = (320, 640, 1280, 1280)
    layers_per_block: int = 2
    cross_attention_dim: int = 768
    num_heads_per_block: tuple = (8, 8, 8, 8)
    # which blocks carry cross-attention transformers (SD15: first 3 down)
    attn_blocks: tuple = (True, True, True, False)
    transformer_layers_per_block: tuple = (1, 1, 1, 1)
    use_linear_projection: bool = False
    norm_groups: int = 32
    # SDXL addition embedding ("text_time"): pooled text + micro-conditioning
    addition_embed_type: str | None = None
    addition_time_embed_dim: int = 0
    addition_pooled_dim: int = 0
    addition_num_time_ids: int = 6

    @property
    def temb_dim(self) -> int:
        return self.block_out_channels[0] * 4

    @staticmethod
    def sd15() -> "UNetConfig":
        return UNetConfig()

    @staticmethod
    def sd21() -> "UNetConfig":
        """SD2.1 geometry — also SD-Turbo (stabilityai/sd-turbo)."""
        return UNetConfig(
            cross_attention_dim=1024,
            num_heads_per_block=(5, 10, 20, 20),
            use_linear_projection=True,
        )

    @staticmethod
    def sdxl() -> "UNetConfig":
        """SDXL geometry — also SDXL-Turbo."""
        return UNetConfig(
            block_out_channels=(320, 640, 1280),
            cross_attention_dim=2048,
            num_heads_per_block=(5, 10, 20),
            attn_blocks=(False, True, True),
            transformer_layers_per_block=(1, 2, 10),
            use_linear_projection=True,
            addition_embed_type="text_time",
            addition_time_embed_dim=256,
            addition_pooled_dim=1280,
        )

    @staticmethod
    def tiny(cross_dim: int = 32) -> "UNetConfig":
        """CPU-testable miniature with the same topology as sd15."""
        return UNetConfig(
            block_out_channels=(8, 16),
            layers_per_block=1,
            cross_attention_dim=cross_dim,
            num_heads_per_block=(2, 2),
            attn_blocks=(True, False),
            transformer_layers_per_block=(1, 1),
            norm_groups=4,
        )

    @staticmethod
    def tiny_xl(cross_dim: int = 32) -> "UNetConfig":
        """Miniature with SDXL-style addition embeddings for tests."""
        return UNetConfig(
            block_out_channels=(8, 16),
            layers_per_block=1,
            cross_attention_dim=cross_dim,
            num_heads_per_block=(2, 2),
            attn_blocks=(False, True),
            transformer_layers_per_block=(1, 2),
            use_linear_projection=True,
            norm_groups=4,
            addition_embed_type="text_time",
            addition_time_embed_dim=8,
            addition_pooled_dim=16,
        )


# --------------------------------------------------------------------------
# init
# --------------------------------------------------------------------------

def _init_resnet(key, in_ch: int, out_ch: int, temb_dim: int):
    k1, k2, k3, k4 = jax.random.split(key, 4)
    p = {
        "norm1": init_norm(in_ch),
        "conv1": init_conv(k1, in_ch, out_ch, 3),
        "time_emb_proj": init_linear(k2, temb_dim, out_ch),
        "norm2": init_norm(out_ch),
        "conv2": init_conv(k3, out_ch, out_ch, 3, scale=0.5),
    }
    if in_ch != out_ch:
        p["conv_shortcut"] = init_conv(k4, in_ch, out_ch, 1)
    return p


def _init_transformer(key, ch: int, cfg: UNetConfig, depth: int, heads: int):
    head_dim = ch // heads
    keys = jax.random.split(key, 2 + depth)
    p = {
        "norm": init_norm(ch),
        "proj_in": (
            init_linear(keys[0], ch, ch)
            if cfg.use_linear_projection
            else init_conv(keys[0], ch, ch, 1)
        ),
        "blocks": [],
        "proj_out": (
            init_linear(keys[1], ch, ch, scale=0.2)
            if cfg.use_linear_projection
            else init_conv(keys[1], ch, ch, 1, scale=0.2)
        ),
    }
    for d in range(depth):
        k1, k2, k3 = jax.random.split(keys[2 + d], 3)
        p["blocks"].append(
            {
                "norm1": init_norm(ch),
                "attn1": init_attention(k1, ch, None, heads, head_dim),
                "norm2": init_norm(ch),
                "attn2": init_attention(k2, ch, cfg.cross_attention_dim, heads, head_dim),
                "norm3": init_norm(ch),
                "ff": init_geglu_ff(k3, ch),
            }
        )
    return p


class _KeyGen:
    """Inexhaustible PRNG key stream (split-on-demand)."""

    def __init__(self, key):
        self._key = key

    def __next__(self):
        self._key, k = jax.random.split(self._key)
        return k


def init_unet(key, cfg: UNetConfig):
    nb = len(cfg.block_out_channels)
    ki = _KeyGen(key)
    ch0 = cfg.block_out_channels[0]
    p: dict = {
        "conv_in": init_conv(next(ki), cfg.in_channels, ch0, 3),
        "time_embedding": {
            "linear_1": init_linear(next(ki), ch0, cfg.temb_dim),
            "linear_2": init_linear(next(ki), cfg.temb_dim, cfg.temb_dim),
        },
        "down_blocks": [],
        "up_blocks": [],
        "conv_norm_out": init_norm(ch0),
        "conv_out": init_conv(next(ki), ch0, cfg.out_channels, 3, scale=0.2),
    }
    if cfg.addition_embed_type == "text_time":
        in_dim = (
            cfg.addition_time_embed_dim * cfg.addition_num_time_ids
            + cfg.addition_pooled_dim
        )
        p["add_embedding"] = {
            "linear_1": init_linear(next(ki), in_dim, cfg.temb_dim),
            "linear_2": init_linear(next(ki), cfg.temb_dim, cfg.temb_dim),
        }

    # down
    out_ch = ch0
    skip_chs = [ch0]
    for i, ch in enumerate(cfg.block_out_channels):
        in_ch, out_ch = out_ch, ch
        blk = {"resnets": [], "attentions": [], "downsample": None}
        for j in range(cfg.layers_per_block):
            blk["resnets"].append(
                _init_resnet(next(ki), in_ch if j == 0 else out_ch, out_ch, cfg.temb_dim)
            )
            if cfg.attn_blocks[i]:
                blk["attentions"].append(
                    _init_transformer(
                        next(ki),
                        out_ch,
                        cfg,
                        cfg.transformer_layers_per_block[i],
                        cfg.num_heads_per_block[i],
                    )
                )
            skip_chs.append(out_ch)
        if i < nb - 1:
            blk["downsample"] = init_conv(next(ki), out_ch, out_ch, 3)
            skip_chs.append(out_ch)
        p["down_blocks"].append(blk)

    # mid (always attends in SD geometries; SDXL mid depth = last block depth)
    mid_ch = cfg.block_out_channels[-1]
    mid_heads = cfg.num_heads_per_block[-1]
    mid_depth = cfg.transformer_layers_per_block[-1]
    p["mid_block"] = {
        "resnet1": _init_resnet(next(ki), mid_ch, mid_ch, cfg.temb_dim),
        "attention": _init_transformer(next(ki), mid_ch, cfg, mid_depth, mid_heads),
        "resnet2": _init_resnet(next(ki), mid_ch, mid_ch, cfg.temb_dim),
    }

    # up (mirror of down, +1 resnet per block, skip concat)
    prev_ch = mid_ch
    for i in reversed(range(nb)):
        ch = cfg.block_out_channels[i]
        blk = {"resnets": [], "attentions": [], "upsample": None}
        for j in range(cfg.layers_per_block + 1):
            skip = skip_chs.pop()
            blk["resnets"].append(
                _init_resnet(next(ki), prev_ch + skip, ch, cfg.temb_dim)
            )
            prev_ch = ch
            if cfg.attn_blocks[i]:
                blk["attentions"].append(
                    _init_transformer(
                        next(ki),
                        ch,
                        cfg,
                        cfg.transformer_layers_per_block[i],
                        cfg.num_heads_per_block[i],
                    )
                )
        if i > 0:
            blk["upsample"] = init_conv(next(ki), ch, ch, 3)
        p["up_blocks"].append(blk)
    assert not skip_chs
    return p


# --------------------------------------------------------------------------
# apply
# --------------------------------------------------------------------------

def _resnet(p, x, temb, groups: int = 32):
    with jax.named_scope("norm"):
        h = group_norm(p["norm1"], x, groups, act="silu")
    h = conv2d(p["conv1"], h)
    h = h + linear(p["time_emb_proj"], silu(temb))[:, None, None, :]
    with jax.named_scope("norm"):
        h = group_norm(p["norm2"], h, groups, act="silu")
    h = conv2d(p["conv2"], h)
    if "conv_shortcut" in p:
        x = conv2d(p["conv_shortcut"], x)
    return x + h


def _transformer(p, x, context, cfg: UNetConfig, heads: int, attn_impl: str):
    n, h, w, c = x.shape
    residual = x
    with jax.named_scope("proj"):
        z = group_norm(p["norm"], x, cfg.norm_groups)
        if cfg.use_linear_projection:
            z = z.reshape(n, h * w, c)
            z = linear(p["proj_in"], z)
        else:
            z = conv2d(p["proj_in"], z)
            z = z.reshape(n, h * w, c)
    for blk in p["blocks"]:
        # the scopes name the model part, never the Mosaic kernel inside it
        # (its pallas_call carries its own name): the trace readers find a
        # kernel by that substring of an op's name
        with jax.named_scope("self_attn"):
            z = z + attention(blk["attn1"], layer_norm(blk["norm1"], z), None, heads, attn_impl=attn_impl)
        with jax.named_scope("cross_attn"):
            z = z + attention(blk["attn2"], layer_norm(blk["norm2"], z), context, heads, attn_impl=attn_impl)
        with jax.named_scope("ff"):
            z = z + geglu_ff(blk["ff"], layer_norm(blk["norm3"], z))
    with jax.named_scope("proj"):
        if cfg.use_linear_projection:
            z = linear(p["proj_out"], z)
            z = z.reshape(n, h, w, c)
        else:
            z = z.reshape(n, h, w, c)
            z = conv2d(p["proj_out"], z)
        return z + residual


def _upsample2x(x):
    n, h, w, c = x.shape
    x = x[:, :, None, :, None, :]
    x = jnp.broadcast_to(x, (n, h, 2, w, 2, c))
    return x.reshape(n, h * 2, w * 2, c)


def time_cond_embedding(p, cfg: UNetConfig, timesteps, added_cond=None, dtype=jnp.float32):
    """Timestep (+ SDXL text_time addition) embedding -> [B, temb_dim]."""
    ch0 = cfg.block_out_channels[0]
    temb = timestep_embedding(timesteps, ch0, dtype=dtype)
    te = p["time_embedding"]
    temb = linear(te["linear_2"], silu(linear(te["linear_1"], temb)))
    if cfg.addition_embed_type == "text_time":
        if added_cond is None:
            raise ValueError("SDXL-style config requires added_cond")
        time_ids = added_cond["time_ids"]  # [B, num_time_ids]
        pooled = added_cond["text_embeds"]  # [B, pooled_dim]
        b = time_ids.shape[0]
        with jax.named_scope("add_embedding"):
            tid = timestep_embedding(
                time_ids.reshape(-1), cfg.addition_time_embed_dim, dtype=dtype
            ).reshape(b, -1)
            add = jnp.concatenate([pooled.astype(dtype), tid], axis=-1)
            ae = p["add_embedding"]
            temb = temb + linear(
                ae["linear_2"], silu(linear(ae["linear_1"], add))
            )
    return temb


def apply_unet(
    p,
    x,
    timesteps,
    context,
    cfg: UNetConfig,
    added_cond=None,
    down_residuals=None,
    mid_residual=None,
    attn_impl: str = "xla",
    deep_cache: str = "off",
    cached_h=None,
):
    """x [B,h,w,Cin], timesteps [B], context [B,L,cross_dim] -> [B,h,w,Cout].

    ``down_residuals`` / ``mid_residual`` are ControlNet residual additions
    (reference's ControlNet path, lib/wrapper.py:617-643) matching the skip
    stack layout produced here.

    ``deep_cache`` (DeepCache-style temporal feature reuse — a TPU-friendly
    static-cadence variant: two fixed graphs instead of data-dependent
    control flow):
      - "off":      plain forward.
      - "capture":  plain forward that ALSO returns the feature map entering
                    the outermost up block -> (out, deep_h).
      - "use":      recompute only the outermost tier (conv_in + first down
                    block + last up block) and splice ``cached_h`` in for
                    the deep remainder.  With identical inputs and a cache
                    captured from them, output equals the full pass exactly
                    (the deep recompute is the only thing skipped) — the
                    wiring invariant the tests pin.
    """
    nb = len(cfg.block_out_channels)
    with jax.named_scope("time_embed"):
        temb = time_cond_embedding(p, cfg, timesteps, added_cond, dtype=x.dtype)
    context = context.astype(x.dtype)

    def resnet(j, rn, h):
        with jax.named_scope(f"resnet_{j}"):
            return _resnet(rn, h, temb, cfg.norm_groups)

    def transformer(j, tp, h, heads):
        with jax.named_scope(f"transformer_{j}"):
            return _transformer(tp, h, context, cfg, heads, attn_impl)

    def conv_in(x):
        with jax.named_scope("conv_in"):
            return conv2d(p["conv_in"], x)

    def conv_out(h):
        with jax.named_scope("conv_out"):
            with jax.named_scope("norm"):
                h = group_norm(p["conv_norm_out"], h, cfg.norm_groups, act="silu")
            return conv2d(p["conv_out"], h)

    def up_resnet(j, rn, h, skip):
        with jax.named_scope(f"resnet_{j}"):  # the skip concat is its input
            h = jnp.concatenate([h, skip], axis=-1)
            return _resnet(rn, h, temb, cfg.norm_groups)

    if deep_cache == "use":
        if down_residuals is not None or mid_residual is not None:
            raise ValueError(
                "deep_cache='use' is incompatible with ControlNet residuals "
                "(they feed the skipped deep blocks)"
            )
        if cached_h is None:
            raise ValueError("deep_cache='use' requires cached_h")
        h = conv_in(x)
        skips = [h]
        blk0 = p["down_blocks"][0]
        with jax.named_scope("down_0"):
            for j, rn in enumerate(blk0["resnets"]):
                h = resnet(j, rn, h)
                if blk0["attentions"]:
                    h = transformer(
                        j, blk0["attentions"][j], h, cfg.num_heads_per_block[0]
                    )
                skips.append(h)
        blk = p["up_blocks"][-1]
        if len(blk["resnets"]) != len(skips):
            raise ValueError(
                f"deep-cache skip mismatch: outermost up block wants "
                f"{len(blk['resnets'])} skips, shallow pass made {len(skips)}"
            )
        h = cached_h.astype(x.dtype)
        with jax.named_scope(f"up_{len(p['up_blocks']) - 1}"):
            for j, rn in enumerate(blk["resnets"]):
                h = up_resnet(j, rn, h, skips.pop())
                if blk["attentions"]:
                    h = transformer(
                        j, blk["attentions"][j], h, cfg.num_heads_per_block[0]
                    )
        return conv_out(h)

    h = conv_in(x)
    skips = [h]
    for i, blk in enumerate(p["down_blocks"]):
        with jax.named_scope(f"down_{i}"):
            for j, rn in enumerate(blk["resnets"]):
                h = resnet(j, rn, h)
                if blk["attentions"]:
                    h = transformer(
                        j, blk["attentions"][j], h, cfg.num_heads_per_block[i]
                    )
                skips.append(h)
            if blk["downsample"] is not None:
                with jax.named_scope("downsample"):
                    h = conv2d(blk["downsample"], h, stride=2, padding=1)
                skips.append(h)

    if down_residuals is not None:
        if len(down_residuals) != len(skips):
            raise ValueError(
                f"expected {len(skips)} down residuals, got {len(down_residuals)}"
            )
        skips = [s + r.astype(s.dtype) for s, r in zip(skips, down_residuals)]

    mb = p["mid_block"]
    with jax.named_scope("mid"):
        h = resnet(0, mb["resnet1"], h)
        h = transformer(0, mb["attention"], h, cfg.num_heads_per_block[-1])
        h = resnet(1, mb["resnet2"], h)
    if mid_residual is not None:
        h = h + mid_residual.astype(h.dtype)

    deep_h = None
    for k, blk in enumerate(p["up_blocks"]):
        i = nb - 1 - k
        if k == len(p["up_blocks"]) - 1 and deep_cache == "capture":
            deep_h = h  # the feature the "use" pass splices back in
        with jax.named_scope(f"up_{k}"):
            for j, rn in enumerate(blk["resnets"]):
                h = up_resnet(j, rn, h, skips.pop())
                if blk["attentions"]:
                    h = transformer(
                        j, blk["attentions"][j], h, cfg.num_heads_per_block[i]
                    )
            if blk["upsample"] is not None:
                with jax.named_scope("upsample"):
                    h = _upsample2x(h)
                    h = conv2d(blk["upsample"], h)

    h = conv_out(h)
    if deep_cache == "capture":
        return h, deep_h
    return h
