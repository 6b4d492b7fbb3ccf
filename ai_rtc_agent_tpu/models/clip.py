"""CLIP text encoders — the prompt-embedding models of the pipeline.

TPU-native replacement for ``transformers.CLIPTextModel`` /
``CLIPTextModelWithProjection`` which the reference loads to GPU at
lib/wrapper.py:468-473 (and whose embeddings the stream caches so prompt
updates are embedding swaps, not recompiles — reference lib/pipeline.py:44-45).

Supported presets:
  SD15   OpenAI ViT-L/14 text tower: 12 layers, d=768, quick_gelu,
         final-layer hidden states.
  SD21   OpenCLIP ViT-H text tower: 23 of 24 layers (penultimate), d=1024,
         gelu.  (SD-Turbo shares this tower.)
  SDXL   dual tower: ViT-L (penultimate) concat OpenCLIP ViT-bigG
         (penultimate, d=1280) -> 2048-dim context; bigG also yields the
         pooled projection for the addition embedding.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from .layers import (
    ACTIVATIONS,
    causal_mask,
    init_linear,
    init_norm,
    layer_norm,
    linear,
)


@dataclass(frozen=True)
class CLIPTextConfig:
    vocab_size: int = 49408
    max_length: int = 77
    width: int = 768
    layers: int = 12
    heads: int = 12
    activation: str = "quick_gelu"
    # how many final layers to SKIP (0 = use last hidden state; 1 = the
    # "penultimate layer" convention of SD2.x / SDXL towers)
    clip_skip: int = 0
    use_text_projection: bool = False
    projection_dim: int = 0

    @staticmethod
    def sd15() -> "CLIPTextConfig":
        return CLIPTextConfig()

    @staticmethod
    def sd21() -> "CLIPTextConfig":
        return CLIPTextConfig(width=1024, layers=24, heads=16, activation="gelu", clip_skip=1)

    @staticmethod
    def sdxl_l() -> "CLIPTextConfig":
        """SDXL's first tower: the SD1.5 ViT-L, read at the penultimate
        layer (``StableDiffusionXLPipeline.encode_prompt`` takes
        ``hidden_states[-2]`` of both towers)."""
        return CLIPTextConfig(clip_skip=1)

    @staticmethod
    def sdxl_g() -> "CLIPTextConfig":
        return CLIPTextConfig(
            width=1280,
            layers=32,
            heads=20,
            activation="gelu",
            clip_skip=1,
            use_text_projection=True,
            projection_dim=1280,
        )

    @staticmethod
    def tiny() -> "CLIPTextConfig":
        return CLIPTextConfig(vocab_size=256, max_length=16, width=32, layers=2, heads=4)

    @staticmethod
    def tiny_dual() -> "CLIPTextConfig":
        """First tower of the hermetic SDXL-style tiny family (widths
        halve so the two towers concatenate to tiny_xl's cross dim)."""
        return CLIPTextConfig(vocab_size=256, max_length=16, width=16, layers=2, heads=2)

    @staticmethod
    def tiny_g() -> "CLIPTextConfig":
        """Second (projected) tower of the tiny SDXL-style family — the
        OpenCLIP-G analog providing hidden states + pooled projection."""
        return CLIPTextConfig(
            vocab_size=256, max_length=16, width=16, layers=2, heads=2,
            use_text_projection=True, projection_dim=16,
        )


def init_clip_text(key, cfg: CLIPTextConfig):
    keys = jax.random.split(key, 4 + cfg.layers)
    p = {
        "token_embedding": jax.random.normal(keys[0], (cfg.vocab_size, cfg.width)) * 0.02,
        "position_embedding": jax.random.normal(keys[1], (cfg.max_length, cfg.width)) * 0.01,
        "final_norm": init_norm(cfg.width),
        "layers": [],
    }
    head_dim = cfg.width // cfg.heads
    for i in range(cfg.layers):
        k1, k2, k3, k4, k5, k6 = jax.random.split(keys[3 + i], 6)
        p["layers"].append(
            {
                "ln1": init_norm(cfg.width),
                "q": init_linear(k1, cfg.width, cfg.width),
                "k": init_linear(k2, cfg.width, cfg.width),
                "v": init_linear(k3, cfg.width, cfg.width),
                "out": init_linear(k4, cfg.width, cfg.width),
                "ln2": init_norm(cfg.width),
                "fc1": init_linear(k5, cfg.width, cfg.width * 4),
                "fc2": init_linear(k6, cfg.width * 4, cfg.width),
            }
        )
    if cfg.use_text_projection:
        p["text_projection"] = init_linear(keys[2], cfg.width, cfg.projection_dim, bias=False)
    del head_dim
    return p


def _attn(layer, x, mask, heads: int):
    b, l, d = x.shape
    hd = d // heads
    q = linear(layer["q"], x).reshape(b, l, heads, hd)
    k = linear(layer["k"], x).reshape(b, l, heads, hd)
    v = linear(layer["v"], x).reshape(b, l, heads, hd)
    scale = hd**-0.5
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    logits = logits + mask
    w = jax.nn.softmax(logits, axis=-1).astype(x.dtype)
    o = jnp.einsum("bhqk,bkhd->bqhd", w, v).reshape(b, l, d)
    return linear(layer["out"], o)


def apply_clip_text(
    p,
    token_ids,
    cfg: CLIPTextConfig,
    dtype=jnp.float32,
):
    """token_ids [B, L] int32 -> dict with:
       hidden    [B, L, width]  (clip_skip-adjusted, final-norm applied only
                                 when clip_skip == 0, matching HF semantics)
       pooled    [B, width]     EOT-token hidden state after final_norm
       projected [B, proj_dim]  only when use_text_projection
    """
    b, l = token_ids.shape
    x = p["token_embedding"][token_ids].astype(dtype)
    x = x + p["position_embedding"][:l].astype(dtype)
    mask = causal_mask(l)
    hiddens = [x]
    for layer in p["layers"]:
        h = layer_norm(layer["ln1"], x)
        x = x + _attn(layer, h, mask, cfg.heads)
        h = layer_norm(layer["ln2"], x)
        h = linear(layer["fc1"], h)
        h = ACTIVATIONS[cfg.activation](h)
        x = x + linear(layer["fc2"], h)
        hiddens.append(x)

    final = layer_norm(p["final_norm"], x)
    if cfg.clip_skip == 0:
        hidden = final
    else:
        hidden = hiddens[-1 - cfg.clip_skip]

    # pooled = hidden state at the EOT token (highest token id by CLIP
    # convention: argmax over ids) of the final-normed sequence
    eot = jnp.argmax(token_ids, axis=-1)
    pooled = jnp.take_along_axis(final, eot[:, None, None], axis=1)[:, 0]
    out = {"hidden": hidden, "pooled": pooled}
    if cfg.use_text_projection and "text_projection" in p:
        out["projected"] = linear(p["text_projection"], pooled)
    return out
