"""The three networks of a Stable-Diffusion stream step, as plain float32
forward passes driven by the sizes in a configuration file
(``benchmark/configs/<name>.json``): the conditional UNet
(diffusers ``UNet2DConditionModel``: SD1.5 / SD2.1 / SDXL geometry), the CLIP
text tower (``CLIPTextModel``, ``CLIPTextModelWithProjection``) and the tiny
autoencoder TAESD
(``AutoencoderTiny``).  Published descriptions followed; departures the
program makes and the reference therefore shares are listed in the
configuration file under ``assumed``.
"""

from __future__ import annotations

import jax.numpy as jnp

from . import nn


# -- UNet -------------------------------------------------------------------

def _heads(u: dict) -> list:
    """``attention_head_dim`` in these published configs is the number of
    heads per block (a known misnomer of the diffusers config)."""
    h = u["attention_head_dim"]
    n = len(u["block_out_channels"])
    return list(h) if isinstance(h, (list, tuple)) else [h] * n


def _resnet(p, x, temb, groups):
    h = nn.conv(p["conv1"], nn.silu(nn.group_norm(p["norm1"], x, groups)))
    h = h + nn.dense(p["time_emb_proj"], nn.silu(temb))[:, None, None, :]
    h = nn.conv(p["conv2"], nn.silu(nn.group_norm(p["norm2"], h, groups)))
    if "conv_shortcut" in p:
        x = nn.conv(p["conv_shortcut"], x)
    return x + h


def _geglu(p, x):
    a, g = jnp.split(nn.dense(p["proj"], x), 2, axis=-1)
    return nn.dense(p["out"], a * nn.gelu(g))


def _transformer(p, x, ctx, u, heads):
    n, h, w, c = x.shape
    z = nn.group_norm(p["norm"], x, u["norm_num_groups"])
    if u["use_linear_projection"]:
        z = nn.dense(p["proj_in"], z.reshape(n, h * w, c))
    else:
        z = nn.conv(p["proj_in"], z).reshape(n, h * w, c)
    for b in p["blocks"]:
        z = z + nn.multi_head(b["attn1"], nn.layer_norm(b["norm1"], z), None, heads)
        z = z + nn.multi_head(b["attn2"], nn.layer_norm(b["norm2"], z), ctx, heads)
        z = z + _geglu(b["ff"], nn.layer_norm(b["norm3"], z))
    if u["use_linear_projection"]:
        z = nn.dense(p["proj_out"], z).reshape(n, h, w, c)
    else:
        z = nn.conv(p["proj_out"], z.reshape(n, h, w, c))
    return z + x


def unet(p, x, timesteps, ctx, u: dict, added=None):
    """x [B,h,w,4], timesteps [B], ctx [B,L,cross] -> eps [B,h,w,4].
    ``added`` [B,temb]: an addition embedding (``addition_embed_type``),
    summed onto the time embedding before the first block reads it."""
    groups = u["norm_num_groups"]
    heads = _heads(u)
    te = p["time_embedding"]
    temb = nn.sinusoid(timesteps, u["block_out_channels"][0])
    temb = nn.dense(te["linear_2"], nn.silu(nn.dense(te["linear_1"], temb)))
    if added is not None:
        temb = temb + added

    h = nn.conv(p["conv_in"], x)
    skips = [h]
    for i, blk in enumerate(p["down_blocks"]):
        for j, rn in enumerate(blk["resnets"]):
            h = _resnet(rn, h, temb, groups)
            if blk["attentions"]:
                h = _transformer(blk["attentions"][j], h, ctx, u, heads[i])
            skips.append(h)
        if blk["downsample"] is not None:
            h = nn.conv(blk["downsample"], h, stride=2)
            skips.append(h)

    mid = p["mid_block"]
    h = _resnet(mid["resnet1"], h, temb, groups)
    h = _transformer(mid["attention"], h, ctx, u, heads[-1])
    h = _resnet(mid["resnet2"], h, temb, groups)

    n_blocks = len(p["up_blocks"])
    for k, blk in enumerate(p["up_blocks"]):
        i = n_blocks - 1 - k
        for j, rn in enumerate(blk["resnets"]):
            h = _resnet(rn, jnp.concatenate([h, skips.pop()], axis=-1), temb, groups)
            if blk["attentions"]:
                h = _transformer(blk["attentions"][j], h, ctx, u, heads[i])
        if blk["upsample"] is not None:
            h = nn.conv(blk["upsample"], nn.upsample2x(h))
    h = nn.silu(nn.group_norm(p["conv_norm_out"], h, groups))
    return nn.conv(p["conv_out"], h)


# -- CLIP text tower --------------------------------------------------------

def clip_layers(p, token_ids, t: dict) -> list:
    """token_ids [B,L] -> the hidden states [B,L,width] after the embeddings
    and after each layer, none of them final-normed."""
    act = {"quick_gelu": nn.quick_gelu, "gelu": nn.gelu}[t["hidden_act"]]
    heads = t["num_attention_heads"]
    b, l = token_ids.shape
    x = nn.f32(p["token_embedding"][token_ids]) + nn.f32(p["position_embedding"][:l])
    causal = jnp.where(jnp.tril(jnp.ones((l, l), bool)), 0.0, -1e9)[None, None]
    hiddens = [x]
    for layer in p["layers"]:
        y = nn.layer_norm(layer["ln1"], x)
        d = y.shape[-1] // heads
        q, k, v = (
            nn.dense(layer[n], y).reshape(b, l, heads, d) for n in ("q", "k", "v")
        )
        a = nn.softmax_attention(q, k, v, causal).reshape(b, l, heads * d)
        x = x + nn.dense(layer["out"], a)
        y = nn.layer_norm(layer["ln2"], x)
        x = x + nn.dense(layer["fc2"], act(nn.dense(layer["fc1"], y)))
        hiddens.append(x)
    return hiddens


def _clip_hidden(p, hiddens: list, t: dict):
    if t["clip_skip"] == 0:
        return nn.layer_norm(p["final_norm"], hiddens[-1])
    return hiddens[-1 - t["clip_skip"]]


def clip_text(p, token_ids, t: dict):
    """token_ids [B,L] -> hidden states [B,L,width] fed to cross attention:
    the last layer's, final-normed, when ``clip_skip`` is 0; the raw output
    of layer ``-1-clip_skip`` otherwise."""
    return _clip_hidden(p, clip_layers(p, token_ids, t), t)


def clip_text_projected(p, token_ids, t: dict):
    """``CLIPTextModelWithProjection``: -> (hidden states as ``clip_text``
    gives them, the text embedding [B,projection_dim]).  The embedding is the
    last layer's final-normed state at the end-of-text token (the highest id
    of the CLIP vocabulary, so the first argmax of a row), through the
    bias-free ``text_projection``."""
    hiddens = clip_layers(p, token_ids, t)
    final = nn.layer_norm(p["final_norm"], hiddens[-1])
    eot = jnp.argmax(token_ids, axis=-1)
    pooled = final[jnp.arange(final.shape[0]), eot]
    return _clip_hidden(p, hiddens, t), nn.dense(p["text_projection"], pooled)


# -- TAESD ------------------------------------------------------------------

def _tae_block(p, x):
    h = jnp.maximum(nn.conv(p["conv1"], x), 0.0)
    h = jnp.maximum(nn.conv(p["conv2"], h), 0.0)
    return jnp.maximum(nn.conv(p["conv3"], h) + x, 0.0)


def taesd_encode(p, img01):
    """RGB [N,H,W,3] in [0,1] -> scaled latents [N,H/8,W/8,4]."""
    h = _tae_block(p["block_in"], nn.conv(p["conv_in"], img01))
    for stage in p["stages"]:
        h = nn.conv(stage["down"], h, stride=2)
        for b in stage["blocks"]:
            h = _tae_block(b, h)
    return nn.conv(p["conv_out"], h)


def taesd_decode(p, z):
    """latents [N,h,w,4] -> RGB [N,8h,8w,3] clipped to [0,1]."""
    h = jnp.maximum(nn.conv(p["conv_in"], jnp.tanh(z / 3.0) * 3.0), 0.0)
    for stage in p["stages"]:
        for b in stage["blocks"]:
            h = _tae_block(b, h)
        h = nn.conv(stage["up"], nn.upsample2x(h))
    h = _tae_block(p["block_out"], h)
    return jnp.clip(nn.conv(p["conv_out"], h), 0.0, 1.0)
