"""The stream step of ``sd_stream`` conditioned by a side network
(ControlNet: Zhang, Rao and Agrawala, "Adding Conditional Control to
Text-to-Image Diffusion Models", arXiv:2302.05543, section 3.2 and figure
3), with the conditioning image made in the step from the incoming frame.
Everything else (TAESD, the latent ring, R-CFG, the LCM output, the hash
tokenizer) is ``sd_stream``'s, which this module extends.

The side network, as the paper describes it for Stable Diffusion:

* a trainable copy of the UNet's encoder (``conv_in``, the time embedding,
  the down blocks: twelve outputs with SD1.5's geometry) and of its middle
  block, run on the same noisy latent ``x_t``, timestep and text context as
  the UNet itself;
* the conditioning image, at image resolution, goes through a small stack
  of 3x3 convolutions with SiLU between them
  (``conditioning_embedding_out_channels`` 16, 32, 96, 256: at every width
  one convolution that keeps it and one of stride 2 that takes the next, so
  three halvings bring a 512x512 image to the 64x64 latent grid; a last
  convolution to the UNet's first width) and is added to ``conv_in(x_t)``;
* one 1x1 "zero convolution" on each of the twelve encoder outputs and on
  the middle block's output; the thirteen results, times
  ``conditioning_scale``, are added to the UNet's twelve skip connections
  (after its encoder has run: the UNet's own forward path through the
  encoder is untouched) and to its middle block's output.

The conditioning ring.  With a stream batch of ``B`` stages in flight the
``B`` rows of one UNet pass belong to ``B`` different frames: row ``j``
denoises the frame that came in ``j`` steps ago.  Each row is conditioned on
the edge map of *its own* frame, so the edge maps ride a ring beside the
latent ring: the new frame's map enters at the front, every older map moves
one row back, the last row's leaves with its latent.  **Fill at claim: a
fresh session's ring holds zeros (an image with no edges), as its latent
ring holds noise (no frame at all)**; the first ``B - 1`` outputs are
warm-up on both sides.

Departures (the program's, which the reference shares; the configuration
file lists them under ``assumed``):

* **the annotator is not OpenCV's Canny.**  Non-maximum suppression and
  hysteresis have no in-graph form; the program's operator, implemented
  here independently (a convolution where the program shifts slices), is:
  luma ``0.299 R + 0.587 G + 0.114 B`` of the frame in [0, 1]; the two 3x3
  Sobel responses over 4, zero beyond the frame's edge; their magnitude
  ``sqrt(gx^2 + gy^2 + 1e-12)``; a soft double threshold
  ``sigmoid(12 (m - low) / (high - low) - 6)`` with ``low`` 0.1 and
  ``high`` 0.3 (0.25 % at ``low``, 99.75 % at ``high``); the one channel
  repeated three times;
* with weights from the seed the "zero" convolutions are not zero: that is
  what lets the comparison see the side network at all;
* this module imports ``sd_stream`` beside ``nn``, ``models`` and
  ``layout``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from . import layout, models, nn, sd_stream
from .sd_stream import hash_tokens  # noqa: F401  (a reference module's second name)


# -- the weight tree ----------------------------------------------------------

def side_network_shapes(cn: dict) -> dict:
    """The side network's tree from the ``controlnet`` section of a
    configuration file (the keys of its ``config.json``)."""
    chans = list(cn["block_out_channels"])
    enc = layout.unet(dict(cn, out_channels=cn["in_channels"]))
    widths = list(cn["conditioning_embedding_out_channels"])
    skips = [chans[0]]
    for i, ch in enumerate(chans):
        skips += [ch] * cn["layers_per_block"]
        if i < len(chans) - 1:
            skips.append(ch)
    return {
        "conv_in": enc["conv_in"],
        "time_embedding": enc["time_embedding"],
        "down_blocks": enc["down_blocks"],
        "mid_block": enc["mid_block"],
        "cond_embedding": {
            "conv_in": layout._conv(cn["conditioning_channels"], widths[0]),
            "blocks": [
                {"conv1": layout._conv(a, a), "conv2": layout._conv(a, b)}
                for a, b in zip(widths[:-1], widths[1:])
            ],
            "conv_out": layout._conv(widths[-1], chans[0]),
        },
        "zero_convs": [layout._conv(c, c, 1) for c in skips],
        "mid_zero_conv": layout._conv(chans[-1], chans[-1], 1),
    }


def weight_shapes(cfg: dict) -> dict:
    return dict(layout.weight_shapes(cfg), controlnet=side_network_shapes(cfg["controlnet"]))


# -- the annotator ------------------------------------------------------------

_SOBEL_X = np.array([[-1, 0, 1], [-2, 0, 2], [-1, 0, 1]], np.float32) / 4.0


def soft_canny(img01, low: float, high: float):
    """RGB [N,H,W,3] in [0,1] -> edge map [N,H,W,3] in [0,1]."""
    luma = (
        0.299 * img01[..., 0:1] + 0.587 * img01[..., 1:2] + 0.114 * img01[..., 2:3]
    )
    # one convolution, two output channels: d/dx and d/dy
    kernel = jnp.asarray(np.stack([_SOBEL_X, _SOBEL_X.T], axis=-1)[:, :, None, :])
    g = jax.lax.conv_general_dilated(
        luma, kernel, (1, 1), ((1, 1), (1, 1)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=nn.HI,
    )
    mag = jnp.sqrt((g * g).sum(-1, keepdims=True) + 1e-12)
    edge = 1.0 / (1.0 + jnp.exp(-(12.0 * (mag - low) / (high - low) - 6.0)))
    return jnp.broadcast_to(edge, img01.shape)


# -- the side network and the UNet that takes its residuals -------------------

def _time_embedding(p, timesteps, width: int):
    temb = nn.sinusoid(timesteps, width)
    return nn.dense(p["linear_2"], nn.silu(nn.dense(p["linear_1"], temb)))


def _encoder(p, h, temb, ctx, u: dict) -> tuple:
    """The down blocks and the middle block on ``h`` = what followed
    ``conv_in`` -> (the encoder's outputs, ``h`` first; the middle block's
    output)."""
    groups, heads = u["norm_num_groups"], models._heads(u)
    outs = [h]
    for i, blk in enumerate(p["down_blocks"]):
        for j, rn in enumerate(blk["resnets"]):
            h = models._resnet(rn, h, temb, groups)
            if blk["attentions"]:
                h = models._transformer(blk["attentions"][j], h, ctx, u, heads[i])
            outs.append(h)
        if blk["downsample"] is not None:
            h = nn.conv(blk["downsample"], h, stride=2)
            outs.append(h)
    mid = p["mid_block"]
    h = models._resnet(mid["resnet1"], h, temb, groups)
    h = models._transformer(mid["attention"], h, ctx, u, heads[-1])
    return outs, models._resnet(mid["resnet2"], h, temb, groups)


def hint(p, edges):
    """The conditioning image [B,H,W,3] -> [B,h,w,first width]."""
    c = nn.silu(nn.conv(p["conv_in"], edges))
    for blk in p["blocks"]:
        c = nn.silu(nn.conv(blk["conv1"], c))
        c = nn.silu(nn.conv(blk["conv2"], c, stride=2))
    return nn.conv(p["conv_out"], c)


def side_network(p, x, timesteps, ctx, edges, cn: dict, scale):
    """-> (twelve residuals for the UNet's skips, one for its middle)."""
    temb = _time_embedding(p["time_embedding"], timesteps, cn["block_out_channels"][0])
    h = nn.conv(p["conv_in"], x) + hint(p["cond_embedding"], edges)
    outs, mid = _encoder(p, h, temb, ctx, cn)
    down = [scale * nn.conv(z, o) for z, o in zip(p["zero_convs"], outs)]
    return down, scale * nn.conv(p["mid_zero_conv"], mid)


def unet(p, x, timesteps, ctx, u: dict, down, mid_residual):
    """``models.unet`` with the side network's residuals: on the skips once
    the encoder has run, and on the middle block's output."""
    groups, heads = u["norm_num_groups"], models._heads(u)
    temb = _time_embedding(p["time_embedding"], timesteps, u["block_out_channels"][0])
    skips, h = _encoder(p, nn.conv(p["conv_in"], x), temb, ctx, u)
    skips = [s + r for s, r in zip(skips, down)]
    h = h + mid_residual
    n_blocks = len(p["up_blocks"])
    for k, blk in enumerate(p["up_blocks"]):
        for j, rn in enumerate(blk["resnets"]):
            h = models._resnet(rn, jnp.concatenate([h, skips.pop()], axis=-1), temb, groups)
            if blk["attentions"]:
                h = models._transformer(blk["attentions"][j], h, ctx, u, heads[n_blocks - 1 - k])
        if blk["upsample"] is not None:
            h = nn.conv(blk["upsample"], nn.upsample2x(h))
    return nn.conv(p["conv_out"], nn.silu(nn.group_norm(p["conv_norm_out"], h, groups)))


# -- the stream ---------------------------------------------------------------

class Reference(sd_stream.Reference):
    """``sd_stream.Reference`` over the tree ``{"unet","clip","taesd",
    "controlnet"}``.  What conditions a step is the text context, the edge
    maps of the stream batch's rows and the conditioning scale."""

    def __init__(self, cfg: dict, weights: dict):
        super().__init__(cfg, weights)
        a = cfg["annotator"]
        if a["kind"] != "canny_soft":
            raise ValueError(a["kind"])
        self._edge = jax.jit(
            lambda frame_u8: soft_canny(
                frame_u8.astype(jnp.float32)[None] / 255.0, a["low"], a["high"]
            )
        )

    def session(self, prompt: str, seed: int) -> "Session":
        return Session(self, prompt, seed)

    def _eps(self, w, x_t, cond):
        def rows(a):
            return jnp.broadcast_to(a, x_t.shape[:1] + a.shape[1:])

        t, ctx = jnp.asarray(self.k["t"], jnp.int32), rows(cond["ctx"])
        down, mid = side_network(
            w["controlnet"], x_t, t, ctx, cond["edges"], self.cfg["controlnet"],
            cond["scale"],
        )
        return unet(w["unet"], x_t, t, ctx, self.cfg["unet"], down, mid)


class Session(sd_stream.Session):
    """One session's reference stream with its conditioning ring.
    ``scale``: this session's conditioning scale (the configuration file's
    ``conditioning_scale`` unless set)."""

    def __init__(self, ref: Reference, prompt: str, seed: int):
        super().__init__(ref, prompt, seed)
        s = ref.s
        stages = len(s["t_index_list"])
        self.scale = float(ref.cfg["conditioning_scale"])
        self.edges = jnp.zeros((stages - 1, s["height"], s["width"], 3), jnp.float32)

    def step(self, frame_u8: np.ndarray) -> np.ndarray:
        ref = self.ref
        with jax.default_matmul_precision("highest"):
            frame = jnp.asarray(frame_u8)
            edges = jnp.concatenate([ref._edge(frame), self.edges], axis=0)
            cond = {
                "ctx": self.cond, "edges": edges,
                "scale": jnp.asarray(self.scale, jnp.float32),
            }
            self.ring, self.stock, out = ref._step(
                ref.w, cond, self.noise, self.ring, self.stock, frame
            )
        self.edges = edges[:-1]
        return np.asarray(out)
