"""Operations and bytes of the ControlNet-conditioned stream step from a
configuration's shapes: ``sd_stream``'s count (TAESD encode, the UNet over
the stream batch, TAESD decode) plus, on every row of the stream batch, the
side network (the conditioning stack, the encoder copy with its middle
block, the thirteen 1x1 zero convolutions), and once a frame the annotator.

Matrix work, two operations per multiply-add, as in ``sd_stream``; the
thirteen residual adds are elementwise and left out like every other
elementwise pass.  The annotator has no matrix work at all (a 3x3 stencil
on one channel): it is counted as the two Sobel responses it is, 2 x 2 x 9
operations a pixel, so that the count does not pretend it is free; at
512x512 that is 9.4e6 of 4.8e12.

``attention_calls(cfg)`` lists the calls of one row's pass in call order:
the side network's fourteen (its six encoder transformers and its middle
block, a self- and a cross-attention each) and then the UNet's thirty-two.
"""

from __future__ import annotations

from ..reference.sd_control_stream import weight_shapes
from . import sd_stream
from .sd_stream import (  # noqa: F401  (what the roofline readers ask of a flops module)
    _conv,
    _lin,
    _resnet,
    _transformer,
    attention_bytes,
    attention_flops,
)


def _encoder_calls(cfg: dict, u: dict) -> list:
    """The attention calls of an encoder and its middle block: what
    ``sd_stream.attention_calls`` lists before the first up block (a level
    that attends has ``layers_per_block + 1`` transformers on the way up,
    each ``depth`` blocks of a self- and a cross-attention)."""
    levels = len(u["block_out_channels"])
    depth = u.get("transformer_layers_per_block", 1)
    depth = list(depth) if isinstance(depth, list) else [depth] * levels
    up = sum(
        2 * depth[i] * (u["layers_per_block"] + 1)
        for i, t in enumerate(u["down_block_types"]) if t.startswith("CrossAttn")
    )
    calls = sd_stream.attention_calls(dict(cfg, unet=u))
    return calls[: len(calls) - up]


def attention_calls(cfg: dict) -> list:
    return _encoder_calls(cfg, cfg["controlnet"]) + sd_stream.attention_calls(cfg)


def annotator_flops(cfg: dict) -> int:
    s = cfg["stream"]
    return 2 * 2 * 9 * s["height"] * s["width"]


def hint_flops(cfg: dict) -> int:
    """The conditioning stack on one row's edge map."""
    s = cfg["stream"]
    p = weight_shapes(cfg)["controlnet"]["cond_embedding"]
    h, w = s["height"], s["width"]
    f = _conv(p["conv_in"]["kernel"], h, w)[0]
    for blk in p["blocks"]:
        f += _conv(blk["conv1"]["kernel"], h, w)[0]
        c, h, w = _conv(blk["conv2"]["kernel"], h, w, 2)
        f += c
    return f + _conv(p["conv_out"]["kernel"], h, w)[0]


def side_network_flops(cfg: dict, rows: int) -> int:
    """The encoder copy, its middle block and the zero convolutions over
    ``rows`` rows, the conditioning stack included."""
    cn, s = cfg["controlnet"], cfg["stream"]
    p = weight_shapes(cfg)["controlnet"]
    heads = cn["attention_head_dim"]
    heads = list(heads) if isinstance(heads, list) else [heads] * len(cn["block_out_channels"])
    lk = cfg["text_encoder"]["max_position_embeddings"]
    h = w = s["height"] // s["latent_scale"]
    te = p["time_embedding"]
    f = rows * (
        _lin(te["linear_1"]["kernel"], 1) + _lin(te["linear_2"]["kernel"], 1)
        + _conv(p["conv_in"]["kernel"], h, w)[0] + hint_flops(cfg)
    )
    sizes = [(h, w)]  # of every encoder output, for its zero convolution
    for i, blk in enumerate(p["down_blocks"]):
        for j, rn in enumerate(blk["resnets"]):
            f += _resnet(rn, h, w, rows)
            if blk["attentions"]:
                f += _transformer(blk["attentions"][j], h, w, rows, lk, heads[i])
            sizes.append((h, w))
        if blk["downsample"] is not None:
            c, h, w = _conv(blk["downsample"]["kernel"], h, w, 2)
            f += rows * c
            sizes.append((h, w))
    mid = p["mid_block"]
    f += _resnet(mid["resnet1"], h, w, rows) + _resnet(mid["resnet2"], h, w, rows)
    f += _transformer(mid["attention"], h, w, rows, lk, heads[-1])
    for z, (zh, zw) in zip(p["zero_convs"], sizes):
        f += rows * _conv(z["kernel"], zh, zw)[0]
    return f + rows * _conv(p["mid_zero_conv"]["kernel"], h, w)[0]


def frame_flops(cfg: dict) -> int:
    rows = len(cfg["stream"]["t_index_list"]) * cfg["stream"]["frame_buffer_size"]
    return sd_stream.frame_flops(cfg) + side_network_flops(cfg, rows) + annotator_flops(cfg)
