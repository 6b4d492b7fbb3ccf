"""Operations and bytes of FLUX's stream step (``reference/flux_stream.py``)
from a configuration's shapes.

Matrix work only, two operations per multiply-add, as ``sd_stream``: per
row of the stream batch one transformer pass (``img_in``, ``txt_in``, the
time and pooled-text embedders, every double block's modulations, fused
q/k/v, output projections, joint attention and MLPs of both streams, every
single block's modulation, ``linear1``, attention and ``linear2``, the last
layer); per frame TAESD's encode and decode.  The rotations and the q/k RMS
norms are elementwise and not counted.  The text tower runs once a prompt,
in set-up, and is not in a frame.

``rope_attention_calls`` and its ``_flops`` / ``_bytes`` are what
``layer_metrics/rope_attention_roofline.py`` asks: the kernel's calls of
one pass over one row.
"""

from __future__ import annotations

from ..reference.flux_stream import weight_shapes
from .sd_stream import _conv, _lin, _tae_block


def _tokens(cfg: dict) -> tuple:
    """(image tokens, text tokens): a token packs 2x2 latent cells."""
    s = cfg["stream"]
    side = s["height"] // s["latent_scale"] // 2
    return side * side, cfg["text_encoder_2"]["max_sequence_length"]


def rope_attention_calls(cfg: dict) -> list:
    """Every ``rope_attention`` call of one pass over one row: each double
    and each single block attends over the whole joint sequence."""
    t = cfg["transformer"]
    img, txt = _tokens(cfg)
    call = {"lq": img + txt, "lk": img + txt, "heads": t["num_attention_heads"],
            "head_dim": t["attention_head_dim"]}
    return [dict(call) for _ in range(t["num_layers"] + t["num_single_layers"])]


def rope_attention_flops(call: dict, rows: int = 1) -> int:
    """QK^T and PV: two contractions of lq x lk x (heads*head_dim)."""
    return 4 * rows * call["lq"] * call["lk"] * call["heads"] * call["head_dim"]


def rope_attention_bytes(call: dict, rows: int = 1, itemsize: int = 2) -> int:
    """q and o [lq, inner], k and v [lk, inner], each moved once, and the
    float32 cosine and sine tables [lk, head_dim], read once a call."""
    inner = call["heads"] * call["head_dim"]
    return rows * itemsize * inner * 2 * (call["lq"] + call["lk"]) + 2 * 4 * call["lk"] * call["head_dim"]


def transformer_flops(cfg: dict) -> int:
    """One pass over one row."""
    p = weight_shapes(cfg)["flux"]
    img, txt = _tokens(cfg)
    f = _lin(p["img_in"]["kernel"], img) + _lin(p["txt_in"]["kernel"], txt)
    for mlp in (p["time_in"], p["vector_in"]):
        f += _lin(mlp["in_layer"]["kernel"], 1) + _lin(mlp["out_layer"]["kernel"], 1)
    calls = iter(rope_attention_calls(cfg))
    for b in p["double_blocks"]:
        for s, tokens in (("img", img), ("txt", txt)):
            f += _lin(b[f"{s}_mod"]["lin"]["kernel"], 1)
            f += _lin(b[f"{s}_attn"]["qkv"]["kernel"], tokens)
            f += _lin(b[f"{s}_attn"]["proj"]["kernel"], tokens)
            mlp = b[f"{s}_mlp"]
            f += _lin(mlp["proj"]["kernel"], tokens) + _lin(mlp["out"]["kernel"], tokens)
        f += rope_attention_flops(next(calls))
    for b in p["single_blocks"]:
        f += _lin(b["modulation"]["lin"]["kernel"], 1)
        f += _lin(b["linear1"]["kernel"], img + txt) + _lin(b["linear2"]["kernel"], img + txt)
        f += rope_attention_flops(next(calls))
    fl = p["final_layer"]
    return f + _lin(fl["adaLN_modulation"]["kernel"], 1) + _lin(fl["linear"]["kernel"], img)


def taesd_flops(cfg: dict) -> tuple:
    """-> (encode, decode) of one frame at 16 latent channels (TAEF1), as
    ``sd3_stream`` counts TAESD3's."""
    s = cfg["stream"]
    p = weight_shapes(cfg)["taesd"]
    h, w = s["height"], s["width"]
    e = p["encoder"]
    enc = _conv(e["conv_in"]["kernel"], h, w)[0] + _tae_block(e["block_in"], h, w)
    for st in e["stages"]:
        c, h, w = _conv(st["down"]["kernel"], h, w, 2)
        enc += c + sum(_tae_block(b, h, w) for b in st["blocks"])
    enc += _conv(e["conv_out"]["kernel"], h, w)[0]
    d = p["decoder"]
    dec = _conv(d["conv_in"]["kernel"], h, w)[0]
    for st in d["stages"]:
        dec += sum(_tae_block(b, h, w) for b in st["blocks"])
        h, w = 2 * h, 2 * w
        dec += _conv(st["up"]["kernel"], h, w)[0]
    dec += _tae_block(d["block_out"], h, w) + _conv(d["conv_out"]["kernel"], h, w)[0]
    return enc, dec


def frame_flops(cfg: dict) -> int:
    rows = len(cfg["stream"]["t_index_list"]) * cfg["stream"]["frame_buffer_size"]
    enc, dec = taesd_flops(cfg)
    return enc + rows * transformer_flops(cfg) + dec
