"""Operations and bytes from a configuration's shapes.

Matrix work only (convolutions, linears, the two attention contractions),
two operations per multiply-add: what the algorithm needs for one call,
whatever implements it.  Elementwise work (norms, activations, softmax) is
left out: under one percent at the published widths.

``frame_flops(cfg)``: one stylized frame = one stream step: TAESD encode of
one frame, one UNet pass over the stream batch (one row per denoising
stage), TAESD decode of the one latent that leaves the ring.
"""

from __future__ import annotations

from ..reference.layout import weight_shapes


def _conv(shape, h, w, stride=1):
    k, _, cin, cout = shape
    ho, wo = h // stride, w // stride
    return 2 * k * k * cin * cout * ho * wo, ho, wo


def _lin(shape, tokens):
    return 2 * shape[0] * shape[1] * tokens


def attention_calls(cfg: dict) -> list:
    """Every attention call of one UNet pass over one row, in call order:
    dicts of ``lq, lk, heads, head_dim``.  (Rows multiply the batch.)"""
    u, s = cfg["unet"], cfg["stream"]
    chans = list(u["block_out_channels"])
    heads = u["attention_head_dim"]
    heads = list(heads) if isinstance(heads, list) else [heads] * len(chans)
    attends = [t.startswith("CrossAttn") for t in u["down_block_types"]]
    depth = u.get("transformer_layers_per_block", 1)
    depth = list(depth) if isinstance(depth, list) else [depth] * len(chans)
    lk = cfg["text_encoder"]["max_position_embeddings"]
    side = s["height"] // s["latent_scale"]
    calls = []

    def block(i, level):
        tokens = (side >> level) ** 2
        for _ in range(depth[i]):
            for ctx in (tokens, lk):
                calls.append({
                    "lq": tokens, "lk": ctx, "heads": heads[i],
                    "head_dim": chans[i] // heads[i],
                })

    n = len(chans)
    for i in range(n):
        if attends[i]:
            for _ in range(u["layers_per_block"]):
                block(i, i)
    block(n - 1, n - 1)  # mid block
    for i in reversed(range(n)):
        if attends[i]:
            for _ in range(u["layers_per_block"] + 1):
                block(i, i)
    return calls


def attention_flops(call: dict, rows: int = 1) -> int:
    """QK^T and PV: two contractions of lq x lk x (heads*head_dim)."""
    return 4 * rows * call["lq"] * call["lk"] * call["heads"] * call["head_dim"]


def attention_bytes(call: dict, rows: int = 1, itemsize: int = 2) -> int:
    """q and o [lq, inner], k and v [lk, inner], each moved once."""
    inner = call["heads"] * call["head_dim"]
    return rows * itemsize * inner * 2 * (call["lq"] + call["lk"])


def _resnet(p, h, w, rows):
    f = _conv(p["conv1"]["kernel"], h, w)[0] + _conv(p["conv2"]["kernel"], h, w)[0]
    f += _lin(p["time_emb_proj"]["kernel"], 1)
    if "conv_shortcut" in p:
        f += _conv(p["conv_shortcut"]["kernel"], h, w)[0]
    return rows * f


def _transformer(p, h, w, rows, lk, heads):
    tokens = h * w
    proj = p["proj_in"]["kernel"]
    f = 2 * (_lin(proj, tokens) if len(proj) == 2 else _conv(proj, h, w)[0])
    for b in p["blocks"]:
        for name, ctx in (("attn1", tokens), ("attn2", lk)):
            a = b[name]
            f += _lin(a["to_q"]["kernel"], tokens) + _lin(a["to_out"]["kernel"], tokens)
            f += _lin(a["to_k"]["kernel"], ctx) + _lin(a["to_v"]["kernel"], ctx)
            inner = a["to_q"]["kernel"][1]
            f += attention_flops(
                {"lq": tokens, "lk": ctx, "heads": heads, "head_dim": inner // heads}
            )
        f += _lin(b["ff"]["proj"]["kernel"], tokens) + _lin(b["ff"]["out"]["kernel"], tokens)
    return rows * f


def unet_flops(cfg: dict, rows: int) -> int:
    u, s = cfg["unet"], cfg["stream"]
    p = weight_shapes(cfg)["unet"]
    heads = u["attention_head_dim"]
    n = len(u["block_out_channels"])
    heads = list(heads) if isinstance(heads, list) else [heads] * n
    lk = cfg["text_encoder"]["max_position_embeddings"]
    h = w = s["height"] // s["latent_scale"]
    te = p["time_embedding"]
    f = rows * (_lin(te["linear_1"]["kernel"], 1) + _lin(te["linear_2"]["kernel"], 1))
    f += rows * _conv(p["conv_in"]["kernel"], h, w)[0]
    for i, blk in enumerate(p["down_blocks"]):
        for j, rn in enumerate(blk["resnets"]):
            f += _resnet(rn, h, w, rows)
            if blk["attentions"]:
                f += _transformer(blk["attentions"][j], h, w, rows, lk, heads[i])
        if blk["downsample"] is not None:
            c, h, w = _conv(blk["downsample"]["kernel"], h, w, 2)
            f += rows * c
    mid = p["mid_block"]
    f += _resnet(mid["resnet1"], h, w, rows) + _resnet(mid["resnet2"], h, w, rows)
    f += _transformer(mid["attention"], h, w, rows, lk, heads[-1])
    for k, blk in enumerate(p["up_blocks"]):
        i = n - 1 - k
        for j, rn in enumerate(blk["resnets"]):
            f += _resnet(rn, h, w, rows)
            if blk["attentions"]:
                f += _transformer(blk["attentions"][j], h, w, rows, lk, heads[i])
        if blk["upsample"] is not None:
            h, w = 2 * h, 2 * w
            f += rows * _conv(blk["upsample"]["kernel"], h, w)[0]
    return f + rows * _conv(p["conv_out"]["kernel"], h, w)[0]


def _tae_block(p, h, w):
    return sum(_conv(p[c]["kernel"], h, w)[0] for c in ("conv1", "conv2", "conv3"))


def taesd_flops(cfg: dict) -> tuple:
    """-> (encode, decode) of one frame."""
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
    return enc + unet_flops(cfg, rows) + dec
