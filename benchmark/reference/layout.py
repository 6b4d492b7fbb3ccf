"""The weight tree of a configuration, as shapes, from the sizes in its
file alone: nested dicts and lists whose leaves are shape tuples.

This is the one thing the reference and the program have to agree on: the
benchmark fills this tree from the seed (``benchmark/weights.py``), the
reference reads it, and the program is handed the same arrays.  If the
program's own tree ever differs (a renamed leaf, another shape), the run
stops before it measures anything.

Leaves: ``kernel`` [in, out] or [k, k, in, out]; ``bias`` [out]; norm
``scale``/``bias`` [channels]; embedding tables [rows, width].
"""

from __future__ import annotations


def _lin(i, o, bias=True):
    return {"kernel": (i, o), **({"bias": (o,)} if bias else {})}


def _conv(i, o, k=3, bias=True):
    return {"kernel": (k, k, i, o), **({"bias": (o,)} if bias else {})}


def _norm(c):
    return {"scale": (c,), "bias": (c,)}


def _resnet(i, o, temb):
    p = {
        "norm1": _norm(i), "conv1": _conv(i, o),
        "time_emb_proj": _lin(temb, o),
        "norm2": _norm(o), "conv2": _conv(o, o),
    }
    if i != o:
        p["conv_shortcut"] = _conv(i, o, 1)
    return p


def _attn(q_dim, ctx_dim, inner):
    return {
        "to_q": _lin(q_dim, inner, False), "to_k": _lin(ctx_dim, inner, False),
        "to_v": _lin(ctx_dim, inner, False), "to_out": _lin(inner, q_dim),
    }


def _transformer(ch, u, depth):
    proj = (lambda: _lin(ch, ch)) if u["use_linear_projection"] else (
        lambda: _conv(ch, ch, 1))
    return {
        "norm": _norm(ch), "proj_in": proj(), "proj_out": proj(),
        "blocks": [
            {
                "norm1": _norm(ch), "attn1": _attn(ch, ch, ch),
                "norm2": _norm(ch),
                "attn2": _attn(ch, u["cross_attention_dim"], ch),
                "norm3": _norm(ch),
                "ff": {"proj": _lin(ch, ch * 8), "out": _lin(ch * 4, ch)},
            }
            for _ in range(depth)
        ],
    }


def unet(u: dict) -> dict:
    chans = list(u["block_out_channels"])
    n = len(chans)
    lpb = u["layers_per_block"]
    attends = [t.startswith("CrossAttn") for t in u["down_block_types"]]
    depth = u.get("transformer_layers_per_block", 1)
    depth = list(depth) if isinstance(depth, (list, tuple)) else [depth] * n
    temb = chans[0] * 4
    p = {
        "conv_in": _conv(u["in_channels"], chans[0]),
        "time_embedding": {
            "linear_1": _lin(chans[0], temb), "linear_2": _lin(temb, temb),
        },
        "down_blocks": [], "up_blocks": [],
        "conv_norm_out": _norm(chans[0]),
        "conv_out": _conv(chans[0], u["out_channels"]),
    }
    out, skips = chans[0], [chans[0]]
    for i, ch in enumerate(chans):
        inp, out = out, ch
        blk = {"resnets": [], "attentions": [], "downsample": None}
        for j in range(lpb):
            blk["resnets"].append(_resnet(inp if j == 0 else out, out, temb))
            if attends[i]:
                blk["attentions"].append(_transformer(out, u, depth[i]))
            skips.append(out)
        if i < n - 1:
            blk["downsample"] = _conv(out, out)
            skips.append(out)
        p["down_blocks"].append(blk)
    mid = chans[-1]
    p["mid_block"] = {
        "resnet1": _resnet(mid, mid, temb),
        "attention": _transformer(mid, u, depth[-1]),
        "resnet2": _resnet(mid, mid, temb),
    }
    prev = mid
    for i in reversed(range(n)):
        ch = chans[i]
        blk = {"resnets": [], "attentions": [], "upsample": None}
        for _ in range(lpb + 1):
            blk["resnets"].append(_resnet(prev + skips.pop(), ch, temb))
            prev = ch
            if attends[i]:
                blk["attentions"].append(_transformer(ch, u, depth[i]))
        if i > 0:
            blk["upsample"] = _conv(ch, ch)
        p["up_blocks"].append(blk)
    return p


def clip_text(t: dict) -> dict:
    w, ff = t["hidden_size"], t["intermediate_size"]
    return {
        "token_embedding": (t["vocab_size"], w),
        "position_embedding": (t["max_position_embeddings"], w),
        "final_norm": _norm(w),
        "layers": [
            {
                "ln1": _norm(w), "q": _lin(w, w), "k": _lin(w, w),
                "v": _lin(w, w), "out": _lin(w, w), "ln2": _norm(w),
                "fc1": _lin(w, ff), "fc2": _lin(ff, w),
            }
            for _ in range(t["num_hidden_layers"])
        ],
    }


def taesd(v: dict) -> dict:
    w = v["encoder_block_out_channels"][0]
    stages = len(v["encoder_block_out_channels"]) - 1
    per_stage = v["num_encoder_blocks"][1]
    lat, img = v["latent_channels"], v["in_channels"]
    blk = lambda: {"conv1": _conv(w, w), "conv2": _conv(w, w), "conv3": _conv(w, w)}  # noqa: E731
    return {
        "encoder": {
            "conv_in": _conv(img, w), "block_in": blk(),
            "stages": [
                {"down": _conv(w, w, bias=False),
                 "blocks": [blk() for _ in range(per_stage)]}
                for _ in range(stages)
            ],
            "conv_out": _conv(w, lat),
        },
        "decoder": {
            "conv_in": _conv(lat, w),
            "stages": [
                {"blocks": [blk() for _ in range(per_stage)],
                 "up": _conv(w, w, bias=False)}
                for _ in range(stages)
            ],
            "block_out": blk(), "conv_out": _conv(w, img),
        },
    }


def weight_shapes(cfg: dict) -> dict:
    return {
        "unet": unet(cfg["unet"]),
        "clip": clip_text(cfg["text_encoder"]),
        "taesd": taesd(cfg["vae"]),
    }
