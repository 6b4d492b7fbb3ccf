"""Operations and bytes of the SDXL family's stream step from a
configuration's shapes: ``sd_stream``'s count (whose UNet walk follows
``down_block_types`` and ``transformer_layers_per_block`` tier by tier, and
whose attention calls see the 77 keys of either tower) plus the addition
embedding's two linear layers, once a step: every row of the stream batch
shares the session's one text embedding and ``time_ids``.  The text towers
run once a prompt, in set-up, and are not in a frame."""

from __future__ import annotations

from ..reference.sdxl_stream import weight_shapes
from . import sd_stream
from .sd_stream import (  # noqa: F401  (what the roofline readers ask of a flops module)
    attention_bytes,
    attention_calls,
    attention_flops,
)


def add_embedding_flops(cfg: dict) -> int:
    ae = weight_shapes(cfg)["unet"]["add_embedding"]
    return sum(2 * k[0] * k[1] for k in (ae["linear_1"]["kernel"], ae["linear_2"]["kernel"]))


def frame_flops(cfg: dict) -> int:
    return sd_stream.frame_flops(cfg) + add_embedding_flops(cfg)
