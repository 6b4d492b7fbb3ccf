"""The stream step of ``sd_stream`` for the SDXL family: two text towers and
the UNet's ``text_time`` addition embedding.  Everything else (TAESD, the
latent ring, R-CFG, the LCM / turbo output, the hash tokenizer, the sessions)
is ``sd_stream``'s, which this module extends; transformer depth by tier
comes with the weight tree (``transformer_layers_per_block`` in the
configuration file, ``layout.unet``).

Followed: diffusers' ``UNet2DConditionModel`` with
``addition_embed_type="text_time"`` and ``StableDiffusionXLPipeline``'s
prompt encoding; the SDXL report (arXiv:2307.01952), section 2.2, for the
micro-conditioning.

* Context: the hidden states of ``text_encoder`` (CLIP ViT-L) and of
  ``text_encoder_2`` (OpenCLIP ViT-bigG) concatenated on the width: 768 +
  1280 = 2048 = ``cross_attention_dim``.
* Text embedding: ``text_encoder_2``'s final-normed state at the end-of-text
  token through its bias-free ``text_projection``.
* Micro-conditioning: the six ``time_ids`` (original height and width, crop
  top and left, target height and width) each through the
  ``addition_time_embed_dim``-wide sinusoid of the time embedding ([cos|sin],
  max period 10000), concatenated after the text embedding (1280 + 6 x 256 =
  2816 = ``projection_class_embeddings_input_dim``), through
  ``add_embedding``'s two linear layers with a SiLU between, added to the
  time embedding.

Departures (the program's, which the reference shares; the configuration
file lists them under ``assumed``):

* the first tower feeds its *last* layer's final-normed states where the
  pipeline takes the penultimate layer's (``text_encoder.clip_skip`` 0; the
  second tower's ``clip_skip`` is 1 as published);
* ``time_ids`` are the stream's own ``(height, width, 0, 0, height, width)``:
  the frame is neither resized nor cropped;
* both towers read one row of token ids (the pipeline has a tokenizer per
  tower; with the hash tokenizer there is one vocabulary);
* this module imports ``sd_stream`` beside ``nn``, ``models`` and ``layout``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import layout, models, nn, sd_stream
from .sd_stream import hash_tokens

N_TIME_IDS = 6


def weight_shapes(cfg: dict) -> dict:
    u, t2 = cfg["unet"], cfg["text_encoder_2"]
    temb = u["block_out_channels"][0] * 4
    add_in = t2["projection_dim"] + N_TIME_IDS * u["addition_time_embed_dim"]
    unet = layout.unet(u)
    unet["add_embedding"] = {
        "linear_1": {"kernel": (add_in, temb), "bias": (temb,)},
        "linear_2": {"kernel": (temb, temb), "bias": (temb,)},
    }
    clip2 = layout.clip_text(t2)
    clip2["text_projection"] = {"kernel": (t2["hidden_size"], t2["projection_dim"])}
    return {
        "unet": unet,
        "clip": layout.clip_text(cfg["text_encoder"]),
        "clip2": clip2,
        "taesd": layout.taesd(cfg["vae"]),
    }


class Reference(sd_stream.Reference):
    """``sd_stream.Reference`` over the tree ``{"unet","clip","clip2",
    "taesd"}``; a session's conditioning is the 2048-wide context and the
    second tower's text embedding."""

    def encode_prompt(self, prompt: str):
        t1, t2 = self.cfg["text_encoder"], self.cfg["text_encoder_2"]
        ids = jnp.asarray(
            [hash_tokens(prompt, t1["vocab_size"], t1["max_position_embeddings"])],
            jnp.int32,
        )
        with jax.default_matmul_precision("highest"):
            h1 = models.clip_text(self.w["clip"], ids, t1)
            h2, text = models.clip_text_projected(self.w["clip2"], ids, t2)
        return {"ctx": jnp.concatenate([h1, h2], axis=-1), "text": text}

    def _conditioning(self, w, cond):
        u, s = self.cfg["unet"], self.s
        time_ids = jnp.asarray(
            [s["height"], s["width"], 0, 0, s["height"], s["width"]], jnp.float32
        )
        micro = nn.sinusoid(time_ids, u["addition_time_embed_dim"]).reshape(1, -1)
        ae = w["unet"]["add_embedding"]
        added = nn.dense(ae["linear_2"], nn.silu(nn.dense(
            ae["linear_1"], jnp.concatenate([cond["text"], micro], axis=-1)
        )))
        return cond["ctx"], added
