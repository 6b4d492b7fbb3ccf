"""The stream step of FLUX.1's double- and single-stream rectified-flow
transformer as a plain float32 reference: one session, frame after frame,
from the moment it is claimed.

Per frame: uint8 -> [0,1]; TAESD (TAEF1) encode to 16 latent channels; the
new latent at the first stage's noise level, ``(1 - s) z + s noise``, in
front of the ring; one transformer pass over that batch, each row at its
own level, predicting the velocity ``v``; every row's clean latent
``x0 = x - s v``; the last row leaves the ring and is decoded, the others
move to the next stage's level with the stage's own fixed noise.  No
guidance.

Followed: Black Forest Labs' reference code (github.com/black-forest-labs/
flux): ``model.Flux`` (``img_in``, ``time_in`` and ``vector_in`` MLPs,
``txt_in``, ``EmbedND`` over the ids ``sampling.prepare`` gives, the
double blocks, the single blocks on the text-first joint sequence, the
image part through ``LastLayer``), ``modules.layers`` (``Modulation``,
``QKNorm``'s RMSNorms, ``DoubleStreamBlock``, ``SingleStreamBlock``),
``math`` (``rope``, ``apply_rope``, ``attention``) and the 2x2 packing of
``sampling.prepare`` / ``unpack``; the pooled text is CLIP-L's pooler
output (``HFEmbedder``).

Departures (the configuration file lists them under ``assumed`` and
``reduced``):

* random weights from the seed (``benchmark/weights.py``), TAEF1's last
  encoder kernel scaled by the file's ``weights.rules`` so that the frame
  reaches the output through a velocity that was never trained;
* one chip's stage of a four-stage pipeline: the file's ``num_layers`` and
  ``num_single_layers`` blocks, in their order, and the embedders and last
  layer;
* no T5-XXL encoder: its states are standard normal, from a generator
  seeded by the prompt's CRC-32 (the program draws the same numbers);
* the hash tokenizer of ``sd_stream`` for CLIP;
* TAEF1 emits and takes the transformer's own latents: the AE's factors
  (scaling 0.3611, shift 0.1159) are not applied on either side;
* the noise levels are diffusers' ``FlowMatchEulerDiscreteScheduler`` ladder
  at the file's ``shift`` (1.0 for schnell: ``linspace(1, 1/1000, 50)``)
  at ``t_index_list``, where BFL's sampler takes ``linspace(1, 0, n + 1)``;
  each row is re-noised with its stage's fixed noise (StreamDiffusion's
  stream batch), where the sampler steps from one level to the next.

Imports ``nn``, ``models`` (the text tower, TAESD) and ``layout`` beside
``sd_stream``'s tokenizer and ``sd3_stream``'s flow ladder; nothing of the
program.
"""

from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp
import numpy as np

from . import layout, models, nn
from .sd3_stream import sigmas
from .sd_stream import hash_tokens

EPS = 1e-6


def _lin(i, o):
    return {"kernel": (i, o), "bias": (o,)}


def transformer_shapes(t: dict) -> dict:
    w = t["num_attention_heads"] * t["attention_head_dim"]
    d = t["attention_head_dim"]
    m = int(w * t["mlp_ratio"])
    qk = lambda: {"query_norm": {"scale": (d,)}, "key_norm": {"scale": (d,)}}  # noqa: E731
    stream = lambda: {  # noqa: E731
        "mod": {"lin": _lin(w, 6 * w)},
        "attn": {"qkv": _lin(w, 3 * w), "norm": qk(), "proj": _lin(w, w)},
        "mlp": {"proj": _lin(w, m), "out": _lin(m, w)},
    }
    double = []
    for _ in range(t["num_layers"]):
        img, txt = stream(), stream()
        double.append({f"{s}_{part}": st[part] for s, st in (("img", img), ("txt", txt))
                       for part in ("mod", "attn", "mlp")})
    return {
        "img_in": _lin(t["in_channels"], w),
        "txt_in": _lin(t["joint_attention_dim"], w),
        "time_in": {"in_layer": _lin(256, w), "out_layer": _lin(w, w)},
        "vector_in": {"in_layer": _lin(t["pooled_projection_dim"], w), "out_layer": _lin(w, w)},
        "double_blocks": double,
        "single_blocks": [
            {
                "modulation": {"lin": _lin(w, 3 * w)},
                "linear1": _lin(w, 3 * w + m),
                "norm": qk(),
                "linear2": _lin(w + m, w),
            }
            for _ in range(t["num_single_layers"])
        ],
        "final_layer": {"adaLN_modulation": _lin(w, 2 * w), "linear": _lin(w, t["in_channels"])},
    }


def weight_shapes(cfg: dict) -> dict:
    return {
        "flux": transformer_shapes(cfg["transformer"]),
        "clip": layout.clip_text(cfg["text_encoder"]),
        "taesd": layout.taesd(cfg["vae"]),
    }


# -- the transformer ----------------------------------------------------------

def _norm(x):
    """LayerNorm without affine."""
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + EPS)


def _rms(p, x):
    return x / jnp.sqrt((x ** 2).mean(-1, keepdims=True) + EPS) * nn.f32(p["scale"])


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _mlp(p, x):
    return nn.dense(p["out"], _gelu_tanh(nn.dense(p["proj"], x)))


def _embedder(p, x):
    return nn.dense(p["out_layer"], nn.silu(nn.dense(p["in_layer"], x)))


def timestep_embedding(t, dim: int = 256, max_period: float = 10000.0):
    """``flux.modules.layers.timestep_embedding`` of ``t`` already times
    1000: [cos | sin] at frequencies ``exp(-ln(max_period) * i / half)``."""
    half = dim // 2
    freqs = jnp.exp(-math.log(max_period) * jnp.arange(half, dtype=jnp.float32) / half)
    args = t[:, None].astype(jnp.float32) * freqs[None]
    return jnp.concatenate([jnp.cos(args), jnp.sin(args)], axis=-1)


def positions(text_tokens: int, gh: int, gw: int) -> np.ndarray:
    """``sampling.prepare``'s ids, text first: [L, 3], text (0, 0, 0), the
    image token of row r and column c (0, r, c)."""
    img = np.zeros((gh, gw, 3))
    img[..., 1] = np.arange(gh)[:, None]
    img[..., 2] = np.arange(gw)[None, :]
    return np.concatenate([np.zeros((text_tokens, 3)), img.reshape(-1, 3)])


def rope(ids: np.ndarray, axes: list, theta: float) -> np.ndarray:
    """``EmbedND``: [L, D/2, 2, 2], each pair's rotation matrix
    [[cos, -sin], [sin, cos]], the axes' pairs concatenated in order."""
    mats = []
    for i, d in enumerate(axes):
        omega = 1.0 / theta ** (np.arange(0, d, 2, dtype=np.float64) / d)
        a = ids[:, i, None] * omega[None]
        mats.append(np.stack([np.cos(a), -np.sin(a), np.sin(a), np.cos(a)], -1))
    return np.concatenate(mats, axis=1).reshape(ids.shape[0], -1, 2, 2)


def apply_rope(x, pe):
    """``math.apply_rope``: x [B, H, L, D], pe [L, D/2, 2, 2]."""
    xp = x.reshape(*x.shape[:-1], -1, 1, 2)
    out = pe[..., 0] * xp[..., 0] + pe[..., 1] * xp[..., 1]
    return out.reshape(x.shape)


def attention(q, k, v, pe):
    """``math.attention``: q, k, v [B, H, L, D] -> [B, L, H*D]."""
    q, k = apply_rope(q, pe), apply_rope(k, pe)
    o = nn.softmax_attention(*(a.transpose(0, 2, 1, 3) for a in (q, k, v)))
    b, l, h, d = o.shape
    return o.reshape(b, l, h * d)


def _qkv(qkv, norm, heads: int):
    """``"B L (K H D) -> K B H L D"``, then ``QKNorm``."""
    b, l, _ = qkv.shape
    q, k, v = qkv.reshape(b, l, 3, heads, -1).transpose(2, 0, 3, 1, 4)
    return _rms(norm["query_norm"], q), _rms(norm["key_norm"], k), v


def _modulation(p, vec, n: int):
    return jnp.split(nn.dense(p["lin"], nn.silu(vec))[:, None, :], n, axis=-1)


def double_block(p, img, txt, vec, pe, heads: int):
    ish1, isc1, ig1, ish2, isc2, ig2 = _modulation(p["img_mod"], vec, 6)
    tsh1, tsc1, tg1, tsh2, tsc2, tg2 = _modulation(p["txt_mod"], vec, 6)
    iq, ik, iv = _qkv(nn.dense(p["img_attn"]["qkv"], (1 + isc1) * _norm(img) + ish1),
                      p["img_attn"]["norm"], heads)
    tq, tk, tv = _qkv(nn.dense(p["txt_attn"]["qkv"], (1 + tsc1) * _norm(txt) + tsh1),
                      p["txt_attn"]["norm"], heads)
    a = attention(*(jnp.concatenate(pair, axis=2) for pair in ((tq, iq), (tk, ik), (tv, iv))), pe)
    ta, ia = a[:, : txt.shape[1]], a[:, txt.shape[1]:]
    img = img + ig1 * nn.dense(p["img_attn"]["proj"], ia)
    img = img + ig2 * _mlp(p["img_mlp"], (1 + isc2) * _norm(img) + ish2)
    txt = txt + tg1 * nn.dense(p["txt_attn"]["proj"], ta)
    txt = txt + tg2 * _mlp(p["txt_mlp"], (1 + tsc2) * _norm(txt) + tsh2)
    return img, txt


def single_block(p, x, vec, pe, heads: int):
    sh, sc, g = _modulation(p["modulation"], vec, 3)
    y = nn.dense(p["linear1"], (1 + sc) * _norm(x) + sh)
    w = x.shape[-1]
    q, k, v = _qkv(y[..., : 3 * w], p["norm"], heads)
    a = attention(q, k, v, pe)
    return x + g * nn.dense(p["linear2"], jnp.concatenate([a, _gelu_tanh(y[..., 3 * w:])], -1))


def transformer(p, x, timesteps, txt_states, pooled, t: dict):
    """x [B,h,w,16], timesteps [B] (1000 * sigma), txt_states [B,L,4096],
    pooled [B,768] -> the velocity [B,h,w,16]."""
    n, h, w, c = x.shape
    heads = t["num_attention_heads"]
    img = x.reshape(n, h // 2, 2, w // 2, 2, c).transpose(0, 1, 3, 5, 2, 4)
    img = nn.dense(p["img_in"], img.reshape(n, (h // 2) * (w // 2), 4 * c))
    vec = _embedder(p["time_in"], timestep_embedding(timesteps))
    vec = vec + _embedder(p["vector_in"], pooled)
    txt = nn.dense(p["txt_in"], txt_states)
    lt = txt.shape[1]
    pe = jnp.asarray(
        rope(positions(lt, h // 2, w // 2), t["axes_dims_rope"], t["theta"]), jnp.float32
    )
    for b in p["double_blocks"]:
        img, txt = double_block(b, img, txt, vec, pe, heads)
    z = jnp.concatenate([txt, img], axis=1)
    for b in p["single_blocks"]:
        z = single_block(b, z, vec, pe, heads)
    fl = p["final_layer"]
    shift, scale = jnp.split(nn.dense(fl["adaLN_modulation"], nn.silu(vec))[:, None, :], 2, -1)
    z = nn.dense(fl["linear"], (1 + scale) * _norm(z[:, lt:]) + shift)
    z = z.reshape(n, h // 2, w // 2, c, 2, 2).transpose(0, 1, 4, 2, 5, 3)
    return z.reshape(n, h, w, c)


# -- the text side -------------------------------------------------------------

def t5_stand_in(prompt: str, tokens: int, width: int) -> np.ndarray:
    """[1, tokens, width]: standard normal from ``numpy``'s default generator
    seeded by the prompt's CRC-32, in T5-XXL's place."""
    rng = np.random.default_rng(zlib.crc32(prompt.encode("utf-8")))
    return rng.standard_normal((1, tokens, width), dtype=np.float32)


def clip_pooled(p, token_ids, t: dict):
    """``CLIPTextModel``'s ``pooler_output``: the last layer's final-normed
    state at the end-of-text token (the highest id, so the first argmax)."""
    final = nn.layer_norm(p["final_norm"], models.clip_layers(p, token_ids, t)[-1])
    return final[jnp.arange(final.shape[0]), jnp.argmax(token_ids, axis=-1)]


# -- the stream ----------------------------------------------------------------

def _col(v):
    return jnp.asarray(v, jnp.float32).reshape(-1, 1, 1, 1)


class Reference:
    """The reference for one configuration: the weight tree
    ``{"flux","clip","taesd"}`` in the dtype it is served in (each leaf
    widened to float32 where it is read) and one jitted step shared by its
    sessions."""

    def __init__(self, cfg: dict, weights: dict):
        self.cfg, self.s, self.w = cfg, cfg["stream"], weights
        self.sigma = sigmas(self.s)
        self._step = jax.jit(self._step_fn)

    @property
    def stateful(self) -> bool:
        return len(self.s["t_index_list"]) > 1

    @property
    def warm_in_steps(self) -> int:
        """No guidance, no running stock: the ring forgets in as many steps
        as it has stages."""
        return len(self.s["t_index_list"]) if self.stateful else 0

    def session(self, prompt: str, seed: int) -> "Session":
        return Session(self, prompt, seed)

    def encode_prompt(self, prompt: str) -> dict:
        """-> {"ctx" [1, text_tokens, joint_attention_dim], "pooled" [1, 768]}."""
        t, t5 = self.cfg["text_encoder"], self.cfg["text_encoder_2"]
        ids = jnp.asarray(
            [hash_tokens(prompt, t["vocab_size"], t["max_position_embeddings"])], jnp.int32
        )
        with jax.default_matmul_precision("highest"):
            pooled = clip_pooled(self.w["clip"], ids, t)
        ctx = t5_stand_in(prompt, t5["max_sequence_length"], t5["d_model"])
        return {"ctx": jnp.asarray(ctx), "pooled": pooled}

    def _velocity(self, w, x_t, cond):
        rows = x_t.shape[0]
        return transformer(
            w["flux"], x_t, jnp.asarray(1000.0 * self.sigma, jnp.float32),
            jnp.broadcast_to(cond["ctx"], (rows,) + cond["ctx"].shape[1:]),
            jnp.broadcast_to(cond["pooled"], (rows,) + cond["pooled"].shape[1:]),
            self.cfg["transformer"],
        )

    def _step_fn(self, w, cond, noise, ring, frame_u8):
        s = self.sigma
        img = frame_u8.astype(jnp.float32)[None] / 255.0
        z0 = models.taesd_encode(w["taesd"]["encoder"], img)
        x_new = (1.0 - s[0]) * z0 + s[0] * noise[:1]
        x_t = jnp.concatenate([x_new, ring], axis=0)
        x0 = x_t - _col(s) * self._velocity(w, x_t, cond)
        nxt = s[1:]
        new_ring = _col(1.0 - nxt) * x0[:-1] + _col(nxt) * noise[1:]
        out = models.taesd_decode(w["taesd"]["decoder"], x0[-1:])
        return new_ring, jnp.clip(out[0] * 255.0, 0.0, 255.0)


class Session:
    """One session's reference stream, from the moment it is claimed."""

    def __init__(self, ref: Reference, prompt: str, seed: int):
        s = ref.s
        self.ref = ref
        B = len(s["t_index_list"])
        h, w = s["height"] // s["latent_scale"], s["width"] // s["latent_scale"]
        # the session's fixed noise, drawn from the session seed in the
        # configuration's dtype as the program draws it, carried in float32
        self.noise = jax.random.normal(
            jax.random.PRNGKey(seed), (B, h, w, ref.cfg["vae"]["latent_channels"]),
            jnp.dtype(s["dtype"]),
        ).astype(jnp.float32)
        self.ring = self.noise[1:]
        self.stock = jnp.zeros((0,), jnp.float32)  # no guidance: nothing carried
        self.cond = ref.encode_prompt(prompt)

    def step(self, frame_u8: np.ndarray) -> np.ndarray:
        with jax.default_matmul_precision("highest"):
            self.ring, out = self.ref._step(
                self.ref.w, self.cond, self.noise, self.ring, jnp.asarray(frame_u8),
            )
        return np.asarray(out)
