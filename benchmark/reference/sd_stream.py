"""The stream-batch img2img step (StreamDiffusion) as a plain float32
reference: one session, frame after frame, from the moment it is claimed.

Per frame: uint8 -> [0,1]; TAESD encode; noise to the first sub-timestep;
put the new latent in front of the ring of latents that are part-way through
their denoising stages; one UNet pass over that batch; (R-CFG "self":
combine with the stock noise and refresh it); LCM consistency step (or the
1-step turbo ``pred_x0``); the last row leaves the ring and is decoded, the
others are re-noised to their next sub-timestep.  The equations are those of
the StreamDiffusion paper (stream batch, residual CFG) and of the LCM paper
(boundary-condition coefficients with timestep scaling 10, sigma_data 0.5).

The hash tokenizer is the one a weight-less (random-weight) deployment of
the program falls back to; the reference keeps its own copy because the
token ids are part of the input, not of the computation.
"""

from __future__ import annotations

import re

import jax
import jax.numpy as jnp
import numpy as np

from . import models
from .layout import weight_shapes  # noqa: F401  (a reference module's second name)


def hash_tokens(text: str, vocab_size: int, max_length: int) -> list:
    bos, eos = vocab_size - 2, vocab_size - 1
    ids = [bos]
    for word in re.findall(r"\w+", text.lower()):
        h = 0
        for ch in word:
            h = (h * 131 + ord(ch)) % (vocab_size - 2)
        ids.append(h)
    ids = ids[: max_length - 1] + [eos]
    return ids + [eos] * (max_length - len(ids))


def sub_timesteps(s: dict) -> np.ndarray:
    T, n = s["num_train_timesteps"], s["num_inference_steps"]
    if s["timestep_spacing"] == "leading":
        ladder = (np.arange(n) * (T // n))[::-1]
    elif s["timestep_spacing"] == "trailing":
        ladder = np.round(T - np.arange(n) * (T / n)).astype(np.int64) - 1
    else:
        raise ValueError(s["timestep_spacing"])
    return ladder[np.asarray(s["t_index_list"])]


def coefficients(s: dict) -> dict:
    """Per-stage scheduler constants, float64 on the host."""
    T = s["num_train_timesteps"]
    if s["beta_schedule"] != "scaled_linear":
        raise ValueError(s["beta_schedule"])
    betas = np.linspace(s["beta_start"] ** 0.5, s["beta_end"] ** 0.5, T) ** 2
    abar = np.cumprod(1.0 - betas)
    t = sub_timesteps(s)
    alpha, sigma = np.sqrt(abar[t]), np.sqrt(1.0 - abar[t])
    scaled = t / 10.0
    c_skip = 0.25 / (scaled**2 + 0.25)
    c_out = scaled / np.sqrt(scaled**2 + 0.25)
    if s["scheduler"] == "turbo":  # the 1-step turbo output is pred_x0 itself
        c_skip, c_out = np.zeros_like(c_skip), np.ones_like(c_out)
    nxt_alpha = np.append(alpha[1:], 1.0)
    nxt_sigma = np.append(sigma[1:], 0.0)
    return {
        "t": t, "alpha": alpha, "sigma": sigma, "c_skip": c_skip,
        "c_out": c_out, "next_alpha": nxt_alpha, "next_sigma": nxt_sigma,
    }


def _col(v):
    return jnp.asarray(v, jnp.float32).reshape(-1, 1, 1, 1)


class Reference:
    """The reference for one configuration: the weight tree
    ``{"unet","clip","taesd"}`` in the dtype it is served in (each leaf is
    widened to float32 where it is read, ``nn.f32``) and one jitted step
    shared by its sessions.  ``cfg``: the parsed configuration file."""

    def __init__(self, cfg: dict, weights: dict):
        self.cfg, self.s, self.w = cfg, cfg["stream"], weights
        self.k = coefficients(self.s)
        self._step = jax.jit(self._step_fn)

    @property
    def stateful(self) -> bool:
        """Whether a frame's output depends on the frames before it."""
        return len(self.s["t_index_list"]) > 1 or self.s["cfg_type"] != "none"

    @property
    def warm_in_steps(self) -> int:
        """Steps after which a session started afresh has the state of one
        followed from its claim, to a thousandth of the noise prediction.
        The latent ring forgets in as many steps as it has stages.  The R-CFG
        stock noise is a running mean, ``(eps + beta * stock) / (1 + beta)``:
        what it started from fades by ``beta / (1 + beta)`` a step (0.71 at
        t = 620) and enters the prediction times ``(guidance - 1) * delta``."""
        if not self.stateful:
            return 0
        stages = len(self.s["t_index_list"])
        if self.s["cfg_type"] != "self":
            return stages
        beta = self.k["sigma"] / np.maximum(self.k["alpha"], 1e-6)
        fade = float(np.max(beta / (1.0 + beta)))
        weight = abs(self.s["guidance_scale"] - 1.0) * abs(self.s["delta"])
        if weight <= 1e-3:
            return stages
        return stages + int(np.ceil(np.log(1e-3 / weight) / np.log(fade)))

    def session(self, prompt: str, seed: int) -> "Session":
        return Session(self, prompt, seed)

    def encode_prompt(self, prompt: str):
        """What a session's prompt conditions its steps on: here the text
        tower's hidden states [1,L,width]; a family's own, whatever its
        ``_conditioning`` reads."""
        t = self.cfg["text_encoder"]
        ids = hash_tokens(prompt, t["vocab_size"], t["max_position_embeddings"])
        with jax.default_matmul_precision("highest"):
            return models.clip_text(
                self.w["clip"], jnp.asarray([ids], jnp.int32), t
            )

    def _conditioning(self, w, cond):
        """-> (cross-attention context [1,L,cross], addition embedding
        [1,temb] or None) of a session, from what ``encode_prompt`` gave."""
        return cond, None

    def _eps(self, w, x_t, cond):
        """The UNet's noise prediction for the stream batch ``x_t`` [B,h,w,4],
        every row under the session's one prompt."""
        def rows(a):
            return jnp.broadcast_to(a, x_t.shape[:1] + a.shape[1:])

        ctx, added = self._conditioning(w, cond)
        return models.unet(
            w["unet"], x_t, jnp.asarray(self.k["t"], jnp.int32), rows(ctx),
            self.cfg["unet"], added=None if added is None else rows(added),
        )

    def _step_fn(self, w, cond, noise, ring, stock, frame_u8):
        s, k = self.s, self.k
        img = frame_u8.astype(jnp.float32)[None] / 255.0
        z0 = models.taesd_encode(w["taesd"]["encoder"], img)
        x_new = k["alpha"][0] * z0 + k["sigma"][0] * noise[:1]
        x_t = jnp.concatenate([x_new, ring], axis=0)
        eps_c = self._eps(w, x_t, cond)
        if s["cfg_type"] == "none":
            eps, new_stock = eps_c, stock
        elif s["cfg_type"] == "self":
            g, d = s["guidance_scale"], s["delta"]
            eps = g * eps_c - (g - 1.0) * d * stock
            beta = _col(k["sigma"] / np.maximum(k["alpha"], 1e-6))
            new_stock = (eps_c + beta * stock) / (1.0 + beta)
        else:
            raise ValueError(s["cfg_type"])
        x0 = (x_t - _col(k["sigma"]) * eps) / _col(k["alpha"])
        den = _col(k["c_skip"]) * x_t + _col(k["c_out"]) * x0
        new_ring = (
            _col(k["next_alpha"][:-1]) * den[:-1]
            + _col(k["next_sigma"][:-1]) * noise[1:]
        )
        out = models.taesd_decode(w["taesd"]["decoder"], den[-1:])
        return new_ring, new_stock, jnp.clip(out[0] * 255.0, 0.0, 255.0)


class Session:
    """One session's reference stream, from the moment it is claimed."""

    def __init__(self, ref: Reference, prompt: str, seed: int):
        s = ref.s
        self.ref = ref
        B = len(s["t_index_list"])
        h, w = s["height"] // s["latent_scale"], s["width"] // s["latent_scale"]
        # the session's fixed noise: a standard normal drawn from the
        # session seed in the configuration's dtype (part of the state a
        # session is given, like its prompt), then carried in float32
        self.noise = jax.random.normal(
            jax.random.PRNGKey(seed), (B, h, w, 4), jnp.dtype(s["dtype"])
        ).astype(jnp.float32)
        self.ring = self.noise[1:]
        self.stock = jnp.zeros_like(self.noise)
        self.cond = ref.encode_prompt(prompt)

    def step(self, frame_u8: np.ndarray) -> np.ndarray:
        """One frame in, that step's output frame out: float32 [H,W,3] in
        uint8 levels, unrounded."""
        with jax.default_matmul_precision("highest"):
            self.ring, self.stock, out = self.ref._step(
                self.ref.w, self.cond, self.noise, self.ring, self.stock,
                jnp.asarray(frame_u8),
            )
        return np.asarray(out)
