"""Noise schedules and timestep machinery (pure functions, fp32 by default).

TPU-native replacement for the scheduler config the reference pulls from
diffusers (``DEISMultistepScheduler`` config-load at reference
lib/wrapper.py:474-481) plus the t-index -> sub-timestep surgery the wrapper
performs itself (reference lib/wrapper.py:389-407, prepare() at :197-234).

Everything here is a pure function of static python ints + arrays so it can
be called at trace time inside a jitted graph or ahead of time on host.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Sequence

import jax.numpy as jnp
import numpy as np

# SD-1.x / SD-2.x / SDXL training schedule constants (the "scaled_linear"
# schedule all Stable Diffusion variants are trained with).
DEFAULT_TRAIN_STEPS = 1000
DEFAULT_BETA_START = 0.00085
DEFAULT_BETA_END = 0.012


@dataclass(frozen=True)
class NoiseSchedule:
    """Precomputed diffusion schedule tables (host numpy, cast on use).

    alphas_cumprod[t] is \\bar{alpha}_t for t in [0, num_train_steps).
    """

    num_train_steps: int
    alphas_cumprod: np.ndarray  # [T] fp64
    betas: np.ndarray  # [T] fp64

    @property
    def final_alpha_cumprod(self) -> float:
        # \bar{alpha}_{-1} := 1 (fully clean), used when stepping past t=0.
        return 1.0


def make_schedule(
    num_train_steps: int = DEFAULT_TRAIN_STEPS,
    beta_start: float = DEFAULT_BETA_START,
    beta_end: float = DEFAULT_BETA_END,
    kind: str = "scaled_linear",
) -> NoiseSchedule:
    t = np.arange(num_train_steps, dtype=np.float64)
    if kind == "scaled_linear":
        betas = (
            np.linspace(beta_start**0.5, beta_end**0.5, num_train_steps, dtype=np.float64)
            ** 2
        )
    elif kind == "linear":
        betas = np.linspace(beta_start, beta_end, num_train_steps, dtype=np.float64)
    else:
        raise ValueError(f"unknown schedule kind: {kind}")
    del t
    alphas = 1.0 - betas
    alphas_cumprod = np.cumprod(alphas)
    return NoiseSchedule(num_train_steps, alphas_cumprod, betas)


def inference_timesteps(
    num_inference_steps: int,
    num_train_steps: int = DEFAULT_TRAIN_STEPS,
    spacing: str = "leading",
) -> np.ndarray:
    """The descending timestep ladder for ``num_inference_steps`` steps.

    ``leading`` matches the classic DDIM/LCM spacing the reference's default
    50-step ladder uses: t_i = (T // n) * i, returned descending, so
    ``timesteps[t_index]`` reproduces the mapping at reference
    lib/wrapper.py:394-399 (``self.timesteps[t] for t in t_index_list``).
    ``trailing`` is the SD-Turbo convention: t_i = round(T - i * T/n) - 1.
    """
    T, n = num_train_steps, num_inference_steps
    if n < 1 or n > T:
        raise ValueError(f"num_inference_steps must be in [1, {T}], got {n}")
    if spacing == "leading":
        # ascending by construction -> reverse to descending
        ts = (np.arange(n) * (T // n)).round().astype(np.int64)[::-1]
    elif spacing == "trailing":
        # descending by construction (t_0 = T-1)
        ts = np.round(T - np.arange(n) * (T / n)).astype(np.int64) - 1
    else:
        raise ValueError(f"unknown spacing: {spacing}")
    assert n == 1 or ts[0] > ts[-1], "timesteps must be descending"
    return ts.copy()  # descending: most-noisy first


def sub_timesteps(
    t_index_list: Sequence[int],
    num_inference_steps: int,
    num_train_steps: int = DEFAULT_TRAIN_STEPS,
    spacing: str = "leading",
) -> np.ndarray:
    """t_index_list -> ascending-noise-order sub timesteps.

    Reference semantics (lib/wrapper.py:394-399): indexes into the *ascending*
    view of the ladder, i.e. t_index 18 of 50 selects a mid-noise timestep and
    45 selects a high-index (low-noise) one...  Concretely the reference does
    ``self.timesteps = scheduler.timesteps`` (descending) then
    ``sub_timesteps = [timesteps[t] for t in t_index_list]`` — larger t_index
    = later position in the descending ladder = LESS noise.  The stream batch
    therefore runs sub_timesteps[0] (most noise, newest frame) ... [-1] (least
    noise, frame about to leave).  We reproduce exactly that.
    """
    ts = inference_timesteps(num_inference_steps, num_train_steps, spacing)
    return ts[_t_indices(t_index_list, num_inference_steps)]


def _t_indices(t_index_list: Sequence[int], num_inference_steps: int) -> np.ndarray:
    idx = np.asarray(list(t_index_list), dtype=np.int64)
    if idx.ndim != 1 or len(idx) == 0:
        raise ValueError("t_index_list must be a non-empty 1-D sequence")
    if (idx < 0).any() or (idx >= num_inference_steps).any():
        raise ValueError(
            f"t_index_list entries must be in [0, {num_inference_steps}), got {idx}"
        )
    if (np.diff(idx) <= 0).any():
        raise ValueError(f"t_index_list must be strictly increasing, got {idx}")
    return idx


def flow_sigmas(
    num_inference_steps: int, shift: float,
    num_train_steps: int = DEFAULT_TRAIN_STEPS,
) -> np.ndarray:
    """The descending noise levels of a rectified-flow model's
    ``num_inference_steps``-step ladder: diffusers'
    ``FlowMatchEulerDiscreteScheduler(shift=shift).set_timesteps(n)``, with
    the shift the model's scheduler config states (SD3 3.0; FLUX.1-schnell
    1.0, where the ladder is the training ladder's ends spanned evenly).
    The training ladder is ``s = t / T`` for t = T .. 1, shifted as
    ``shift * s / (1 + (shift - 1) * s)``; the inference ladder spans its
    ends evenly in unshifted units (the smallest level is the shifted one,
    read as unshifted) and is shifted again: sigma_0 = 1, pure noise."""
    T = num_train_steps

    def shifted(s):
        return shift * s / (1.0 + (shift - 1.0) * s)

    s = np.linspace(1.0, shifted(1.0 / T), num_inference_steps, dtype=np.float64)
    return shifted(s)


def flow_sub_sigmas(
    t_index_list: Sequence[int], num_inference_steps: int, shift: float
) -> np.ndarray:
    """t_index_list -> each stage's noise level on the flow ladder, noisiest
    first: :func:`sub_timesteps` for a rectified-flow model."""
    idx = _t_indices(t_index_list, num_inference_steps)
    return flow_sigmas(num_inference_steps, shift)[idx]


def batched_sub_timesteps(
    t_index_list: Sequence[int],
    num_inference_steps: int,
    frame_buffer_size: int = 1,
    num_train_steps: int = DEFAULT_TRAIN_STEPS,
    spacing: str = "leading",
) -> np.ndarray:
    """``repeat_interleave`` of sub timesteps by frame_buffer_size.

    Mirrors the stream-batch law ``batch = len(t_index_list) *
    frame_buffer_size`` (reference lib/wrapper.py:159-163) and the
    repeat_interleave at :400-407: batch entry b = sub_timesteps[b // fbs].
    """
    st = sub_timesteps(t_index_list, num_inference_steps, num_train_steps, spacing)
    return np.repeat(st, frame_buffer_size)


def alpha_sigma(schedule: NoiseSchedule, timesteps) -> tuple[jnp.ndarray, jnp.ndarray]:
    """sqrt(\\bar{alpha}_t) and sqrt(1-\\bar{alpha}_t) for integer timesteps.

    ``timesteps`` may be any integer array (device or host); t == -1 (or any
    negative) means "clean" and maps to alpha=1, sigma=0.
    """
    table = jnp.asarray(schedule.alphas_cumprod, dtype=jnp.float32)
    t = jnp.asarray(timesteps)
    clean = t < 0
    tc = jnp.clip(t, 0, schedule.num_train_steps - 1)
    ac = jnp.where(clean, 1.0, table[tc])
    return jnp.sqrt(ac), jnp.sqrt(1.0 - ac)


def add_noise(schedule: NoiseSchedule, x0, noise, timesteps):
    """q(x_t | x_0): alpha*x0 + sigma*noise, broadcasting over batch.

    Mirrors ``scheduler.add_noise`` as used at reference lib/wrapper.py:317
    (input-frame noising) — but as a pure function usable in-graph.
    """
    a, s = alpha_sigma(schedule, timesteps)
    shape = (-1,) + (1,) * (x0.ndim - 1)
    return a.reshape(shape).astype(x0.dtype) * x0 + s.reshape(shape).astype(
        x0.dtype
    ) * noise
