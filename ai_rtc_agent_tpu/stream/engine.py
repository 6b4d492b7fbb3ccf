"""The stream-batch denoising engine — heart of the framework.

TPU-native replacement for the external ``StreamDiffusion`` core the
reference drives at lib/wrapper.py:494-512 / :330 (stream batch, LCM step,
R-CFG, prompt cache) — re-designed as ONE jit-compiled pure function:

    step(params, state, frame_u8) -> (state', out_u8)

* The latent ring buffer, stock noise, prompt embeddings and scheduler
  coefficient vectors all live in ``state`` (a dict pytree of device
  arrays).  The state is DONATED every call, so the ring buffer rotates
  in-place in HBM with zero copies.
* Prompt updates and same-length t_index updates are state swaps — no
  retrace, no recompile (recompilation discipline per SURVEY.md section 7).
* uint8 pre/post-processing happens in-graph (ops/image.py), so exactly one
  uint8 [H,W,3] crosses host->device and one [H,W,3] crosses device->host
  per frame — the TPU analog of the reference's NVDEC/NVENC zero-copy
  property (reference README.md:11-15).

Stream-batch semantics (reference batch law lib/wrapper.py:159-163):
  batch B = len(t_index_list) * frame_buffer_size.  Each call consumes
  frame_buffer_size new frames at the noisiest sub-timestep, advances every
  buffered latent one denoising stage, and emits the frames that just
  completed the final stage — per-frame latency of ONE UNet pass while
  getting len(t_index_list)-step quality.
"""

from __future__ import annotations

import math
import os
import threading
from dataclasses import dataclass, field
from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from ..obs import devtel
from ..ops import image as I
from ..ops import lcm as L
from ..ops import rcfg as R
from ..ops import schedule as S


@dataclass(frozen=True)
class StreamConfig:
    """Static (compile-time) stream configuration."""

    mode: str = "img2img"  # img2img | txt2img
    height: int = 512
    width: int = 512
    latent_scale: int = 8  # image/latent resolution ratio (TAESD: 8)
    latent_channels: int = 4
    t_index_list: tuple = (18, 26, 35, 45)
    num_inference_steps: int = 50
    frame_buffer_size: int = 1
    cfg_type: str = "self"  # none | full | self | initialize
    use_denoising_batch: bool = True
    do_add_noise: bool = True
    prediction_type: str = "epsilon"
    scheduler: str = "lcm"  # lcm | turbo
    timestep_spacing: str = "leading"
    dtype: str = "float32"  # compute dtype: float32 | bfloat16
    similar_image_filter: bool = False
    similar_image_threshold: float = 0.98
    similar_image_max_skip: int = 10
    # SDXL-style "text_time" addition conditioning: pooled text embeds +
    # micro-conditioning time_ids travel in state (prompt swaps, no retrace)
    use_added_cond: bool = False
    # ControlNet conditioned generation (reference lib/wrapper.py:617-643):
    # the annotator runs IN-GRAPH on the incoming frame; conditioning images
    # ride a ring buffer in state aligned with the latent ring.
    use_controlnet: bool = False
    annotator: str = "canny"  # canny | hed | identity
    # Fuse the whole post-UNet scheduler chain (R-CFG combine -> LCM blend ->
    # ring renoise -> stock update) into ONE Pallas kernel: a single HBM
    # read/write of the latent slabs instead of 6+ elementwise passes
    # (BASELINE north star: "Pallas for ... the LCM scheduler step").
    # Supported for epsilon-prediction + cfg_type none/self/initialize in
    # denoising-batch mode; other combos fall back to composed XLA ops.
    use_fused_epilogue: bool = False
    # Attention implementation baked into the traced graph ("" = resolve
    # from ATTN_IMPL env / backend via current_attn_impl()).  Carried in the
    # config so the AOT cache key, the bundle builder and the serving
    # fallback agree WITHOUT mutating process-global env (a fallback on one
    # pipeline must not silently disable Pallas for pipelines built later).
    attn_impl: str = ""
    # DeepCache-style temporal UNet feature reuse (UNET_CACHE env / --unet-
    # cache): every Nth step runs the full UNet and captures the feature
    # entering the outermost up block; the N-1 steps between recompute only
    # the outermost tier and splice the cache in.  Sound for the stream
    # batch because slot i ALWAYS denoises at timestep t_i — the cached
    # deep features stay timestep-aligned across steps.  0/1 = off.
    # Opt-in: video coherence makes the approximation good in practice, but
    # fast scene cuts briefly reuse stale deep features until the next full
    # step.  Incompatible with ControlNet (residuals feed the skipped deep
    # blocks) and sequential (non-stream-batch) mode.
    unet_cache_interval: int = 0

    @property
    def n_stages(self) -> int:
        return len(self.t_index_list)

    @property
    def batch_size(self) -> int:
        # the stream-batch law (reference lib/wrapper.py:159-163)
        return self.n_stages * self.frame_buffer_size

    @property
    def latent_hw(self) -> tuple:
        return (self.height // self.latent_scale, self.width // self.latent_scale)

    @property
    def jdtype(self):
        return jnp.bfloat16 if self.dtype == "bfloat16" else jnp.float32


@dataclass
class StreamModels:
    """Apply-fn bundle the engine drives (duck-typed, so any model family —
    SD1.5/SD2.1/SDXL/ControlNet variants — plugs in).

    unet(params, x, t, context, added_cond) -> model_out   [B,h,w,4]
    vae_encode(params, img01_nhwc) -> latents              [N,h,w,4]
    vae_decode(params, latents) -> img01_nhwc              [N,H,W,3]
    controlnet(params, x, t, context, cond_img, added_cond, scale)
        -> (down_residuals, mid_residual)                  [optional]
    """

    unet: Callable
    vae_encode: Callable
    vae_decode: Callable
    controlnet: Callable | None = None
    # DeepCache pair (None = family doesn't support it):
    #   unet_capture(params, x, t, context, added) -> (model_out, deep_h)
    #   unet_cached(params, x, t, context, added, deep_h) -> model_out
    unet_capture: Callable | None = None
    unet_cached: Callable | None = None


def _coeff_state(cfg: StreamConfig, schedule: S.NoiseSchedule, t_index_list):
    bt = S.batched_sub_timesteps(
        list(t_index_list),
        cfg.num_inference_steps,
        cfg.frame_buffer_size,
        spacing=cfg.timestep_spacing,
    )
    c = L.make_step_coeffs(schedule, bt, cfg.frame_buffer_size)
    return {
        "timesteps": jnp.asarray(c.timesteps, jnp.int32),
        "alpha": jnp.asarray(c.alpha),
        "sigma": jnp.asarray(c.sigma),
        "c_skip": jnp.asarray(c.c_skip),
        "c_out": jnp.asarray(c.c_out),
        "next_alpha": jnp.asarray(c.next_alpha),
        "next_sigma": jnp.asarray(c.next_sigma),
    }


def _as_step_coeffs(d) -> L.StepCoeffs:
    return L.StepCoeffs(
        d["timesteps"], d["alpha"], d["sigma"], d["c_skip"], d["c_out"],
        d["next_alpha"], d["next_sigma"],
    )


def make_step_fn(models: StreamModels, cfg: StreamConfig,
                 unet_variant: str = "full"):
    """Build the pure step function (to be jitted/AOT-compiled by the caller).

    ``unet_variant``: "full" (plain), or the DeepCache pair — "capture"
    (full UNet; the deep feature lands in ``state['unet_cache']``) and
    "cached" (outermost-tier-only UNet consuming ``state['unet_cache']``).
    The engine alternates the two compiled steps on a host-side cadence
    (StreamConfig.unet_cache_interval) — static graphs, no data-dependent
    control flow under jit."""

    if cfg.use_controlnet and models.controlnet is None:
        raise ValueError(
            "cfg.use_controlnet=True but StreamModels.controlnet is None — "
            "load the bundle with a controlnet model id"
        )
    if unet_variant != "full":
        if models.unet_capture is None or models.unet_cached is None:
            raise ValueError(
                "unet_cache_interval set but this model bundle has no "
                "DeepCache apply pair (unet_capture/unet_cached)"
            )
        if cfg.use_controlnet:
            raise ValueError(
                "unet_cache_interval is incompatible with ControlNet "
                "(residuals feed the skipped deep blocks)"
            )
        if not cfg.use_denoising_batch:
            raise ValueError(
                "unet_cache_interval requires denoising-batch mode (the "
                "sequential path runs multiple timesteps per slot, so the "
                "per-slot timestep alignment the cache relies on is lost)"
            )
    B = cfg.batch_size
    fbs = cfg.frame_buffer_size
    dt = cfg.jdtype

    fused_ok = (
        cfg.use_fused_epilogue
        and cfg.use_denoising_batch
        and cfg.prediction_type == "epsilon"
        and cfg.cfg_type in ("none", "self", "initialize")
    )

    def unet_with_guidance(
        params, x_t, state, coeffs, stock, cond_img=None, return_raw=False
    ):
        """One guided UNet pass over x_t [xb, h, w, c]; xb may be the full
        stream batch (denoising-batch mode) or one stage slice (sequential
        mode).  Returns (eps, new_stock) with new_stock shaped like stock.
        ``cond_img`` [xb,H,W,3]: ControlNet conditioning aligned with x_t.
        ``return_raw``: skip the guidance combine + stock update and return
        the raw conditioned prediction (the fused epilogue kernel does the
        rest in one pass); only valid for cfg_type none/self/initialize."""
        xb = x_t.shape[0]

        def run_unet(x, t, ctx, a, cond):
            """-> (model_out, deep_h_or_None)."""
            if cond is not None:  # ControlNet path (unet_variant=="full")
                with jax.named_scope("controlnet"):
                    dres, mres = models.controlnet(
                        params, x, t, ctx, cond.astype(dt), a, state["cnet_scale"]
                    )
                with jax.named_scope("unet"):
                    return models.unet(
                        params, x, t, ctx, a,
                        down_residuals=dres, mid_residual=mres,
                    ), None
            with jax.named_scope("unet"):
                if unet_variant == "capture":
                    return models.unet_capture(params, x, t, ctx, a)
                if unet_variant == "cached":
                    return models.unet_cached(
                        params, x, t, ctx, a, state["unet_cache"]
                    ), None
                return models.unet(params, x, t, ctx, a), None

        t = coeffs.timesteps
        added = None
        if cfg.use_added_cond:
            added = {
                "time_ids": jnp.broadcast_to(
                    state["added_time_ids"], (xb,) + state["added_time_ids"].shape[1:]
                ),
                "text_embeds": jnp.broadcast_to(
                    state["added_text"], (xb,) + state["added_text"].shape[1:]
                ).astype(dt),
            }
        cond = jnp.broadcast_to(
            state["cond"], (xb,) + state["cond"].shape[1:]
        ).astype(dt)

        if cfg.cfg_type == "full":
            uncond = jnp.broadcast_to(
                state["uncond"], (xb,) + state["uncond"].shape[1:]
            ).astype(dt)
            x2 = jnp.concatenate([x_t, x_t], axis=0)
            t2 = jnp.concatenate([t, t], axis=0)
            ctx2 = jnp.concatenate([uncond, cond], axis=0)
            added2 = (
                jax.tree.map(lambda a: jnp.concatenate([a, a], 0), added)
                if added is not None
                else None
            )
            cond2 = (
                jnp.concatenate([cond_img, cond_img], axis=0)
                if cond_img is not None
                else None
            )
            out, new_cache = run_unet(x2, t2, ctx2, added2, cond2)
            with jax.named_scope("epilogue"):
                eps_u, eps_c = jnp.split(out, 2, axis=0)
                eps = R.combine_full(eps_u, eps_c, state["guidance"])
            new_stock = stock
        else:
            eps_c, new_cache = run_unet(x_t, t, cond, added, cond_img)
            if return_raw:
                return eps_c, stock, new_cache
            if cfg.cfg_type == "none":
                eps = eps_c
                new_stock = stock
            else:  # self | initialize
                with jax.named_scope("epilogue"):
                    eps = R.combine_residual(
                        eps_c, stock.astype(dt), state["guidance"], state["delta"]
                    )
                    if cfg.cfg_type == "self":
                        new_stock = R.update_stock_noise(
                            stock.astype(dt), eps_c, coeffs.alpha, coeffs.sigma
                        )
                    else:
                        new_stock = stock
        return eps, new_stock, new_cache

    def step(params, state, frame_u8):
        """frame_u8: [fbs,H,W,3] (or [H,W,3] when fbs==1) uint8 RGB.

        Every model part runs under a ``jax.named_scope`` (``preprocess``,
        ``vae_encode``, ``add_noise``, ``unet`` and its blocks,
        ``epilogue``, ``vae_decode``, ``postprocess``): metadata only —
        the compiled program and its numbers do not change — so that a
        profiler trace can say which part a microsecond of device time
        belongs to.  No scope is named after a Mosaic kernel: the trace
        readers find a kernel by its name inside an op's name."""
        coeffs = _as_step_coeffs(state["coeffs"])

        # ---- per-session style adapters (adapters/): graft the slot's
        # LoRA factor rows beside the target kernels so layers.linear
        # applies the low-rank residual per row INSIDE the (possibly
        # vmapped) step.  Pure pytree surgery at trace time — untouched
        # leaves keep identity, zero rows are a bitwise no-op, and the
        # factors ride `state` through donation like every other leaf.
        if "adapters" in state:
            from ..adapters import graft_unet_params

            params = dict(params)
            params["unet"] = graft_unet_params(
                params["unet"], state["adapters"]
            )

        # ---- encode the incoming frame(s) to the noisiest stage ----
        if cfg.mode == "img2img":
            with jax.named_scope("preprocess"):
                img = I.preprocess_uint8(frame_u8, dtype=dt)  # [fbs,H,W,3]
            with jax.named_scope("vae_encode"):
                z0 = models.vae_encode(params, img)  # [fbs,h,w,4]
            if cfg.do_add_noise:
                with jax.named_scope("add_noise"):
                    a0 = coeffs.alpha[:fbs].reshape(-1, 1, 1, 1).astype(dt)
                    s0 = coeffs.sigma[:fbs].reshape(-1, 1, 1, 1).astype(dt)
                    x_new = a0 * z0 + s0 * state["noise"][:fbs].astype(dt)
            else:
                x_new = z0
        else:  # txt2img: fresh noise enters the ring
            x_new = state["noise"][:fbs].astype(dt)

        # ---- ControlNet conditioning: annotate in-graph, ride a ring ----
        cond_full = None
        new_cnet_ring = None
        if cfg.use_controlnet:
            with jax.named_scope("annotate"):
                # the frame the step was handed, read a second time: no
                # second host-to-device copy, no host-side edge detector
                # (canny thresholds a gradient: it reads the frame in
                # float32 and only its edge map is kept in the step's dtype)
                src = I.preprocess_uint8(
                    frame_u8, dtype=jnp.float32 if cfg.annotator == "canny" else dt
                )
                cond_new = _annotate(src, cfg, params).astype(dt)  # [fbs,H,W,3]
            # state["cnet_cond"] is [B-fbs,H,W,3] (possibly empty), aligned
            # with x_buf: row j of the stream batch denoises the frame that
            # came in j steps ago, and is conditioned on that frame's edge
            # map; rotation mirrors the latent ring exactly.  A fresh
            # session's ring is zeros (no edges), as its latent ring is
            # noise (no frame): the first B-1 outputs are warm-up.
            with jax.named_scope("cnet_ring"):
                cond_full = jnp.concatenate(
                    [cond_new, state["cnet_cond"].astype(dt)], axis=0
                )
                new_cnet_ring = cond_full[: B - fbs]

        # ---- assemble the stream batch and run the UNet ----
        if cfg.use_denoising_batch:
            x_t = (
                jnp.concatenate([x_new, state["x_buf"].astype(dt)], axis=0)
                if B > fbs
                else x_new
            )
            if fused_ok:
                eps_c, _, new_cache = unet_with_guidance(
                    params, x_t, state, coeffs, state["stock"], cond_full,
                    return_raw=True,
                )
                from ..ops.pallas.fused_scheduler import fused_stream_epilogue

                # the wrapper around the kernel rides the same scope as the
                # plain path below; the kernel keeps its own name inside it
                with jax.named_scope("epilogue"):
                    kc = coeffs
                    if cfg.scheduler == "turbo":
                        # turbo step is pred_x0 == LCM blend with c_skip=0, c_out=1
                        kc = L.StepCoeffs(
                            coeffs.timesteps, coeffs.alpha, coeffs.sigma,
                            jnp.zeros_like(coeffs.c_skip),
                            jnp.ones_like(coeffs.c_out),
                            coeffs.next_alpha, coeffs.next_sigma,
                        )
                    # align noise with "next stage": entry b renoises with the
                    # noise of slot b+fbs; exit entries get next_sigma=0
                    noise_next = (
                        jnp.concatenate(
                            [state["noise"][fbs:], jnp.zeros_like(state["noise"][:fbs])],
                            axis=0,
                        )
                        if B > fbs
                        else jnp.zeros_like(state["noise"])
                    )
                    denoised, advanced, new_stock = fused_stream_epilogue(
                        x_t,
                        eps_c,
                        state["stock"].astype(dt),
                        noise_next.astype(dt),
                        kc,
                        state["guidance"],
                        state["delta"],
                        cfg_type=cfg.cfg_type,
                    )
                    out_latent = denoised[B - fbs :]
                    new_buf = advanced[: B - fbs] if B > fbs else state["x_buf"]
            else:
                eps, new_stock, new_cache = unet_with_guidance(
                    params, x_t, state, coeffs, state["stock"], cond_full
                )
                with jax.named_scope("epilogue"):
                    if cfg.scheduler == "turbo":
                        denoised = L.turbo_denoise(x_t, eps, coeffs, cfg.prediction_type)
                    else:
                        denoised = L.lcm_denoise(x_t, eps, coeffs, cfg.prediction_type)

                    # ---- rotate the ring: advance every entry one stage ----
                    out_latent = denoised[B - fbs :]
                    if B > fbs:
                        stage_noise = state["noise"][fbs:].astype(dt)
                        advanced = L.renoise_next(
                            denoised[: B - fbs],
                            stage_noise,
                            L.StepCoeffs(
                                *[
                                    getattr(coeffs, f)[: B - fbs]
                                    for f in (
                                        "timesteps", "alpha", "sigma", "c_skip", "c_out",
                                        "next_alpha", "next_sigma",
                                    )
                                ]
                            ),
                        )
                        new_buf = advanced
                    else:
                        new_buf = state["x_buf"]
        else:
            # sequential (non-stream) mode: all stages for this frame now —
            # n UNet passes of batch fbs; parity with the reference's
            # use_denoising_batch=False path (lib/wrapper.py ctor arg).
            x = x_new
            new_stock = state["stock"]
            for i in range(cfg.n_stages):
                sl = slice(i * fbs, (i + 1) * fbs)
                sub = L.StepCoeffs(
                    *[
                        getattr(coeffs, f)[sl]
                        for f in (
                            "timesteps", "alpha", "sigma", "c_skip", "c_out",
                            "next_alpha", "next_sigma",
                        )
                    ]
                )
                eps, stock_sl, _ = unet_with_guidance(
                    params, x, state, sub, new_stock[sl],
                    cond_full[:fbs] if cond_full is not None else None,
                )
                new_stock = (
                    new_stock
                    if stock_sl is None
                    else jnp.concatenate(
                        [new_stock[: i * fbs], stock_sl, new_stock[(i + 1) * fbs :]],
                        axis=0,
                    )
                )
                with jax.named_scope("epilogue"):
                    if cfg.scheduler == "turbo":
                        d = L.turbo_denoise(x, eps, sub, cfg.prediction_type)
                    else:
                        d = L.lcm_denoise(x, eps, sub, cfg.prediction_type)
                    x = L.renoise_next(d, state["noise"][sl].astype(dt), sub)
            out_latent = x
            new_buf = state["x_buf"]

        # ---- decode + postprocess in-graph ----
        with jax.named_scope("vae_decode"):
            img_out = models.vae_decode(params, out_latent)
        with jax.named_scope("postprocess"):
            out_u8 = I.postprocess_uint8(img_out.astype(jnp.float32))

        new_state = dict(state)
        new_state["x_buf"] = new_buf
        new_state["stock"] = new_stock
        if cfg.use_controlnet and new_cnet_ring is not None:
            new_state["cnet_cond"] = new_cnet_ring
        if unet_variant == "capture":
            new_state["unet_cache"] = new_cache.astype(dt)
        return new_state, out_u8

    return step


def make_bucket_step(vstep, capacity: int, scatter_output: bool = True):
    """The batch scheduler's step program: gather -> vmapped step ->
    scatter over the stacked ``[capacity, ...]`` session pytree, as ONE
    jitted call so the gather and scatter fuse with the step.

    ``vstep(params, states_k, frames_k) -> (new_states_k, out_k)`` is the
    vmapped :func:`make_step_fn` step; ``idx`` [k] selects which of the
    ``capacity`` state rows take part.  Duplicate indices (bucket padding)
    are sound: the duplicated rows compute identical values, so the
    duplicate scatter writes land identical data.

    ``scatter_output``: False (what ``stream/scheduler.py`` passes) returns
    the k-shaped output aligned with ``idx`` — the scheduler resolves
    waiters by batch position, and the zeros+scatter pass measurably taxes
    small buckets; True returns a full-capacity output indexed by slot id
    (rows not in ``idx`` are zeros)."""

    def bucket(params, states, frames_k, idx):
        # the jitted function keeps the name ``bucket``: the trace readers
        # find the step's program by it (``jit_bucket``)
        with jax.named_scope("gather"):
            sub = jax.tree.map(lambda a: jnp.take(a, idx, axis=0), states)
        new_sub, out = vstep(params, sub, frames_k)
        with jax.named_scope("scatter"):
            new_states = jax.tree.map(
                lambda full, ns: full.at[idx].set(ns), states, new_sub
            )
            if not scatter_output:
                return new_states, out
            # scatter into a full-capacity output so callers keep indexing
            # by slot id (rows not in idx are zeros, discarded)
            full_out = jnp.zeros(
                (capacity,) + out.shape[1:], out.dtype
            ).at[idx].set(out)
        return new_states, full_out

    return bucket


def _has_quantized_kernels(tree) -> bool:
    """True when any {kernel_q, scale} pair (models/quant.py) is present."""
    if isinstance(tree, dict):
        return any(
            k == "kernel_q" or _has_quantized_kernels(v) for k, v in tree.items()
        )
    return False


def params_variant_extra(params) -> dict:
    """AOT-cache key extras derived from the PARAMS variant.

    QUANT_WEIGHTS=w8 changes the traced graph (int8 kernels + fused
    dequant) without touching StreamConfig, so stream_engine_key alone
    cannot distinguish a quantized engine from the dense baseline.  Every
    key producer (StreamEngine.use_aot_cache, BatchScheduler.bucket_keys,
    the build CLI) splices this in so a quantized executable can never
    collide with — or stand in for — the dense one.  Empty when dense, so
    every pre-existing engine key stays valid."""
    return {"quant": "w8"} if _has_quantized_kernels(params) else {}


def stage_frame(frame_u8, device=None):
    """Start the host->HBM transfer for one frame WITHOUT blocking.

    The single reusable staging path shared by StreamEngine.submit and the
    batch scheduler's per-session submit (stream/scheduler.py): device_put
    returns immediately and the copy rides under in-flight compute
    (reference NVDEC zero-copy analog, README.md:11-15).  Called BEFORE
    any dispatch lock is taken — a large-frame H2D copy must never
    serialize concurrent sessions' dispatches on what looks like
    microseconds of host work.

    ``device``: the owning shard's device for mesh-sharded serving (the
    dp-sharded scheduler stages each session's row onto ITS shard, so the
    H2D copy lands where the row computes instead of on device 0 followed
    by a cross-device reshuffle).  None keeps the single-device default.

    Being the ONE H2D path (machine-checked: analysis/
    device_transfers.py) also makes it the one H2D *meter*: every staged
    frame lands in the device-telemetry transfer counters
    (obs/devtel.py; one global read + None test when the plane is off)."""
    if isinstance(frame_u8, np.ndarray):
        devtel.note_h2d(frame_u8.nbytes)
        if device is not None:
            return jax.device_put(frame_u8, device)
        return jax.device_put(frame_u8)
    return frame_u8


def current_attn_impl() -> str:
    """Resolved ATTN_IMPL default — THE single definition shared by the
    bundle builder (models/registry), the serving build probe
    (stream/pipeline) and the AOT cache key below, so they cannot disagree
    (empty-string env counts as unset everywhere)."""
    from ..utils import env as _env

    return _env.attn_impl_default(jax.default_backend())


def current_fused_epilogue() -> bool:
    """Resolved FUSED_EPILOGUE default (on for real TPUs; env kill-switch).

    Single definition for the same reason as :func:`current_attn_impl`:
    models/registry's bundle default and bench.py's PERF_LOG variant label
    must agree on which graph actually ran."""
    from ..utils import env as _env

    return _env.fused_epilogue_default(jax.default_backend())


def stream_engine_key(model_id: str, cfg: StreamConfig, **extra) -> str:
    """Canonical engine-cache key for a (model, stream config) pair — shared
    by the build CLI, the serving fast path and the batch scheduler (which
    adds ``sbucket=k, sessions=S``), so every graph-changing flag lives in
    exactly one key recipe (reference cache-key discipline:
    lib/wrapper.py:732-746)."""
    from ..aot.cache import engine_key

    return engine_key(
        model_id,
        cfg.mode,
        batch=cfg.batch_size,
        hw=f"{cfg.height}x{cfg.width}",
        dtype=cfg.dtype,
        cfgtype=cfg.cfg_type,
        sched=cfg.scheduler,
        # graph-changing flags that do NOT change arg shapes — must be part
        # of the key or different graphs collide on one cache entry
        cnet=f"{int(cfg.use_controlnet)}{cfg.annotator if cfg.use_controlnet else ''}",
        fused=int(cfg.use_fused_epilogue),
        # only when ON, so every pre-existing engine key stays valid
        **({"dcache": cfg.unet_cache_interval}
           if cfg.unet_cache_interval >= 2 else {}),
        # the attention impl is baked into the traced graph at bundle build
        # time; without it in the key a Pallas-attention executable could be
        # adopted by a serving process that just fell back to XLA (and vice
        # versa a fallback engine would poison the Pallas cache slot)
        attn=cfg.attn_impl or current_attn_impl(),
        **extra,
    )


class SimilarityFilter:
    """Host-side STOCHASTIC similar-image filter — the fork's
    SimilarImageFilter semantics (reference lib/wrapper.py:192-195):
    cosine similarity between consecutive (subsampled) frames; the skip
    probability ramps linearly from 0 at the threshold to 1 at sim=1,
    sampled per frame, with a max-skip guard so a static scene still
    refreshes.  An identical frame (sim=1) always skips; anything at or
    below the threshold never does — the stochastic band between keeps
    slow pans alive instead of hard-freezing them at a cliff.

    One instance per STREAM: the engine owns one for the shared-pipeline
    path, and every batch-scheduler session (stream/scheduler.py) owns its
    own so one session's static scene never skips another session's
    frames."""

    def __init__(self, threshold: float, max_skip: int, seed: int = 0):
        self.threshold = threshold
        self.max_skip = max_skip
        self._rng = np.random.default_rng(seed)
        self._prev_small = None
        self._skip_count = 0

    def should_skip(self, frame_u8, have_output: bool) -> bool:
        """True when this frame should duplicate the previous output
        instead of stepping the engine.  ``have_output``: a previous
        output exists to duplicate (never skip before the first frame)."""
        # subsample BEFORE the float cast: touch ~1/256 of the pixels, not
        # a full-frame float32 copy per submitted frame (hot path)
        small = np.asarray(frame_u8)[..., ::16, ::16, :].astype(np.float32)
        if self._prev_small is not None and have_output:
            a = small.ravel()
            b = self._prev_small.ravel()
            na, nb = float(np.linalg.norm(a)), float(np.linalg.norm(b))
            if na > 0.0 and nb > 0.0:
                sim = float(a @ b) / (na * nb)
            else:
                # an all-black frame is only "similar" to another all-black
                # frame — never to arbitrary content (a fade to black must
                # not freeze the stream on stale frames)
                sim = 1.0 if na == nb else 0.0
            thr = self.threshold
            prob = (
                0.0 if thr >= 1.0
                else max(0.0, 1.0 - (1.0 - sim) / (1.0 - thr))
            )
            if (
                self._rng.random() < prob
                and self._skip_count < self.max_skip
            ):
                self._skip_count += 1
                return True
        self._prev_small = small
        self._skip_count = 0
        return False

    def export_state(self) -> dict:
        """JSON-able snapshot of the filter's decision state (live
        session migration, stream/scheduler.py): the subsampled previous
        frame, the skip streak, and the RNG position — a restored filter
        makes exactly the stochastic skip choices this one would have."""
        import base64

        prev = self._prev_small
        return {
            "skip_count": int(self._skip_count),
            "rng_state": self._rng.bit_generator.state,
            "prev_small": None if prev is None else {
                "shape": list(prev.shape),
                "b64": base64.b64encode(
                    np.ascontiguousarray(prev, dtype=np.float32).tobytes()
                ).decode("ascii"),
            },
        }

    def restore_state(self, state: dict):
        """Inverse of :meth:`export_state`; bad payloads raise ValueError
        (the migration surface refuses rather than resuming with a
        half-restored filter)."""
        import base64
        import binascii

        try:
            self._skip_count = int(state["skip_count"])
            self._rng.bit_generator.state = state["rng_state"]
            prev = state.get("prev_small")
            if prev is None:
                self._prev_small = None
            else:
                raw = base64.b64decode(prev["b64"])
                self._prev_small = np.frombuffer(
                    raw, dtype=np.float32
                ).reshape([int(s) for s in prev["shape"]]).copy()
        except (KeyError, TypeError, ValueError, binascii.Error) as e:
            raise ValueError(f"similarity-filter state unusable: {e}") from e


def _annotate(img01_nhwc, cfg: StreamConfig, params=None):
    """In-graph conditioning annotator.

    canny: the soft-Canny conditioning BASELINE.json tracks.  hed: the
    reference's sole supported processor (lib/wrapper.py:39-40, 617-643),
    as an in-graph conv net whose weights stream from the public
    ControlNetHED checkpoint (models/hed.py) — fused into the step instead
    of the reference's separate CUDA detector pass."""
    if cfg.annotator == "canny":
        from ..models.controlnet import canny_soft

        return canny_soft(img01_nhwc)
    if cfg.annotator == "hed":
        if params is None or "hed" not in params:
            raise ValueError(
                "annotator='hed' needs HED params in the bundle — load with "
                "registry.load_model_bundle(..., annotator='hed')"
            )
        from ..models.hed import apply_hed

        return apply_hed(params["hed"], img01_nhwc)
    if cfg.annotator == "identity":
        return img01_nhwc
    raise ValueError(f"unknown annotator {cfg.annotator!r} (canny|hed|identity)")


class StreamEngine:
    """Host-side driver around the jitted step fn (prompt cache, state
    management, warm-up, similarity filter).

    Parity surface with the reference wrapper (lib/wrapper.py):
      prepare(prompt, num_inference_steps, guidance_scale, delta, seed)
      __call__(frame) / update_prompt(prompt) / update_t_index_list(list)
    ``encode_prompt`` is injected (a callable str -> (cond, uncond) numpy
    [1,L,D] pair) so the engine stays tokenizer-agnostic.
    """

    def __init__(
        self,
        models: StreamModels,
        params,
        cfg: StreamConfig,
        encode_prompt: Callable[[str], tuple],
        schedule: S.NoiseSchedule | None = None,
        jit_compile: bool = True,
        donate: bool = True,
        mesh=None,
    ):
        """``mesh``: optional multi-chip serving mesh.  With a tp axis > 1
        the UNet/VAE params are placed by the Megatron-style rules
        (parallel/sharding.py) and ONE stream step runs tensor-parallel
        across the chips — XLA inserts the psums over ICI.  Single-stream
        scale-out for when one chip can't hit the fps bar (SURVEY sec.2c
        TP row)."""
        self.models = models
        self.cfg = cfg
        self.encode_prompt = encode_prompt
        self.schedule = schedule or S.make_schedule()
        self.mesh = mesh
        self._t_index_list = tuple(cfg.t_index_list)
        if mesh is not None and mesh.shape.get("tp", 1) > 1:
            from ..parallel import sharding as SH

            if _has_quantized_kernels(params):
                # sharding rules key on '.../kernel' leaf names; quantized
                # {kernel_q, scale} pairs would serve fully REPLICATED —
                # an N-chip mesh silently computing single-chip (ADVICE r2)
                raise ValueError(
                    "QUANT_WEIGHTS int8 kernels are incompatible with "
                    "tensor-parallel serving (tp>1): quantized leaves have "
                    "no sharding rules and would replicate. Disable one."
                )
            params = jax.device_put(params, SH.param_shardings(mesh, params))
        self.params = params

        def _wrap_sp(fn):
            if mesh is None or mesh.shape.get("sp", 1) <= 1:
                return fn
            # sequence-parallel serving: activate the sp attention context
            # around the step so ATTN_IMPL=ring/ulysses models route their
            # token axis over the mesh (layers.sp_attention_mesh); the
            # wrapper costs a list push/pop per call — only trace time
            # matters
            from ..models.layers import sp_attention_mesh

            def wrapped(params, state, frame_u8, _inner=fn):
                with sp_attention_mesh(self.mesh, axis="sp"):
                    return _inner(params, state, frame_u8)

            return wrapped

        def _jit(fn):
            if not jit_compile:
                return fn
            return jax.jit(fn, donate_argnums=(1,) if donate else ())

        self._cache_interval = (
            cfg.unet_cache_interval if cfg.unet_cache_interval >= 2 else 0
        )
        self._tick = 0
        if self._cache_interval:
            # DeepCache cadence: two static graphs, host-side alternation
            self._raw_capture_step = _wrap_sp(
                make_step_fn(models, cfg, unet_variant="capture")
            )
            self._step = _jit(self._raw_capture_step)
            self._step_cached = _jit(
                _wrap_sp(make_step_fn(models, cfg, unet_variant="cached"))
            )
        else:
            self._step = _jit(_wrap_sp(make_step_fn(models, cfg)))
            self._step_cached = None
        self.state = None
        self._last_out = None
        self._last_submitted = None
        # observability flag (obs/trace.py): True when the most recent
        # submit() ON THIS THREAD resolved via the similarity filter
        # instead of a device step — a plain attribute write (no clock,
        # no env: trace-purity safe) that the pipeline façade turns into
        # a trace mark.  Thread-local because the shared-engine plane
        # gives every session this one engine: set-then-read happens
        # within one to_thread hop, and a concurrent session's submit on
        # another thread must not cross-contaminate the mark
        self._submit_skip_flag = threading.local()
        # compute-path fault injection (resilience/faults.py): None unless
        # a plan targeting the engine is active — disabled injection costs
        # one is-None test per submit
        from ..resilience import faults as _faults

        self._fault_scope = _faults.scope("engine")
        self._sim_filter = SimilarityFilter(
            cfg.similar_image_threshold, cfg.similar_image_max_skip, seed=0
        )
        # submit() is a read-modify-write of self.state; concurrent tracks
        # (several connections sharing one pipeline, each stepping on a
        # worker thread) must serialize it.  The reference gets this for
        # free by blocking its event loop (lib/tracks.py:24) — we don't.
        self._submit_lock = threading.Lock()

    # -- state construction -------------------------------------------------

    def prepare(
        self,
        prompt: str,
        num_inference_steps: int | None = None,
        guidance_scale: float = 1.2,
        delta: float = 1.0,
        seed: int = 2,
        negative_prompt: str = "",
        controlnet_scale: float = 1.0,
    ):
        """Build the initial StreamState (reference prepare(): lib/wrapper.py:197-234).
        ``controlnet_scale``: the side network's conditioning strength this
        state starts with (diffusers' default 1.0); data in the state, so
        :meth:`update_controlnet_scale` swaps it with no recompile."""
        cfg = self.cfg
        if (
            num_inference_steps is not None
            and num_inference_steps != cfg.num_inference_steps
        ):
            raise ValueError(
                "num_inference_steps is compile-time static; rebuild the engine"
            )
        h, w = cfg.latent_hw
        B = cfg.batch_size
        key = jax.random.PRNGKey(seed)
        noise = jax.random.normal(key, (B, h, w, cfg.latent_channels), cfg.jdtype)
        cond, uncond, extras = self._encode(prompt)
        state = {
            "x_buf": (
                noise[cfg.frame_buffer_size :]
                if B > cfg.frame_buffer_size
                else jnp.zeros((0, h, w, cfg.latent_channels), cfg.jdtype)
            ),
            "noise": noise,
            "stock": jnp.zeros_like(noise),
            "cond": jnp.asarray(cond, cfg.jdtype),
            "uncond": jnp.asarray(uncond, cfg.jdtype),
            "guidance": jnp.asarray(guidance_scale, jnp.float32),
            "delta": jnp.asarray(delta, jnp.float32),
            "coeffs": _coeff_state(cfg, self.schedule, self._t_index_list),
        }
        if cfg.use_added_cond:
            state["added_text"] = jnp.asarray(extras["pooled"], cfg.jdtype)
            state["added_time_ids"] = jnp.asarray(
                extras.get(
                    "time_ids",
                    np.array(
                        [[cfg.height, cfg.width, 0, 0, cfg.height, cfg.width]],
                        np.float32,
                    ),
                )
            )
        if cfg.use_controlnet:
            state["cnet_cond"] = jnp.zeros(
                (B - cfg.frame_buffer_size, cfg.height, cfg.width, 3), cfg.jdtype
            )
            state["cnet_scale"] = jnp.asarray(controlnet_scale, jnp.float32)
        if cfg.cfg_type == "initialize":
            # Onetime-Negative: seed the stock noise with one real uncond pass
            coeffs = _as_step_coeffs(state["coeffs"])
            x = state["noise"].astype(cfg.jdtype)
            added = None
            if cfg.use_added_cond:
                added = {
                    "time_ids": jnp.broadcast_to(
                        state["added_time_ids"], (B,) + state["added_time_ids"].shape[1:]
                    ),
                    "text_embeds": jnp.broadcast_to(
                        state["added_text"], (B,) + state["added_text"].shape[1:]
                    ).astype(cfg.jdtype),
                }
            unc = jnp.broadcast_to(
                state["uncond"], (B,) + state["uncond"].shape[1:]
            ).astype(cfg.jdtype)
            state["stock"] = self.models.unet(
                self.params, x, coeffs.timesteps, unc, added
            )
        if self._cache_interval:
            # pre-size the DeepCache slot (trace-only, no compile) so the
            # capture step's state pytree is identical on every call —
            # otherwise the first capture (no cache key) and later captures
            # (cache key present) would cost two full compiles
            spec = jax.ShapeDtypeStruct(
                (cfg.frame_buffer_size, cfg.height, cfg.width, 3), jnp.uint8
            )
            shaped, _ = jax.eval_shape(
                self._raw_capture_step, self.params, state, spec
            )
            dh = shaped["unet_cache"]
            state["unet_cache"] = jnp.zeros(dh.shape, dh.dtype)
            # first real submit captures a fresh cache; prepare() is the
            # single-thread build phase — serving threads exist only
            # after it returns the engine
            self._tick = 0  # tpurtc: allow[lock-discipline] -- prepare() runs before the engine is shared; submit/update paths (the guarded writers) cannot be live yet
        self.state = state
        return self

    # -- AOT engine adoption ------------------------------------------------

    def use_aot_cache(
        self, model_id: str, cache_dir: str | None = None,
        build_on_miss: bool = True,
    ) -> bool:
        """Swap the jitted step for a serialized AOT executable — the serving
        side of the reference's "load engines without base weights" fast path
        (lib/wrapper.py:409-512).  Key discipline matches build_engines, so a
        prebuilt engine from the CLI is adopted directly.

        Returns True when an engine (cached or freshly built) is now in use;
        with ``build_on_miss=False`` a miss leaves the plain jit step and
        returns False.
        """
        from ..aot.cache import EngineCache

        if self.mesh is not None and any(n > 1 for n in self.mesh.shape.values()):
            # serialized executables are per-topology; the tp/sp serving
            # meshes keep the plain jit path
            return False
        if self.state is None:
            raise RuntimeError("call prepare() first (state defines the signature)")
        cache = EngineCache(cache_dir)
        fbs = self.cfg.frame_buffer_size
        frame_spec = jax.ShapeDtypeStruct(
            (self.cfg.height, self.cfg.width, 3)
            if fbs == 1
            else (fbs, self.cfg.height, self.cfg.width, 3),
            jnp.uint8,
        )
        args = (self.params, self.state, frame_spec)
        if self._cache_interval:
            # DeepCache pair: two distinct executables (capture + cached),
            # adopted atomically — a half-adopted pair would mix an AOT
            # step with a cold jit step mid-cadence
            plan = [("capture", {"variant": "capture"}, "_step"),
                    ("cached", {"variant": "cached"}, "_step_cached")]
        else:
            plan = [("full", {}, "_step")]
        # the params variant (w8 quant) is part of the key: a quantized
        # executable must never collide with the dense baseline's slot
        qextra = params_variant_extra(self.params)
        keys = [stream_engine_key(model_id, self.cfg, **extra, **qextra)
                for _, extra, _ in plan]
        if not build_on_miss and not all(
            cache.has(k, args) for k in keys
        ):
            return False
        calls = []
        for (unet_variant, _, _), k in zip(plan, keys):
            step = make_step_fn(self.models, self.cfg, unet_variant=unet_variant)
            call = cache.load_or_build(
                k, step, args, donate_argnums=(1,), build=build_on_miss
            )
            if call is None:  # unreadable blob with build_on_miss=False
                return False
            calls.append(call)
        for (_, _, attr), call in zip(plan, calls):
            setattr(self, attr, call)
        return True

    # -- hot path -----------------------------------------------------------

    def __call__(self, frame_u8: np.ndarray) -> np.ndarray:
        """One stream step. frame_u8 [H,W,3] uint8 -> [H,W,3] uint8.

        With frame_buffer_size>1 pass [fbs,H,W,3] and get [fbs,H,W,3].
        """
        return self.fetch(self.submit(frame_u8))

    @property
    def last_submit_was_skip(self) -> bool:
        """Did the most recent submit() on the CALLING thread resolve via
        the similarity filter?  Thread-local (see __init__): sessions
        sharing this engine read only their own submit's outcome."""
        return getattr(self._submit_skip_flag, "value", False)

    @last_submit_was_skip.setter
    def last_submit_was_skip(self, value: bool):
        self._submit_skip_flag.value = value

    def submit(self, frame_u8: np.ndarray):
        """Dispatch one stream step WITHOUT waiting for the result.

        Returns an opaque pending handle; pass it to :meth:`fetch`.  The
        engine state advances on-device immediately, so several frames can
        be in flight — the dispatch pipeline stays full (the reference
        blocks its event loop per frame, lib/tracks.py:24; we must not:
        SURVEY.md section 7 "hard parts").  Thread-safe: dispatches from
        concurrent tracks serialize on a lock (the dispatch is async — the
        lock covers microseconds of host work, not device time).
        """
        if self.state is None:
            raise RuntimeError("call prepare() first")
        self.last_submit_was_skip = False  # tpurtc: allow[lock-discipline] -- thread-local descriptor (PR 5 fix): each calling thread writes only its own _submit_skip_flag slot
        if self._fault_scope is not None:
            # injected slow step (blocks this worker thread, simulating a
            # wedged device dispatch), DeviceLostError, or NaN output —
            # BEFORE the lock so an injected stall doesn't also wedge
            # concurrent control-plane updates
            action = self._fault_scope.step()
            if action == "nan":
                h, w = self.cfg.height, self.cfg.width
                shape = (
                    (h, w, 3)
                    if frame_u8.ndim == 3
                    else (frame_u8.shape[0], h, w, 3)
                )
                poisoned = np.full(shape, np.nan, np.float32)
                return ("fault", poisoned, frame_u8.ndim == 3)
        squeeze = frame_u8.ndim == 3
        # async host->HBM staging BEFORE the dispatch lock: device_put
        # returns immediately and the copy rides under in-flight compute,
        # so a large-frame transfer can't serialize concurrent sessions'
        # dispatches behind the submit lock.  Filter-enabled engines keep
        # the ORIGINAL single-lock discipline instead (staging inside the
        # lock, AFTER the skip check): splitting check and step across two
        # acquisitions would let a concurrent skip dup a STALE
        # _last_submitted (stream steps backwards — code-review r2), and
        # staging first would pay an H2D for every skipped frame of a
        # static scene (code-review r1).  The default serving configs run
        # the filter per-session in the scheduler, not here, so the hot
        # path gets the lock-free staging.
        staged = (
            stage_frame(frame_u8)
            if not self.cfg.similar_image_filter
            else None
        )
        with self._submit_lock:
            if self.cfg.similar_image_filter:
                if self._maybe_skip(frame_u8):
                    # skip the device step entirely: the handle DUPLICATES
                    # the most recently submitted output buffer, so
                    # resolution order stays correct even when fetches run
                    # concurrently on pool threads (resolving against
                    # host-side _last_out would race the in-flight frames
                    # and step the stream backwards)
                    self.last_submit_was_skip = True
                    if self._last_submitted is not None:
                        return ("dup",) + self._last_submitted
                    return None, squeeze
                # not skipped: stage now (under the lock — the price of
                # exact dup-anchor semantics; skipped frames never pay it)
                staged = stage_frame(frame_u8)
            fn = self._step
            if self._cache_interval:
                # full/capture every Nth step, cached between (static
                # cadence: both graphs are already compiled, the host just
                # picks one — no data-dependent control flow on device)
                if self._tick % self._cache_interval != 0:
                    fn = self._step_cached
                self._tick += 1
            # compile-watchdog attribution: a lazy first-step compile on
            # the shared-engine path (BATCHSCHED=0, no prewarm) is
            # recorded against the engine step, not "unattributed"
            with devtel.compile_scope("engine-step"):
                self.state, out = fn(self.params, self.state, staged)
            try:  # overlap device->host copy with subsequent compute
                out.copy_to_host_async()
            except (AttributeError, RuntimeError):
                pass
            self._last_submitted = (out, squeeze)
            return out, squeeze

    def fetch(self, pending) -> np.ndarray:
        """Resolve a handle from :meth:`submit` to a host uint8 array."""
        if len(pending) == 3:  # ("dup", out, squeeze): similarity skip
            _, out, squeeze = pending
        else:
            out, squeeze = pending
        if out is None:  # skip before any real frame was submitted
            return self._last_out
        arr = np.asarray(out)
        if arr is not out:
            # a real device->host resolve (np input passes through
            # identically — the fault path's poisoned frames are host
            # arrays).  Dup chains re-read the same buffer; jax serves
            # the cached host copy, so this slightly overcounts
            # transfers on static scenes — the scheduler's memoized
            # per-row path (the default) is exact.
            devtel.note_d2h(arr.nbytes)
        out = arr
        if out.shape[0] == 1 and squeeze:
            out = out[0]
        self._last_out = out
        return out

    def _maybe_skip(self, frame_u8) -> bool:
        """One :class:`SimilarityFilter` draw under the submit lock.
        Skipping avoids the device call entirely (the real saving — an
        in-graph select would still burn the FLOPs)."""
        return self._sim_filter.should_skip(
            frame_u8, have_output=self._last_out is not None
        )

    # back-compat views over the extracted SimilarityFilter state (tests
    # and diagnostics poke these directly)
    @property
    def _skip_count(self) -> int:
        return self._sim_filter._skip_count

    @_skip_count.setter
    def _skip_count(self, v: int):
        self._sim_filter._skip_count = v

    @property
    def _prev_frame_small(self):
        return self._sim_filter._prev_small

    @_prev_frame_small.setter
    def _prev_frame_small(self, v):
        self._sim_filter._prev_small = v

    # -- control plane (no recompiles) -------------------------------------

    def update_prompt(self, prompt: str):
        """Embedding swap (reference lib/pipeline.py:44-45).  The encode
        runs un-locked (heavy); only the state writes take the submit lock
        so they can't interleave with a concurrent dispatch."""
        cond, uncond, extras = self._encode(prompt)
        with self._submit_lock:
            self.state["cond"] = jnp.asarray(cond, self.cfg.jdtype)
            self.state["uncond"] = jnp.asarray(uncond, self.cfg.jdtype)
            if self.cfg.use_added_cond and "pooled" in extras:
                self.state["added_text"] = jnp.asarray(
                    extras["pooled"], self.cfg.jdtype
                )
            # DeepCache: deep cross-attention (where prompt conditioning
            # lives) must not serve stale features for up to N-1 frames —
            # force the next step to recapture
            self._tick = 0

    def _encode(self, prompt: str):
        res = self.encode_prompt(prompt)
        if len(res) == 3:
            return res
        cond, uncond = res
        return cond, uncond, {}

    def update_t_index_list(self, t_index_list):
        """Same-length update = coefficient swap, zero recompile (fixes the
        reference's desync quirk at lib/wrapper.py:389-407 by VALIDATING the
        length here, which the reference only does in prepare())."""
        t_index_list = tuple(int(t) for t in t_index_list)
        if len(t_index_list) != len(self._t_index_list):
            raise ValueError(
                f"t_index_list length must stay {len(self._t_index_list)} "
                f"(compiled batch size); rebuild the engine to change depth"
            )
        self._t_index_list = t_index_list
        coeffs = _coeff_state(self.cfg, self.schedule, t_index_list)
        with self._submit_lock:
            self.state["coeffs"] = coeffs
            self._tick = 0  # DeepCache: new timesteps -> recapture next step

    def reset_cache_cadence(self):
        """DeepCache: make the NEXT step a full capture (called after the
        build probe and by control-plane updates so stale deep features are
        never served across a known discontinuity)."""
        with self._submit_lock:
            self._tick = 0

    def update_guidance(self, guidance_scale=None, delta=None):
        with self._submit_lock:
            if guidance_scale is not None:
                self.state["guidance"] = jnp.asarray(guidance_scale, jnp.float32)
            if delta is not None:
                self.state["delta"] = jnp.asarray(delta, jnp.float32)

    def update_controlnet_scale(self, scale: float):
        """Runtime conditioning-strength swap (no recompile) — analog of the
        reference's fixed conditioning scale (lib/wrapper.py:870-877)."""
        if not self.cfg.use_controlnet:
            raise ValueError("engine built without use_controlnet")
        with self._submit_lock:
            self.state["cnet_scale"] = jnp.asarray(scale, jnp.float32)
