"""Pipeline façade — parity surface with reference lib/pipeline.py.

``StreamDiffusionPipeline(model_id)`` owns the model bundle + engine and
exposes exactly the reference's call surface (reference lib/pipeline.py:17-96):
    __call__(frame) -> frame      update_prompt(str)
    preprocess / predict / postprocess        update_t_index_list(list)

Differences, all deliberate and TPU-motivated:
* preprocess/postprocess are IN-GRAPH (ops/image.py); the façade-level
  methods exist for API parity and host-side fallbacks but the hot path
  calls the fused jitted step directly.
* The reference hardcodes device="cuda" and NCHW fp16; here the engine
  compiles for the local TPU (or CPU) in NHWC with bf16/fp32 selected by
  StreamConfig.
* Frame duck-typing contract preserved (reference lib/tracks.py:34-37): a
  frame is either a raw HxWx3 uint8 ndarray (device-bound fast path — the
  NVDEC analog) or an object with .to_ndarray(format="rgb24"), .pts and
  .time_base (av.VideoFrame-compatible software path).
"""

from __future__ import annotations

import logging
from typing import Sequence

import numpy as np

from ..models import registry
from ..obs.trace import get_trace, hop
from ..utils import env
from .engine import StreamConfig, StreamEngine

logger = logging.getLogger(__name__)

DEFAULT_PROMPT = "fireworks in the night sky"
DEFAULT_T_INDEX_LIST = (18, 26, 35, 45)
DEFAULT_NUM_INFERENCE_STEPS = 50
DEFAULT_GUIDANCE_SCALE = 1.2
DEFAULT_DELTA = 1.0
DEFAULT_CONTROLNET_SCALE = 1.0  # diffusers' controlnet_conditioning_scale


class StreamDiffusionPipeline:
    """Owns model params + stream engine; shared by all connections
    (mutable shared state semantics preserved from reference agent.py:423)."""

    def __init__(
        self,
        model_id: str = "stabilityai/sd-turbo",
        config: StreamConfig | None = None,
        prompt: str = DEFAULT_PROMPT,
        lora_dict: dict | None = None,
        seed: int = 2,
        use_safety_checker: bool | None = None,
        mesh=None,
    ):
        """``model_id``: ``<base>+<side network>`` builds the
        ControlNet-conditioned stream (``registry.split_model_id``)."""
        self.prompt = prompt
        self.model_id = model_id
        # live control-plane params — restart() restores THESE, never the
        # module defaults (a fault recovery must not revert /config:
        # ROADMAP open item 2, held by the restart-defaults checker)
        self.guidance_scale = DEFAULT_GUIDANCE_SCALE
        self.delta = DEFAULT_DELTA
        self.controlnet_scale = DEFAULT_CONTROLNET_SCALE
        # optional NSFW gate (reference use_safety_checker,
        # lib/wrapper.py:930-942); env SAFETY_CHECKER enables it globally
        self.safety_checker = maybe_load_safety_checker(model_id, use_safety_checker)
        cfg = config or registry.default_stream_config(model_id)
        if cfg.use_controlnet != (registry.split_model_id(model_id)[1] is not None):
            raise ValueError(
                f"StreamConfig.use_controlnet={cfg.use_controlnet} but the "
                f"model id {model_id!r} says otherwise: a side network is "
                "named as <base>+<controlnet id>"
            )
        bundle = registry.load_model_bundle(
            model_id, lora_dict=lora_dict,
            latent_scale=cfg.latent_scale,
            attn_impl=cfg.attn_impl or None,
            annotator=cfg.annotator if cfg.use_controlnet else None,
        )
        bundle.params = registry.cast_params(bundle.params, cfg.dtype)
        self._bundle = bundle
        self.config = cfg
        self.t_index_list = list(cfg.t_index_list)
        self._seed = seed
        self.engine = StreamEngine(
            models=bundle.stream_models,
            params=bundle.params,
            cfg=cfg,
            encode_prompt=bundle.encode_prompt,
            mesh=mesh,
        )
        self.engine.prepare(
            prompt=prompt,
            guidance_scale=self.guidance_scale,
            delta=self.delta,
            seed=seed,
            controlnet_scale=self.controlnet_scale,
        )
        # Serving fast path: adopt a prebuilt AOT engine when one exists
        # (always), or export-and-persist one when AOT_ENGINES=1 (reference
        # _load_trt_model-vs-compile split, lib/wrapper.py:583-615).  An
        # unreadable or mismatched entry is a logged miss inside the cache;
        # anything else that goes wrong here is a failed boot.
        if self.engine.use_aot_cache(
            model_id, build_on_miss=env.get_bool("AOT_ENGINES", False)
        ):
            logger.info("serving from AOT engine cache")
        self._warm_up()

    def _warm_up(self):
        """Run ONE step before serving starts when a Pallas kernel is in the
        graph (fused epilogue, flash attention).  It is the compile warm-up
        the reference gets from dropping WARMUP_FRAMES at connect
        (reference lib/tracks.py:21-25) and it is where a kernel the
        compiler refuses, or that fails on the device, fails the boot with
        the compiler's message.  There is no rebuild on another graph:
        ``FUSED_EPILOGUE=0`` / ``ATTN_IMPL=xla`` are the explicit way to
        serve without a kernel."""
        from .engine import current_attn_impl

        cfg = self.config
        attn = cfg.attn_impl or current_attn_impl()
        if not (cfg.use_fused_epilogue or attn == "pallas"):
            return
        # at the SERVED batch geometry: fbs>1 steps take [fbs,H,W,3]
        shape = (cfg.height, cfg.width, 3)
        if cfg.frame_buffer_size > 1:
            shape = (cfg.frame_buffer_size,) + shape
        frame = np.zeros(shape, np.uint8)
        self.engine(frame)
        if self.engine._cache_interval:
            # warm the SECOND DeepCache graph too (one step only compiles
            # the capture variant), then restart the cadence so the first
            # live frame recaptures instead of splicing deep features of
            # this zero-filled frame
            self.engine(frame)
            self.engine.reset_cache_cadence()

    # -- recovery (resilience/supervisor.py restart hook) --------------------

    def restart(self):
        """Re-prepare the engine in place: a fresh stream state (clearing
        poisoned latents / desynced ring state after a fault) on the SAME
        compiled executables — seconds, not the minutes a full rebuild
        costs.  Takes the submit lock (bounded) so a late in-flight step
        can't clobber the fresh state with a stale one."""
        lock = self.engine._submit_lock
        got = lock.acquire(timeout=10.0)
        if not got:
            # a wedged step still holds the dispatch lock: preparing
            # UNLOCKED would let its eventual state write clobber the fresh
            # state — fail this attempt and let the supervisor's RetryPolicy
            # come back when the lock is free (or give up -> FAILED)
            raise RuntimeError(
                "engine restart blocked: submit lock still held by a "
                "wedged step"
            )
        try:
            # prepare() rebuilds coefficients from the engine's tracked
            # t_index_list, so runtime t-index updates survive the restart;
            # prompt/guidance/delta restore from the live snapshots this
            # façade tracks (update_prompt / update_guidance)
            self.engine.prepare(
                prompt=self.prompt,
                guidance_scale=self.guidance_scale,
                delta=self.delta,
                seed=self._seed,
                controlnet_scale=self.controlnet_scale,
            )
        finally:
            lock.release()

    # -- control plane (reference lib/pipeline.py:44-48) --------------------

    def update_prompt(self, prompt: str):
        # engine first, snapshot after — restart() restores self.prompt,
        # and a rejected update must never be what it restores (same
        # accept-then-snapshot rule as update_guidance)
        self.engine.update_prompt(prompt)
        self.prompt = prompt

    def update_t_index_list(self, t_index_list: Sequence[int]):
        self.engine.update_t_index_list(t_index_list)
        self.t_index_list = list(t_index_list)

    def update_guidance(self, guidance_scale=None, delta=None):
        """Runtime guidance/delta update (POST /config) — tracked here so
        a supervisor-driven restart() re-prepares with the LIVE values.
        Values convert (and so can fail) BEFORE anything mutates, and the
        façade snapshot updates only after the engine accepted them — a
        rejected update must never be what a later restart() restores."""
        g = None if guidance_scale is None else float(guidance_scale)
        d = None if delta is None else float(delta)
        self.engine.update_guidance(guidance_scale=g, delta=d)
        if g is not None:
            self.guidance_scale = g
        if d is not None:
            self.delta = d

    @property
    def has_controlnet(self) -> bool:
        return self.config.use_controlnet

    def update_controlnet_scale(self, scale: float):
        """Runtime conditioning strength (POST /config ``controlnet_scale``);
        tracked here like guidance so restart() re-prepares with it."""
        scale = float(scale)
        self.engine.update_controlnet_scale(scale)
        self.controlnet_scale = scale

    # -- frame path (reference lib/pipeline.py:50-96) -----------------------

    def preprocess(self, frame) -> np.ndarray:
        """Duck-typed frame -> [H,W,3] uint8 ndarray (+ pts metadata)."""
        return coerce_frame(frame, self.config.height, self.config.width)

    def predict(self, frame_u8: np.ndarray) -> np.ndarray:
        out = self.engine(frame_u8)
        if self.safety_checker is not None:
            out = self.safety_checker(out)
        return out

    def postprocess(self, out_u8: np.ndarray, src_frame=None):
        """Attach timing metadata when the input carried it (VideoFrame
        contract: pts/time_base preserved, reference lib/pipeline.py:89-93)."""
        if src_frame is not None and hasattr(src_frame, "pts"):
            from ..media.frames import wrap_processed

            return wrap_processed(out_u8, src_frame)
        return out_u8

    def __call__(self, frame):
        trace = get_trace(frame)  # None (one getattr) unless tracing is on
        with hop("submit", trace):
            pre = self.preprocess(frame)
        with hop("engine_step", trace):  # sync path: the whole device step
            out = self.predict(pre)
        if trace is not None and self.engine.last_submit_was_skip:
            trace.mark("similar_skip")
        if hasattr(frame, "pts") and not env.hw_encode():
            with hop("postprocess", trace):
                return self.postprocess(out, frame)
        return out

    # -- pipelined (async-dispatch) frame path ------------------------------

    def submit(self, frame):
        """Dispatch one frame without waiting (see engine.submit); returns a
        handle for :meth:`fetch`.  Lets the caller keep several frames in
        flight so device compute, dispatch and readback overlap."""
        trace = get_trace(frame)
        with hop("submit", trace):  # host preprocess + async device dispatch
            pre = self.preprocess(frame)
            handle = self.engine.submit(pre)
        if trace is not None and self.engine.last_submit_was_skip:
            trace.mark("similar_skip")
        return handle

    # -- frame_buffer_size > 1: batched amortization in SERVING -------------
    # (the reference pins fbs at engine-build time, lib/wrapper.py:159-163;
    # here the track layer batches fbs consecutive frames per device step)

    @property
    def frame_buffer_size(self) -> int:
        return self.config.frame_buffer_size

    def submit_batch(self, frames):
        """frames: list of fbs duck-typed frames -> one in-flight handle."""
        pre = np.stack([self.preprocess(f) for f in frames])
        return self.engine.submit(pre)

    def fetch_batch(self, handle, src_frames=None):
        """Resolve a submit_batch handle -> list of fbs output frames (pts
        metadata attached per source like fetch)."""
        out = self.engine.fetch(handle)  # [fbs, H, W, 3]
        if self.safety_checker is not None:
            out = self.safety_checker(out)
        results = []
        for i in range(out.shape[0]):
            src = src_frames[i] if src_frames else None
            if src is not None and hasattr(src, "pts") and not env.hw_encode():
                results.append(self.postprocess(out[i], src))
            else:
                results.append(out[i])
        return results

    def fetch(self, handle, src_frame=None):
        """Resolve a submit() handle; attaches pts metadata like __call__."""
        trace = get_trace(src_frame) if src_frame is not None else None
        # resolve-end stamped BEFORE the safety checker: fetch is the
        # blocking readback hop, and a CLIP forward riding its span would
        # inflate exactly the histogram the SLO fetch budget fences (the
        # scheduler's fetch stamps the same way)
        with hop("fetch", trace) as resolve:
            out = self.engine.fetch(handle)
        if self.safety_checker is not None:
            out = self.safety_checker(out)
        if trace is not None:
            # engine_step = the frame's device residency, submit-end ->
            # resolve-end (the host-observable bound on the async step —
            # stamped OUTSIDE jit, the trace-purity checker holds that line)
            sub_end = trace.span_end("submit")
            trace.add_span(
                "engine_step",
                sub_end if sub_end is not None else resolve.t0, resolve.t1,
            )
        if src_frame is not None and hasattr(src_frame, "pts") and not env.hw_encode():
            with hop("postprocess", trace):
                return self.postprocess(out, src_frame)
        return out


def finish_output(out, src_frame=None, safety_checker=None, trace=None):
    """The pipeline's output contract for a plane that resolves its own
    frames: safety-check the pixels, then wrap pts metadata unless
    HW_ENCODE serving wants bare ndarrays (stamping the postprocess span
    when a trace rides along).  ``ScheduledSession.fetch``
    (stream/scheduler.py) ends in it, so a scheduler session returns what
    ``StreamDiffusionPipeline.fetch`` returns."""
    if safety_checker is not None:
        out = safety_checker(out)
    if src_frame is not None and hasattr(src_frame, "pts") and not env.hw_encode():
        from ..media.frames import wrap_processed

        with hop("postprocess", trace):
            return wrap_processed(out, src_frame)
    return out


def maybe_load_safety_checker(model_id: str, use: bool | None = None):
    """NSFW-gate loader shared by single- and multi-peer serving (reference
    use_safety_checker, lib/wrapper.py:930-942).  ``use=None`` defers to the
    SAFETY_CHECKER env var; returns None when disabled."""
    if use is None:
        use = env.get_bool("SAFETY_CHECKER", False)
    if not use:
        return None
    from ..models import loader as _LD
    from ..models.safety import SafetyChecker

    # prefer the base model's bundled safety_checker/ subfolder, else the
    # standalone checkpoint the download CLI ships (--model-set safety)
    snap = registry.resolve_snapshot_dir(model_id)
    if not snap or not _LD.find_safetensors(snap, "safety_checker"):
        snap = (
            registry.resolve_snapshot_dir("CompVis/stable-diffusion-safety-checker")
            or snap
        )
    return SafetyChecker.load(snap)


def coerce_frame(frame, h: int, w: int) -> np.ndarray:
    """Duck-typed frame (ndarray | av.VideoFrame-like) -> [h,w,3] uint8
    (frame contract preserved from reference lib/tracks.py:34-37)."""
    if hasattr(frame, "to_ndarray"):
        arr = frame.to_ndarray(format="rgb24")
    elif isinstance(frame, np.ndarray):
        arr = frame
    else:
        raise TypeError(f"invalid frame type: {type(frame)!r}")
    if arr.dtype != np.uint8 or arr.ndim != 3 or arr.shape[-1] != 3:
        raise ValueError(f"expected HxWx3 uint8 RGB, got {arr.shape} {arr.dtype}")
    if arr.shape[:2] != (h, w):
        arr = _resize_u8(arr, h, w)
    return arr


def _resize_u8(arr: np.ndarray, h: int, w: int) -> np.ndarray:
    """Nearest-neighbor host resize for mismatched sources (control path)."""
    ys = (np.arange(h) * arr.shape[0] // h).clip(0, arr.shape[0] - 1)
    xs = (np.arange(w) * arr.shape[1] // w).clip(0, arr.shape[1] - 1)
    return arr[ys][:, xs]
