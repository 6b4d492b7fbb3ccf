"""AOT engine builder CLI — parity with reference build.py.

The reference builds TensorRT engines by constructing the wrapper (compile
happens inside _load_model, reference build.py:11-32); here we AOT-compile
the full stream step via jax.export and persist it in the engine cache
(aot/cache.py), optionally fusing LoRAs first (build.py:14-24 parity).
Serving then hits the deserialize fast path — the analog of the reference's
"load engines without base weights" (lib/wrapper.py:409-512).

Usage:
  python -m ai_rtc_agent_tpu.assets.build_engines --model-id stabilityai/sd-turbo
  python -m ai_rtc_agent_tpu.assets.build_engines --model-id lykon/dreamshaper-8 \
      --lora ./models/civitai/studio-ghibli-style-lora.safetensors:1.0
"""

from __future__ import annotations

import argparse
import logging

import jax
import numpy as np

logger = logging.getLogger(__name__)


def build(
    model_id: str,
    lora_dict: dict | None = None,
    cache_dir: str | None = None,
):
    from ..aot.cache import EngineCache
    from ..models import registry
    from ..stream.engine import (
        StreamEngine,
        make_step_fn,
        params_variant_extra,
        stream_engine_key,
    )

    bundle = registry.load_model_bundle(model_id, lora_dict=lora_dict)
    cfg = registry.default_stream_config(model_id)
    # params dtype is part of the engine signature — must match serving
    # (StreamDiffusionPipeline casts identically)
    bundle.params = registry.cast_params(bundle.params, cfg.dtype)
    engine = StreamEngine(
        bundle.stream_models,
        bundle.params,
        cfg,
        bundle.encode_prompt,
        jit_compile=False,
    )
    engine.prepare(prompt="engine build probe")

    frame = np.zeros(
        (cfg.height, cfg.width, 3)
        if cfg.frame_buffer_size == 1
        else (cfg.frame_buffer_size, cfg.height, cfg.width, 3),
        np.uint8,
    )
    cache = EngineCache(cache_dir)
    if cfg.unet_cache_interval >= 2:
        # DeepCache pair: the capture and cached variants are distinct
        # executables (distinct keys), both needed at serve time
        variants = [("capture", "capture"), ("cached", "cached")]
    else:
        variants = [("full", None)]
    keys = []
    state = engine.state
    # params-variant key field (QUANT_WEIGHTS=w8): the build and serving
    # adoption must agree, or a quantized build would never be found (and
    # a dense engine could be adopted by a quantized server)
    qextra = params_variant_extra(bundle.params)
    for unet_variant, key_variant in variants:
        step = make_step_fn(bundle.stream_models, cfg, unet_variant=unet_variant)
        extra = {"variant": key_variant} if key_variant else {}
        key = stream_engine_key(model_id, cfg, **extra, **qextra)
        call = cache.load_or_build(
            key, step, (bundle.params, state, frame), donate_argnums=(1,)
        )
        # smoke-run each built engine once; thread the state forward — the
        # donated input buffers are consumed by the call
        state, out = call(bundle.params, state, frame)
        jax.block_until_ready(out)
        logger.info(
            "engine %s built and verified (out %s)", key, np.asarray(out).shape
        )
        keys.append(key)
    # every key built this run (a DeepCache config builds a PAIR — shipping
    # only one variant would defeat serve-time pair-atomic adoption)
    return keys, bundle


def build_scheduler_buckets(
    model_id: str,
    sessions: int,
    lora_dict: dict | None = None,
    cache_dir: str | None = None,
    bundle=None,
):
    """Prebuild the continuous batch scheduler's bucket geometries
    (stream/scheduler.py): one serialized executable per power-of-two
    occupancy bucket, keyed ``sbucket-k, sessions-S``.  Already-cached
    geometries are detected via ``EngineCache.has()`` and skipped, so a
    partial earlier build (or a crash mid-way) resumes instead of
    recompiling everything.  Uses the scheduler's own adoption path as the
    builder — the keys can never drift from what serving looks for.
    ``bundle``: an already-loaded-and-cast ModelBundle (main() reuses
    build()'s — the checkpoint read and cast are not paid twice)."""
    from ..models import registry
    from ..stream.scheduler import BatchScheduler

    cfg = registry.default_stream_config(model_id)
    if bundle is None:
        bundle = registry.load_model_bundle(model_id, lora_dict=lora_dict)
        bundle.params = registry.cast_params(bundle.params, cfg.dtype)
    # dp=1 explicitly: serialized executables are per-topology, so only
    # the single-device geometries are buildable — a BATCHSCHED_DP env
    # leaking into the build CLI must not flip the keys to the (never
    # serialized) sharded variants; dp>1 serving relies on prewarm
    sched = BatchScheduler(
        bundle.stream_models, bundle.params, cfg, bundle.encode_prompt,
        model_id=model_id, max_sessions=sessions,
        prewarm=False, aot_build_on_miss=False, cache_dir=cache_dir,
        dp=1,
    )
    try:
        status = sched.aot_status(model_id, cache_dir=cache_dir)
        missing = [kv for kv, built in status.items() if not built]
        for (k, variant), built in sorted(status.items()):
            logger.info(
                "scheduler bucket %d/%d (%s): %s",
                k, sessions, variant, "cached" if built else "building",
            )
        if missing and not sched.use_aot_cache(
            model_id, cache_dir=cache_dir, build_on_miss=True
        ):
            raise RuntimeError(
                f"scheduler bucket build failed for {model_id} "
                f"sessions={sessions}"
            )
        logger.info(
            "scheduler bucket engine(s) ready for %s sessions=%d "
            "(%d built, %d already cached)",
            model_id, sessions, len(missing), len(status) - len(missing),
        )
    finally:
        sched.close()


def main(argv=None):
    logging.basicConfig(level=logging.INFO)
    ap = argparse.ArgumentParser()
    ap.add_argument("--model-id", default="stabilityai/sd-turbo")
    ap.add_argument(
        "--lora",
        action="append",
        default=[],
        help="path.safetensors:scale (repeatable)",
    )
    ap.add_argument("--cache-dir", default=None)
    ap.add_argument(
        "--controlnet", default=None,
        help="ControlNet model id: builds the conditioned variant of "
             "<model-id>+<this id> (reference lib/wrapper.py:870-877)",
    )
    ap.add_argument(
        "--sched-buckets", type=int, default=0, metavar="S",
        help="also prebuild the continuous batch scheduler's bucket "
             "geometries for S session slots (one engine per power-of-two "
             "occupancy; already-cached buckets are skipped)",
    )
    args = ap.parse_args(argv)
    from ..utils.device import require_device

    require_device()  # an engine is built for the device that will serve it
    lora_dict = {}
    for spec in args.lora:
        path, _, scale = spec.rpartition(":")
        lora_dict[path or spec] = float(scale) if path else 1.0
    from ..models.registry import compose_model_id

    model_id = compose_model_id(args.model_id, args.controlnet)
    _, bundle = build(model_id, lora_dict or None, args.cache_dir)
    if args.sched_buckets:
        build_scheduler_buckets(
            model_id, args.sched_buckets, lora_dict or None,
            args.cache_dir, bundle=bundle,
        )


if __name__ == "__main__":
    main()
