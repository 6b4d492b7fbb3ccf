#!/usr/bin/env python
"""Validate the AOT engine cache on real hardware (VERDICT r1 item 7).

Phase A (--build): build + persist the serving engine for the flagship
config, then serve N frames from the freshly built executable.
Phase B (default): FRESH process — adopt the cached engine WITHOUT
re-tracing, timing (a) process-start -> engine adopted, (b) fps of the
reloaded engine, and (c) whether donation survived jax.export
(the donated state buffer must be invalidated after a call; if it is not,
the latent ring is being copied every frame — reference fast-path contract:
lib/wrapper.py:409-512).

Run:
  python scripts/aot_tpu_check.py --build     # phase A (slow, compiles)
  python scripts/aot_tpu_check.py             # phase B (must be fast)

Prints one JSON line per phase.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

T_START = time.monotonic()


def build_engine(model_id: str, jit_compile: bool):
    import jax

    from ai_rtc_agent_tpu.models import registry
    from ai_rtc_agent_tpu.stream.engine import StreamEngine

    dtype = "bfloat16" if jax.default_backend() == "tpu" else "float32"
    bundle = registry.load_model_bundle(model_id)
    cfg = registry.default_stream_config(model_id, dtype=dtype)
    bundle.params = registry.cast_params(bundle.params, dtype)
    eng = StreamEngine(
        bundle.stream_models, bundle.params, cfg, bundle.encode_prompt,
        jit_compile=jit_compile,
    )
    eng.prepare("aot check", guidance_scale=1.0)
    return eng, cfg


def measure_fps(eng, cfg, frames: int = 20) -> float:
    rng = np.random.default_rng(0)
    frame = rng.integers(0, 256, (cfg.height, cfg.width, 3), np.uint8)
    eng(frame)  # warm
    t0 = time.monotonic()
    handles = [eng.submit(frame) for _ in range(frames)]
    for h in handles:
        eng.fetch(h)
    return frames / (time.monotonic() - t0)


def check_donation(eng, cfg) -> bool:
    """True when the serving step really donates: the previous state buffer
    must be deleted (accessing it raises) after one call."""
    import jax

    rng = np.random.default_rng(1)
    frame = rng.integers(0, 256, (cfg.height, cfg.width, 3), np.uint8)
    old_ring = eng.state["x_buf"] if eng.state["x_buf"].size else eng.state["noise"]
    eng(frame)
    try:
        jax.block_until_ready(old_ring)
        _ = np.asarray(old_ring)
        return False  # old buffer still alive -> state was copied
    except Exception:
        return True  # deleted -> donated in place


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--build", action="store_true")
    ap.add_argument("--model-id", default="stabilityai/sd-turbo")
    ap.add_argument("--frames", type=int, default=20)
    args = ap.parse_args()

    from ai_rtc_agent_tpu.utils.contract import sigterm_to_exception

    sigterm_to_exception("timeout")
    out = {"phase": "build" if args.build else "reload",
           "ok": False, "backend": "unknown"}
    try:
        import jax

        out["backend"] = jax.default_backend()
        if args.build:
            eng, cfg = build_engine(args.model_id, jit_compile=True)
            t0 = time.monotonic()
            ok = eng.use_aot_cache(args.model_id, build_on_miss=True)
            out["engine_built"] = bool(ok)
            out["build_s"] = round(time.monotonic() - t0, 1)
            out["fps"] = round(measure_fps(eng, cfg, args.frames), 2)
            out["donation_in_place"] = check_donation(eng, cfg)
            out["ok"] = bool(ok)
        else:
            # fast path: no jit wrapper at all — state built, engine adopted
            eng, cfg = build_engine(args.model_id, jit_compile=False)
            t0 = time.monotonic()
            ok = eng.use_aot_cache(args.model_id, build_on_miss=False)
            out["cache_hit"] = bool(ok)
            out["adopt_s"] = round(time.monotonic() - t0, 1)
            out["start_to_ready_s"] = round(time.monotonic() - T_START, 1)
            if ok:
                out["fps"] = round(measure_fps(eng, cfg, args.frames), 2)
                out["donation_in_place"] = check_donation(eng, cfg)
            out["ok"] = bool(ok)
    except BaseException as e:  # noqa: BLE001 — contract line on any failure
        out["error"] = f"{type(e).__name__}: {e}"
    finally:
        print(json.dumps(out))
        sys.stdout.flush()
    sys.exit(0 if out.get("ok") else 1)


if __name__ == "__main__":
    main()
