"""DeepCache quality/speed curve on any model family (PERF.md §DeepCache).

Runs the same moving-scene comparison as tests/test_deepcache_quality.py
but against an arbitrary model id (real weights when available) and also
times the stream, so one run yields the full quality/speed trade-off
table.  Prints ONE JSON line (it carries the backend it ran on).

Usage:
    python scripts/deepcache_quality.py --model-id tiny-test --frames 24
    python scripts/deepcache_quality.py --model-id stabilityai/sd-turbo \
        --size 512 --frames 48          # weights-bearing host / TPU window
"""

import argparse
import json
import sys
import time

sys.path.insert(0, ".")

import numpy as np




def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--model-id", default="tiny-test")
    ap.add_argument("--frames", type=int, default=24)
    ap.add_argument("--size", type=int, default=None)
    ap.add_argument("--intervals", default="2,3,5")
    ap.add_argument("--warmup", type=int, default=6)
    args = ap.parse_args()
    if args.warmup >= args.frames:
        ap.error(
            f"--warmup {args.warmup} must be < --frames {args.frames} "
            "(no steady-state frames would remain to compare)"
        )

    result = {"metric": "deepcache_quality", "model": args.model_id, "ok": False}
    try:
        import jax

        from ai_rtc_agent_tpu.models import registry
        from ai_rtc_agent_tpu.stream.engine import StreamEngine
        from ai_rtc_agent_tpu.utils.quality import moving_scene, psnr, ssim

        result["backend"] = jax.default_backend()

        def run(interval):
            bundle = registry.load_model_bundle(args.model_id)
            kw = {"unet_cache_interval": interval}
            if args.size:
                kw.update(width=args.size, height=args.size)
            cfg = registry.default_stream_config(args.model_id, **kw)
            eng = StreamEngine(
                models=bundle.stream_models,
                params=bundle.params,
                cfg=cfg,
                encode_prompt=bundle.encode_prompt,
            )
            eng.prepare("a moving scene", seed=7)
            frames = moving_scene(args.frames, cfg.height, cfg.width)
            outs = []
            t_steady = None
            for i, f in enumerate(frames):
                if i == args.warmup:
                    t_steady = time.perf_counter()
                outs.append(eng(f))
            dt = time.perf_counter() - t_steady
            fps = (args.frames - args.warmup) / dt if dt > 0 else 0.0
            return outs[args.warmup :], fps

        full, fps_full = run(0)
        rows = {"0": {"fps": round(fps_full, 2), "psnr_db": None, "ssim": None}}
        for interval in [int(x) for x in args.intervals.split(",")]:
            cached, fps_c = run(interval)
            rows[str(interval)] = {
                "fps": round(fps_c, 2),
                "psnr_db": round(
                    float(np.mean([psnr(a, b) for a, b in zip(full, cached)])), 2
                ),
                "ssim": round(
                    float(np.mean([ssim(a, b) for a, b in zip(full, cached)])), 4
                ),
            }
        result["rows"] = rows
        result["ok"] = True
    except Exception as e:  # noqa: BLE001 — contract line on any failure
        result["error"] = f"{type(e).__name__}: {e}"
    finally:
        print(json.dumps(result))
        sys.stdout.flush()
    return 0 if result["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
