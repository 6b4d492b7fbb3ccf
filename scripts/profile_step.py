#!/usr/bin/env python
"""Capture a jax.profiler trace of the serving step (VERDICT r1 item 2).

Runs N warm frames, then traces M steps of the flagship config and writes a
TensorBoard-loadable trace directory plus a one-line JSON summary. Works on
CPU (tiny64) for plumbing checks; the real target is the TPU chip:

  python scripts/profile_step.py --config turbo512 --out /tmp/trace
  tensorboard --logdir /tmp/trace   # -> Profile tab

The trace shows the XLA op timeline — conv/attention kernel times, fusion
boundaries, host gaps between dispatches (the loop overhead that fps work
must attack first).
"""

from __future__ import annotations

import argparse
import json
import sys
import time


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="turbo512",
                    choices=["turbo512", "lcm4x512", "sdxl1024", "tiny64"])
    ap.add_argument("--out", default="/tmp/rtc_trace")
    ap.add_argument("--warm", type=int, default=5)
    ap.add_argument("--steps", type=int, default=10)
    args = ap.parse_args()

    sys.path.insert(0, ".")
    import numpy as np

    import jax
    from bench import build_engine

    eng, cfg = build_engine(args.config)
    rng = np.random.default_rng(0)
    frame = rng.integers(0, 256, (cfg.height, cfg.width, 3), dtype=np.uint8)

    t0 = time.monotonic()
    for _ in range(args.warm):
        eng(frame)
    warm_s = time.monotonic() - t0

    t0 = time.monotonic()
    with jax.profiler.trace(args.out):
        handles = [eng.submit(frame) for _ in range(args.steps)]
        for h in handles:
            eng.fetch(h)
    traced_s = time.monotonic() - t0

    print(json.dumps({
        "config": args.config,
        "backend": jax.default_backend(),
        "warm_s": round(warm_s, 2),
        "traced_steps": args.steps,
        "traced_s": round(traced_s, 3),
        "fps_in_trace": round(args.steps / traced_s, 2),
        "trace_dir": args.out,
    }))


if __name__ == "__main__":
    main()
