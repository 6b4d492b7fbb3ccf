#!/usr/bin/env python
"""The ``joint_attention`` kernel alone on the chip, block plan by block plan.

At SD3's two call shapes (``sd3flash4x512``: four stream rows, 24 heads of
64 over the 1178 joint keys; queries the 1178 joint tokens, or the 1024
image tokens of the last block), under ``vmap`` k=1 as the bucket step runs
it, each candidate ``(block_q, block_k)`` is compiled and timed over 20
calls inside one jit, each call's output the next call's queries, so the
device runs them back to back; once more under the profiler, whose trace
gives the kernel's own device time (the wall clock also holds the
re-layouts of q and the output between calls).  ``block_k`` equal to the
keys is a single pass over a whole-K score tile.  Prints one line a case
(the kernel's ms a call, its roofline share: max(operations / 197e12,
bytes / 819e9) over that time, as ``joint_attention_roofline`` counts it;
the wall clock's ms a call; the largest difference from XLA attention at
``highest`` precision), then one JSON line.

    python scripts/joint_attention_sweep.py [--plans 592x1178,1178x256,...]

Chip only: a time from the CPU says nothing about the chip.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

B, HEADS, HEAD_DIM, KEYS = 4, 24, 64, 1178
TRIPS, REPS = 20, 7
PEAK_FLOPS, PEAK_BYTES = 197e12, 819e9
# block_q x block_k; 0 for the wrapper's own choice (``_joint_blocks``)
PLANS = (
    "0x0", "592x1178", "512x1178", "400x1178", "1178x256", "592x256", "1178x384", "592x384",
    "1178x512", "592x512", "400x1024", "592x1024", "1178x1024",
)


def roof_ms(lq: int) -> float:
    flops = 4 * B * lq * KEYS * HEADS * HEAD_DIM
    moved = 2 * HEADS * HEAD_DIM * 2 * B * (lq + KEYS)
    return 1e3 * max(flops / PEAK_FLOPS, moved / PEAK_BYTES)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--plans", default=",".join(PLANS),
                    help="block_q x block_k, comma-separated (0 = the default)")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from jax._src.lib import _profiler

    from ai_rtc_agent_tpu.ops.pallas import count_attention_paths
    from ai_rtc_agent_tpu.ops.pallas.attention import _xla_attention, joint_attention
    from ai_rtc_agent_tpu.utils.device import require_device
    from benchmark import trace_reduce

    device = require_device()
    if device["platform"] != "tpu":
        raise SystemExit(f"a kernel's time is the chip's; this is {device['platform']}")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 0
    rows = []
    for lq in (KEYS, 1024):
        keys = jax.random.split(jax.random.PRNGKey(lq), 3)
        q = jax.random.normal(keys[0], (1, B, lq, HEADS, HEAD_DIM), jnp.bfloat16)
        k, v = (jax.random.normal(key, (1, B, KEYS, HEADS, HEAD_DIM), jnp.bfloat16)
                for key in keys[1:])
        with jax.default_matmul_precision("highest"):
            want = np.asarray(jax.jit(jax.vmap(_xla_attention))(q, k, v), np.float32)
        for plan in args.plans.split(","):
            bq, bk = (int(n) or None for n in plan.split("x"))
            one = jax.vmap(
                lambda q, k, v: joint_attention(q, k, v, block_q=bq, block_k=bk)
            )

            def trips(q, k, v):
                return jax.lax.fori_loop(0, TRIPS, lambda _, q: one(q, k, v), q)

            try:
                with count_attention_paths() as paths:
                    got = np.asarray(jax.jit(one)(q, k, v), np.float32)
                run = jax.jit(trips)
                run(q, k, v).block_until_ready()
            except Exception as e:  # a plan Mosaic refuses is a row, not the end
                print(f"lq {lq} plan {plan}: FAIL {str(e).splitlines()[0][:200]}", flush=True)
                rows.append({"lq": lq, "plan": plan, "error": str(e).splitlines()[0][:200]})
                continue
            times = []
            for _ in range(REPS):
                t0 = time.perf_counter()
                run(q, k, v).block_until_ready()
                times.append((time.perf_counter() - t0) * 1e3 / TRIPS)
            session = _profiler.ProfilerSession(options)
            run(q, k, v).block_until_ready()
            events = trace_reduce.reduce_trace(
                trace_reduce.from_bytes(session.stop()), ("joint_attention",)
            )["kernels"]["joint_attention"]
            ms = 1e3 * sum(events) / len(events)
            row = {
                "lq": lq, "plan": plan, "path": dict(paths), "kernel_ms": round(ms, 4),
                "calls": len(events), "roofline_pct": round(100 * roof_ms(lq) / ms, 2),
                "wall_ms": round(statistics.median(times), 4),
                "max_abs_diff": round(float(np.max(np.abs(got - want))), 5),
            }
            rows.append(row)
            print(
                f"lq {lq} plan {plan}: kernel {ms:.4f} ms x{len(events)}, "
                f"{row['roofline_pct']:.2f} % of roof; wall {row['wall_ms']:.4f} ms; "
                f"max|d| {row['max_abs_diff']:.5f} {row['path']}", flush=True,
            )
    print(json.dumps({"sweep": "joint_attention", "device": device, "rows": rows}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
