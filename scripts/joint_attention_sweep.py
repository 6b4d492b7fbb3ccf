#!/usr/bin/env python
"""The ``joint_attention`` or ``rope_attention`` kernel alone on the chip,
block plan by block plan.

``--kernel joint`` (the default): at SD3's two call shapes
(``sd3flash4x512``: four stream rows, 24 heads of 64 over the 1178 joint
keys; queries the 1178 joint tokens, or the 1024 image tokens of the last
block).  ``--kernel rope``: at FLUX's (``fluxschnell2x512``: two stream rows,
256 + 1024 joint tokens, 24 heads of 128), reading q, k and v from a double
block's fused ``[2, 1280, 3 x 3072]`` q/k/v, or from a single block's
``linear1`` output with the MLP's 12288 input columns after them.  Under
``vmap`` k=1 as the bucket step runs it, each candidate ``(block_q,
block_k)`` is compiled and timed over 20 calls inside one jit, each call's
output the next call's queries (and, for ``rope``, its keys: the whole
fused input moves), so the device runs them back to back; once more under
the profiler, whose trace gives the kernel's own device time (the wall clock
also holds what moves the operands between calls).  ``block_k`` equal to the
keys is a single pass over a whole-K score tile.  Prints one line a case
(the kernel's ms a call, its roofline share: max(operations / 197e12,
bytes / 819e9) over that time, as ``joint_attention_roofline`` and
``rope_attention_roofline`` count it; the wall clock's ms a call; the
largest difference from the float32 reference at ``highest`` precision),
then one JSON line.

    python scripts/joint_attention_sweep.py [--kernel rope] [--plans 0x0,640x512,...]

A plan of ``0x0`` calls the kernel with no block arguments at all, so the
same line can be read from a tree whose kernel takes none.  Chip only: a
time from the CPU says nothing about the chip.
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

TRIPS, REPS = 20, 7
PEAK_FLOPS, PEAK_BYTES = 197e12, 819e9
# block_q x block_k; 0 for the wrapper's own choice (``_joint_blocks``)
PLANS = {
    "joint": (
        "0x0", "592x1178", "512x1178", "400x1178", "1178x256", "592x256", "1178x384",
        "592x384", "1178x512", "592x512", "400x1024", "592x1024", "1178x1024",
    ),
    "rope": (
        "0x0", "1280x512", "640x512", "1280x256", "640x256", "1280x640", "640x640",
        "640x1280", "1280x1280",
    ),
}
# (rows, queries, keys, heads, head_dim, text tokens) of each kernel's calls
JOINT = [(4, lq, 1178, 24, 64, 0) for lq in (1178, 1024)]
ROPE = [(2, 1280, 1280, 24, 128, 256)]
# a rope call's fused input: q/k/v alone, or with a single block's MLP input
ROPE_EXTRA = (0, 12288)


def roof_ms(b: int, lq: int, lk: int, heads: int, head_dim: int, tables: bool) -> float:
    flops = 4 * b * lq * lk * heads * head_dim
    moved = 2 * heads * head_dim * 2 * b * (lq + lk) + (2 * 4 * lk * head_dim if tables else 0)
    return 1e3 * max(flops / PEAK_FLOPS, moved / PEAK_BYTES)


def _joint_case(jax, jnp, shape, extra):
    """(operands, the kernel as a function of them and its block keywords,
    one trip: the operands of the next call, the float32 reference)."""
    from ai_rtc_agent_tpu.ops.pallas.attention import _xla_attention, joint_attention

    b, lq, lk, heads, d, _ = shape
    keys = jax.random.split(jax.random.PRNGKey(lq), 3)
    q = jax.random.normal(keys[0], (1, b, lq, heads, d), jnp.bfloat16)
    k, v = (jax.random.normal(key, (1, b, lk, heads, d), jnp.bfloat16) for key in keys[1:])

    def kernel(ops, **kw):
        return jax.vmap(lambda q, k, v: joint_attention(q, k, v, **kw))(*ops)

    def trip(ops, out):
        return (out,) + ops[1:]

    with jax.default_matmul_precision("highest"):
        want = jax.jit(jax.vmap(_xla_attention))(q, k, v)
    return (q, k, v), kernel, trip, want


def _rope_case(jax, jnp, shape, extra):
    from ai_rtc_agent_tpu.models.flux import _attend
    from ai_rtc_agent_tpu.ops.pallas.attention import rope_attention

    b, lq, _, heads, d, text = shape
    inner = heads * d
    keys = jax.random.split(jax.random.PRNGKey(lq + extra), 4)
    qkv = jax.random.normal(keys[0], (1, b, lq, 3 * inner + extra), jnp.bfloat16)
    ang = jax.random.uniform(keys[1], (lq, d // 2), minval=-40.0, maxval=40.0)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    scales = tuple(1.0 + 0.2 * jax.random.normal(k, (2, d)) for k in keys[2:])

    def kernel(ops, **kw):
        fn = lambda x: rope_attention(x, cos, sin, *scales, heads, text, **kw)  # noqa: E731
        return jax.vmap(fn)(ops[0])

    def trip(ops, out):
        return (jax.lax.dynamic_update_slice(ops[0], out, (0, 0, 0, 0)),)

    with jax.default_matmul_precision("highest"):
        want = jax.jit(jax.vmap(
            lambda x: _attend(x.astype(jnp.float32), scales, (cos, sin), heads, text, "xla")
        ))(qkv)
    return (qkv,), kernel, trip, want


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--kernel", choices=sorted(PLANS), default="joint")
    ap.add_argument("--plans", default=None,
                    help="block_q x block_k, comma-separated (0x0 = the default; "
                         "default: every plan of the kernel's list)")
    args = ap.parse_args(argv)
    plans = (args.plans or ",".join(PLANS[args.kernel])).split(",")

    import jax
    import jax.numpy as jnp

    from jax._src.lib import _profiler

    from ai_rtc_agent_tpu.ops.pallas import count_attention_paths
    from ai_rtc_agent_tpu.utils.device import require_device
    from benchmark import trace_reduce

    device = require_device()
    if device["platform"] != "tpu":
        raise SystemExit(f"a kernel's time is the chip's; this is {device['platform']}")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 0
    name = f"{args.kernel}_attention"
    make = _rope_case if args.kernel == "rope" else _joint_case
    cases = (
        [(s, e) for s in ROPE for e in ROPE_EXTRA] if args.kernel == "rope"
        else [(s, 0) for s in JOINT]
    )
    rows = []
    for shape, extra in cases:
        b, lq, lk, heads, d, _ = shape
        ops, kernel, trip, want = make(jax, jnp, shape, extra)
        want = np.asarray(want, np.float32)
        label = f"lq {lq}" + (f" +{extra}" if args.kernel == "rope" else "")
        for plan in plans:
            bq, bk = (int(n) for n in plan.split("x"))
            kw = {key: n for key, n in (("block_q", bq), ("block_k", bk)) if n}
            one = lambda ops, kw=kw: kernel(ops, **kw)  # noqa: E731

            def trips(ops, one=one):
                return jax.lax.fori_loop(0, TRIPS, lambda _, ops: trip(ops, one(ops)), ops)

            try:
                with count_attention_paths() as paths:
                    got = np.asarray(jax.jit(one)(ops), np.float32)
                run = jax.jit(trips)
                jax.block_until_ready(run(ops))
            except Exception as e:  # a plan Mosaic refuses is a row, not the end
                print(f"{label} plan {plan}: FAIL {str(e).splitlines()[0][:200]}", flush=True)
                rows.append({"lq": lq, "extra": extra, "plan": plan,
                             "error": str(e).splitlines()[0][:200]})
                continue
            times = []
            for _ in range(REPS):
                t0 = time.perf_counter()
                jax.block_until_ready(run(ops))
                times.append((time.perf_counter() - t0) * 1e3 / TRIPS)
            session = _profiler.ProfilerSession(options)
            jax.block_until_ready(run(ops))
            events = trace_reduce.reduce_trace(
                trace_reduce.from_bytes(session.stop()), (name,)
            )["kernels"][name]
            ms = 1e3 * sum(events) / len(events)
            roof = roof_ms(b, lq, lk, heads, d, args.kernel == "rope")
            row = {
                "lq": lq, "extra": extra, "plan": plan, "path": dict(paths),
                "kernel_ms": round(ms, 4), "calls": len(events),
                "roofline_pct": round(100 * roof / ms, 2),
                "wall_ms": round(statistics.median(times), 4),
                "max_abs_diff": round(float(np.max(np.abs(got - want))), 5),
            }
            rows.append(row)
            print(
                f"{label} plan {plan}: kernel {ms:.4f} ms x{len(events)}, "
                f"{row['roofline_pct']:.2f} % of roof; wall {row['wall_ms']:.4f} ms; "
                f"max|d| {row['max_abs_diff']:.5f} {row['path']}", flush=True,
            )
    print(json.dumps({"sweep": name, "device": device, "rows": rows}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
