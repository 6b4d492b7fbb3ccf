#!/usr/bin/env python
"""Kernel parity on the device: each Pallas kernel, compiled, against a
plain-XLA float32 reference at the shapes the serving graphs reach.

This is the kernel phase of ``chip_smoke.py`` and runs on its own through
the chip tool (``python scripts/tpu_numerics_check.py``).  The reference is
never interpret mode: on a TPU the kernel goes through Mosaic and the
reference through XLA at ``highest`` matmul precision, so the two share no
code below the jnp call.

Shapes ([B, L, heads, head_dim]; cross-attention has 77 keys, which the
kernel takes whole as one block, so it runs in the kernel):

* SD2.1 (sd-turbo, 512x512): L 4096/1024/256/64, heads 5/10/20/20, d 64
* SD1.5 4-stage stream batch (B=4): L 4096/1024/256/64, heads 8,
  d 40/80/160/160, self and cross at every tier
* SDXL (1024x1024): 4096 x 10 heads, 1024 x 20 heads, 77 keys of context;
  SDXL-Turbo at 512x512 (``sdxlturbo512``): 256 x 20 heads, self and cross,
  under ``vmap`` k=1 as its bucket step runs 120 of its 140 calls
* one self-attention shape under ``vmap`` k=2 (the scheduler's bucket step)
* the fused epilogue at B=1 ``none`` (64x64 and 128x128 latents), at B=4
  ``self`` (the reference's default stream batch), and under ``vmap`` k=2
* ``joint_attention`` at SD3's two calls (``sd3flash4x512``: four stream
  rows, 24 heads of 64, 1178 joint keys; queries the joint tokens or the
  1024 image tokens of the last block), under ``vmap`` k=1 as its bucket
  step runs them, and one at k=2

Each attention line names the operand layout the call's shapes chose
(``ops/pallas/attention.py``): ``packed`` (``[B, L, H*D]``, a group of heads
a program; every head dim 64 call with an even head count) or ``per_head``;
each joint line the body the call took (``joint_kloop``: K in blocks and a
ragged last block, or ``joint``: one pass).

``--tiny`` swaps in small shapes so the same code runs on the CPU in
interpret mode (``JAX_PLATFORMS=cpu``) in seconds.  Prints one line per
case, then one JSON line; exits non-zero when any case is out of tolerance.
"""

from __future__ import annotations

import argparse
import json
import logging
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# bf16 inputs and output, f32 accumulation: the kernel's error is the output
# rounding (2^-9 relative on values of order 1) plus the exp/sum reordering
ATTN_ATOL = 2e-2
# f32 elementwise chain: a few ulps
EPILOGUE_RTOL, EPILOGUE_ATOL = 1e-4, 1e-5

# name -> (q shape, kv shape, vmap k)
ATTN_CASES = {
    "sd21_self_4096x5x64": ((1, 4096, 5, 64), (1, 4096, 5, 64), 0),
    "sd21_self_1024x10x64": ((1, 1024, 10, 64), (1, 1024, 10, 64), 0),
    "sd21_self_256x20x64": ((1, 256, 20, 64), (1, 256, 20, 64), 0),
    "sd21_self_64x20x64": ((1, 64, 20, 64), (1, 64, 20, 64), 0),
    "sd21_cross_4096x5x64_k77": ((1, 4096, 5, 64), (1, 77, 5, 64), 0),
    "sd21_cross_64x20x64_k77": ((1, 64, 20, 64), (1, 77, 20, 64), 0),
    "sd15_b4_self_4096x8x40": ((4, 4096, 8, 40), (4, 4096, 8, 40), 0),
    "sd15_b4_self_1024x8x80": ((4, 1024, 8, 80), (4, 1024, 8, 80), 0),
    "sd15_b4_self_256x8x160": ((4, 256, 8, 160), (4, 256, 8, 160), 0),
    "sd15_b4_self_64x8x160": ((4, 64, 8, 160), (4, 64, 8, 160), 0),
    "sd15_b4_cross_4096x8x40_k77": ((4, 4096, 8, 40), (4, 77, 8, 40), 0),
    "sd15_b4_cross_1024x8x80_k77": ((4, 1024, 8, 80), (4, 77, 8, 80), 0),
    "sd15_b4_cross_256x8x160_k77": ((4, 256, 8, 160), (4, 77, 8, 160), 0),
    "sd15_b4_cross_64x8x160_k77": ((4, 64, 8, 160), (4, 77, 8, 160), 0),
    "sdxl_self_4096x10x64": ((1, 4096, 10, 64), (1, 4096, 10, 64), 0),
    "sdxl_self_1024x20x64": ((1, 1024, 20, 64), (1, 1024, 20, 64), 0),
    "sdxl_cross_1024x20x64_k77": ((1, 1024, 20, 64), (1, 77, 20, 64), 0),
    "sdxlturbo_self_256x20x64_vmap1": ((1, 256, 20, 64), (1, 256, 20, 64), 1),
    "sdxlturbo_cross_256x20x64_k77_vmap1": ((1, 256, 20, 64), (1, 77, 20, 64), 1),
    "sd21_self_4096x5x64_vmap2": ((1, 4096, 5, 64), (1, 4096, 5, 64), 2),
}
ATTN_CASES_TINY = {
    "tiny_self_64x2x16": ((1, 64, 2, 16), (1, 64, 2, 16), 0),
    "tiny_cross_64x2x16_k7": ((1, 64, 2, 16), (1, 7, 2, 16), 0),
    "tiny_b4_self_64x2x40": ((4, 64, 2, 40), (4, 64, 2, 40), 0),
    "tiny_b4_self_16x2x160": ((4, 16, 2, 160), (4, 16, 2, 160), 0),
    "tiny_b4_cross_64x2x80_k7": ((4, 64, 2, 80), (4, 7, 2, 80), 0),
    "tiny_b4_cross_16x2x160_k7": ((4, 16, 2, 160), (4, 7, 2, 160), 0),
    "tiny_self_64x2x16_vmap2": ((1, 64, 2, 16), (1, 64, 2, 16), 2),
}
# name -> (q shape, kv shape, vmap k), for ``joint_attention``
JOINT_CASES = {
    "sd3_joint_1178x24x64_vmap1": ((4, 1178, 24, 64), (4, 1178, 24, 64), 1),
    "sd3_joint_1024x24x64_k1178_vmap1": ((4, 1024, 24, 64), (4, 1178, 24, 64), 1),
    "sd3_joint_1178x24x64_vmap2": ((4, 1178, 24, 64), (4, 1178, 24, 64), 2),
}
JOINT_CASES_TINY = {
    "tiny_joint_96x2x64": ((1, 96, 2, 64), (1, 96, 2, 64), 0),
    "tiny_joint_40x2x64_k1025_vmap2": ((1, 40, 2, 64), (1, 1025, 2, 64), 2),
}
# name -> (B, latent h=w, cfg_type, vmap k)
EPILOGUE_CASES = {
    "b1_none_64": (1, 64, "none", 0),
    "b1_none_128": (1, 128, "none", 0),
    "b4_self_64": (4, 64, "self", 0),
    "b2_none_64": (2, 64, "none", 0),
    "b1_none_64_vmap2": (1, 64, "none", 2),
    "b4_self_64_vmap2": (4, 64, "self", 2),
}
EPILOGUE_CASES_TINY = {
    "b1_none_16": (1, 16, "none", 0),
    "b4_self_16": (4, 16, "self", 0),
    "b4_self_16_vmap2": (4, 16, "self", 2),
}


def check_attention(cases: dict, joint: bool = False) -> dict:
    """``flash_attention`` (or, ``joint``, ``joint_attention``) at each case
    against XLA attention at ``highest`` precision."""
    import jax
    import jax.numpy as jnp

    from ai_rtc_agent_tpu.ops.pallas import count_attention_paths
    from ai_rtc_agent_tpu.ops.pallas.attention import (
        _xla_attention,
        flash_attention,
        joint_attention,
    )

    def reference(q, k, v):
        with jax.default_matmul_precision("highest"):
            return _xla_attention(
                q.astype(jnp.float32), k.astype(jnp.float32),
                v.astype(jnp.float32),
            )

    out = {}
    for idx, (name, (qs, kvs, vk)) in enumerate(cases.items()):
        if vk:
            qs, kvs = (vk,) + qs, (vk,) + kvs
        k1, k2, k3 = jax.random.split(jax.random.PRNGKey(idx), 3)
        q = jax.random.normal(k1, qs, jnp.bfloat16)
        k = jax.random.normal(k2, kvs, jnp.bfloat16)
        v = jax.random.normal(k3, kvs, jnp.bfloat16)
        kernel, ref = joint_attention if joint else flash_attention, reference
        if vk:
            kernel, ref = jax.vmap(kernel), jax.vmap(ref)
        t0 = time.monotonic()
        with count_attention_paths() as paths:
            got = np.asarray(jax.jit(kernel)(q, k, v)).astype(np.float32)
        (path,) = paths  # one call traced, down one path
        want = np.asarray(jax.jit(ref)(q, k, v))
        diff = float(np.max(np.abs(got - want)))
        ok = bool(np.isfinite(got).all() and diff < ATTN_ATOL)
        out[name] = {"ok": ok, "max_abs_diff": round(diff, 6), "path": path}
        print(
            f"{'joint' if joint else 'attention'} {name}: {path} max|d|={diff:.5f} tol={ATTN_ATOL} "
            f"{'ok' if ok else 'FAIL'} ({time.monotonic() - t0:.1f}s)",
            flush=True,
        )
    return out


def check_epilogue(cases: dict) -> dict:
    import jax
    import jax.numpy as jnp

    from ai_rtc_agent_tpu.ops import lcm as L
    from ai_rtc_agent_tpu.ops import rcfg as R
    from ai_rtc_agent_tpu.ops.pallas.fused_scheduler import fused_stream_epilogue

    def make_coeffs(B):
        alpha = jnp.linspace(0.9, 0.5, B)
        return L.StepCoeffs(
            timesteps=jnp.arange(B, dtype=jnp.int32),
            alpha=alpha,
            sigma=jnp.sqrt(1.0 - alpha**2),
            c_skip=jnp.linspace(0.2, 0.4, B),
            c_out=jnp.linspace(0.8, 0.6, B),
            next_alpha=jnp.linspace(0.95, 0.6, B),
            next_sigma=jnp.linspace(0.3, 0.8, B),
        )

    out = {}
    for idx, (name, (B, hw, cfg_type, vk)) in enumerate(cases.items()):
        coeffs = make_coeffs(B)
        g, d = jnp.float32(1.2), jnp.float32(0.9)

        def kernel(x, eps, stock, noise):
            return fused_stream_epilogue(
                x, eps, stock, noise, coeffs, g, d, cfg_type=cfg_type
            )

        def reference(x, eps_c, stock, noise):
            # the composed path the engine runs with the kernel off
            # (ops/lcm + ops/rcfg)
            eps = (
                R.combine_residual(eps_c, stock, g, d)
                if cfg_type == "self" else eps_c
            )
            den = L.lcm_denoise(x, eps, coeffs)
            adv = L.renoise_next(den, noise, coeffs)
            new_stock = (
                R.update_stock_noise(stock, eps_c, coeffs.alpha, coeffs.sigma)
                if cfg_type == "self" else stock
            )
            return den, adv, new_stock

        shape = (B, hw, hw, 4)
        if vk:
            shape = (vk,) + shape
            kernel, reference = jax.vmap(kernel), jax.vmap(reference)
        keys = jax.random.split(jax.random.PRNGKey(100 + idx), 4)
        args = [jax.random.normal(k, shape, jnp.float32) for k in keys]
        got = jax.jit(kernel)(*args)
        want = jax.jit(reference)(*args)
        diff, ok = 0.0, True
        for a, b in zip(got, want):
            a, b = np.asarray(a), np.asarray(b)
            diff = max(diff, float(np.max(np.abs(a - b))))
            ok = ok and bool(
                np.isfinite(a).all()
                and np.allclose(a, b, rtol=EPILOGUE_RTOL, atol=EPILOGUE_ATOL)
            )
        out[name] = {"ok": ok, "max_abs_diff": round(diff, 8)}
        print(
            f"epilogue {name}: max|d|={diff:.2e} rtol={EPILOGUE_RTOL} "
            f"{'ok' if ok else 'FAIL'}",
            flush=True,
        )
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--tiny", action="store_true",
                    help="small shapes (CPU interpret mode, seconds)")
    args = ap.parse_args(argv)
    logging.basicConfig(level=logging.INFO, stream=sys.stderr)

    from ai_rtc_agent_tpu.utils.device import require_device

    device = require_device()
    attn = check_attention(ATTN_CASES_TINY if args.tiny else ATTN_CASES)
    epi = check_epilogue(EPILOGUE_CASES_TINY if args.tiny else EPILOGUE_CASES)
    joint = check_attention(JOINT_CASES_TINY if args.tiny else JOINT_CASES, joint=True)
    ok = all(c["ok"] for c in (*attn.values(), *epi.values(), *joint.values()))
    print(json.dumps({
        "check": "kernel_parity", "ok": ok, "device": device,
        "attention": attn, "epilogue": epi, "joint_attention": joint,
    }), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
