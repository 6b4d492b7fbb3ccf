"""Pallas kernel: the fused stream-step epilogue.

The north-star asks for the LCM scheduler step as a TPU kernel (BASELINE.json
north_star).  After the UNet returns eps_c, the remaining per-frame math is a
chain of elementwise ops over [B, h, w, 4] latents:

    R-CFG combine -> pred_x0 -> LCM blend -> ring renoise -> stock update

Done naively that's 5+ HBM round-trips of the latent tensors; this kernel
does ONE read of (x_t, eps_c, stock, noise) and one write of (denoised,
advanced, stock'), with the per-batch-entry scheduler coefficients prefetched
to SMEM.  Grid = batch entries; each program owns one latent slab in VMEM
(64x64x4 fp32 = 64 KiB, well under the ~16 MiB VMEM budget).  A slab is
presented as ``[N/128, 128]`` rows of lanes: Mosaic wants a block's last two
dimensions divisible by (8, 128) or equal to the array's, and a ``(1, N)``
block over a ``[B, N]`` array is neither once B > 1.

Runs under ``interpret=True`` on CPU for the hermetic test suite.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import interpret_default

LANE = 128


def _kernel(
    # scalar-prefetch refs (SMEM): [B] coefficient vectors + [2] scalars
    alpha_ref,
    sigma_ref,
    c_skip_ref,
    c_out_ref,
    next_alpha_ref,
    next_sigma_ref,
    gd_ref,  # [2] = (guidance, delta)
    # VMEM tensor refs, one [N/128, 128] slab per program
    x_ref,
    eps_ref,
    stock_ref,
    noise_ref,
    den_ref,
    adv_ref,
    stock_out_ref,
    *,
    cfg_type: str,
):
    b = pl.program_id(0)
    alpha = alpha_ref[b]
    sigma = sigma_ref[b]
    g = gd_ref[0]
    delta = gd_ref[1]

    x = x_ref[...]
    eps_c = eps_ref[...]

    if cfg_type in ("self", "initialize"):
        stock = stock_ref[...]
        eps = g * eps_c - (g - 1.0) * delta * stock
    else:  # none (full-CFG combining happens before the kernel)
        stock = stock_ref[...]
        eps = eps_c

    x0 = (x - sigma * eps) / alpha
    den = c_skip_ref[b] * x + c_out_ref[b] * x0
    adv = next_alpha_ref[b] * den + next_sigma_ref[b] * noise_ref[...]

    den_ref[...] = den
    adv_ref[...] = adv
    if cfg_type == "self":
        beta = sigma / jnp.maximum(alpha, 1e-6)
        # delta-free on purpose: delta enters only at combine time (see
        # ops/rcfg.update_stock_noise)
        stock_out_ref[...] = (eps_c + beta * stock) / (1.0 + beta)
    else:
        stock_out_ref[...] = stock


def fused_stream_epilogue(
    x_t,
    eps_c,
    stock,
    noise,
    coeffs,
    guidance,
    delta,
    cfg_type: str = "self",
    interpret: bool | None = None,
):
    """x_t/eps_c/stock/noise: [B, h, w, c] -> (denoised, advanced, stock').

    ``coeffs``: ops.lcm.StepCoeffs (jnp).  Shapes are flattened to
    [B, N/128, 128] slabs (N padded to the 128-lane minor dimension).
    """
    if interpret is None:
        interpret = interpret_default()
    B = x_t.shape[0]
    shape = x_t.shape
    n = int(jnp.size(x_t) // B)
    pad = (-n) % LANE
    rows = (n + pad) // LANE

    def flat(a):
        a = a.reshape(B, n).astype(jnp.float32)
        if pad:
            a = jnp.pad(a, ((0, 0), (0, pad)))
        return a.reshape(B, rows, LANE)

    gd = jnp.stack(
        [jnp.asarray(guidance, jnp.float32), jnp.asarray(delta, jnp.float32)]
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=7,
        grid=(B,),
        in_specs=[pl.BlockSpec((None, rows, LANE), lambda b, *_: (b, 0, 0))] * 4,
        out_specs=[pl.BlockSpec((None, rows, LANE), lambda b, *_: (b, 0, 0))] * 3,
    )
    out_shape = [jax.ShapeDtypeStruct((B, rows, LANE), jnp.float32)] * 3
    den, adv, stock_new = pl.pallas_call(
        partial(_kernel, cfg_type=cfg_type),
        grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=interpret,
        name="fused_stream_epilogue",
    )(
        coeffs.alpha.astype(jnp.float32),
        coeffs.sigma.astype(jnp.float32),
        coeffs.c_skip.astype(jnp.float32),
        coeffs.c_out.astype(jnp.float32),
        coeffs.next_alpha.astype(jnp.float32),
        coeffs.next_sigma.astype(jnp.float32),
        gd,
        flat(x_t),
        flat(eps_c),
        flat(stock),
        flat(noise),
    )

    def unflat(a):
        return a.reshape(B, rows * LANE)[:, :n].reshape(shape).astype(x_t.dtype)

    return unflat(den), unflat(adv), unflat(stock_new)
