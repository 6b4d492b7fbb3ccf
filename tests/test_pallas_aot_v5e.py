"""Both Pallas kernels must lower and compile for a v5e — checked here,
ahead of time, with no device present.

libtpu compiles for a topology description, so a kernel the chip's compiler
refuses (the B>1 epilogue block shape was one: "last two dimensions of your
block shape [must be] divisible by 8 and 128") fails in the sandbox first.
These are compile facts, not speeds and not numerics: parity on the chip is
``scripts/tpu_numerics_check.py`` (the kernel phase of ``chip_smoke.py``).
Kernel-only compiles, about a second each.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from ai_rtc_agent_tpu.ops.lcm import StepCoeffs
from ai_rtc_agent_tpu.ops.pallas import mosaic_kernel_counts
from ai_rtc_agent_tpu.ops.pallas.attention import flash_attention
from ai_rtc_agent_tpu.ops.pallas.fused_scheduler import fused_stream_epilogue


@pytest.fixture(scope="module")
def v5e():
    from jax.experimental import topologies

    topo = topologies.get_topology_desc(
        topology_name="v5e:2x2", platform="tpu"
    )
    sharding = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype: jax.ShapeDtypeStruct(
        shape, dtype, sharding=sharding
    )


@pytest.mark.parametrize(
    "q_shape,kv_shape,vmap_k",
    [
        ((1, 4096, 5, 64), (1, 4096, 5, 64), 0),  # SD2.1 top self-attention
        ((4, 4096, 8, 40), (4, 4096, 8, 40), 0),  # SD1.5 stream batch, d=40
        ((1, 4096, 5, 64), (1, 77, 5, 64), 0),  # cross-attention, block_k=77
        ((1, 4096, 5, 64), (1, 4096, 5, 64), 2),  # the scheduler's k=2 bucket
    ],
)
def test_flash_attention_compiles_for_v5e(v5e, q_shape, kv_shape, vmap_k):
    fn = lambda q, k, v: flash_attention(q, k, v, interpret=False)  # noqa: E731
    if vmap_k:
        fn = jax.vmap(fn)
        q_shape, kv_shape = (vmap_k,) + q_shape, (vmap_k,) + kv_shape
    compiled = jax.jit(fn).lower(
        v5e(q_shape, jnp.bfloat16),
        v5e(kv_shape, jnp.bfloat16),
        v5e(kv_shape, jnp.bfloat16),
    ).compile()
    assert mosaic_kernel_counts(compiled.as_text()) == {"flash_attention": 1}


@pytest.mark.parametrize(
    "batch,cfg_type,vmap_k",
    [
        (1, "none", 0),  # sd-turbo
        (4, "self", 0),  # the reference's default 4-stage stream batch
        (4, "self", 2),
    ],
)
def test_fused_epilogue_compiles_for_v5e(v5e, batch, cfg_type, vmap_k):
    def fn(x, eps, stock, noise, coeff, g, d):
        coeffs = StepCoeffs(
            jnp.arange(batch), coeff, coeff, coeff, coeff, coeff, coeff
        )
        return fused_stream_epilogue(
            x, eps, stock, noise, coeffs, g, d, cfg_type=cfg_type,
            interpret=False,
        )

    lead = ()
    if vmap_k:
        fn, lead = jax.vmap(fn), (vmap_k,)
    latent = v5e(lead + (batch, 64, 64, 4), jnp.bfloat16)
    compiled = jax.jit(fn).lower(
        latent, latent, latent, latent,
        v5e(lead + (batch,), jnp.float32),
        v5e(lead, jnp.float32),
        v5e(lead, jnp.float32),
    ).compile()
    assert mosaic_kernel_counts(compiled.as_text()) == {
        "fused_stream_epilogue": 1
    }
