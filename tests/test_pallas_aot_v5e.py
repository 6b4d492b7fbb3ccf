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


def _served_attention_shapes():
    """Every distinct (B, lq, lk, heads, head_dim) the two benchmark
    configurations reach: four tiers x self / 77-key cross x SD2.1 (sd-turbo:
    B=1, head dim 64) / SD1.5 (4-stage stream batch: B=4, 8 heads), each
    under ``vmap`` k=1 as the bucket step runs it, and one at k=2."""
    tiers = [
        # (B, heads, head_dim) per tier, tokens 4096 / 1024 / 256 / 64
        ((1, 5, 64), (1, 10, 64), (1, 20, 64), (1, 20, 64)),
        ((4, 8, 40), (4, 8, 80), (4, 8, 160), (4, 8, 160)),
    ]
    cases = []
    for config in tiers:
        for tokens, (b, h, d) in zip((4096, 1024, 256, 64), config):
            for lk in (tokens, 77):
                cases.append(((b, tokens, h, d), (b, lk, h, d), 1))
    cases.append(((1, 4096, 5, 64), (1, 4096, 5, 64), 2))
    return cases


@pytest.mark.parametrize("q_shape,kv_shape,vmap_k", _served_attention_shapes())
def test_flash_attention_compiles_for_v5e(v5e, q_shape, kv_shape, vmap_k):
    fn = jax.vmap(lambda q, k, v: flash_attention(q, k, v, interpret=False))
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


def test_scoped_bucket_step_compiles_for_v5e_with_the_same_kernels(monkeypatch):
    """ISSUE 25: the model's ``jax.named_scope``s are metadata.  The tiny
    bucket step compiled for the chip holds the same Mosaic kernels with the
    scopes as without, every kernel's custom call still carries the
    kernel's name (the trace readers find it by that), and the scopes are
    in the compiled text, where ``benchmark/scope_reduce.py`` reads them."""
    import contextlib

    from jax.experimental import topologies

    import ai_rtc_agent_tpu.ops.pallas.attention as A
    import ai_rtc_agent_tpu.ops.pallas.fused_scheduler as F
    from ai_rtc_agent_tpu.models import registry
    from ai_rtc_agent_tpu.stream.scheduler import BatchScheduler

    # the program asks the backend whether to interpret its kernels; this
    # process sees a CPU, the compile below is for the chip
    monkeypatch.setattr(A, "interpret_default", lambda: False)
    monkeypatch.setattr(F, "interpret_default", lambda: False)
    topo = topologies.get_topology_desc(topology_name="v5e:2x2", platform="tpu")
    chip = SingleDeviceSharding(topo.devices[0])

    def compiled_text():
        bundle = registry.load_model_bundle("tiny-test", attn_impl="pallas")
        cfg = registry.default_stream_config(
            "tiny-test", attn_impl="pallas", use_fused_epilogue=True,
            dtype="bfloat16", height=32, width=32,
        )
        s = BatchScheduler(
            bundle.stream_models, registry.cast_params(bundle.params, cfg.dtype),
            cfg, bundle.encode_prompt, max_sessions=1, prewarm=False,
        )
        try:
            specs = jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip),
                s._bucket_specs(1),
            )
            return s._bucket_step(1, "full").lower(*specs).compile().as_text()
        finally:
            s.close()

    scoped = compiled_text()
    kernels = mosaic_kernel_counts(scoped)
    assert kernels["fused_stream_epilogue"] == 1 and kernels["flash_attention"] >= 4
    assert "unnamed" not in kernels
    for scope in ("vmap(unet)/down_0/resnet_0/", "transformer_0/self_attn/",
                  "vmap(vae_decode)/", "jit(bucket)/gather/"):
        assert scope in scoped, scope
    monkeypatch.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
    plain = compiled_text()
    assert mosaic_kernel_counts(plain) == kernels
    assert "self_attn" not in plain
