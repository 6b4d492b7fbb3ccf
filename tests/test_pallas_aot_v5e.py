"""Both Pallas kernels must lower and compile for a v5e — checked here,
ahead of time, with no device present.

libtpu compiles for a topology description, so a kernel the chip's compiler
refuses (the B>1 epilogue block shape was one: "last two dimensions of your
block shape [must be] divisible by 8 and 128") fails in the sandbox first.
These are compile facts, not speeds and not numerics: parity on the chip is
``scripts/tpu_numerics_check.py`` (the kernel phase of ``chip_smoke.py``).
Kernel-only compiles, single attention blocks and single resnets, one to
ten seconds each.
"""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from ai_rtc_agent_tpu.ops.lcm import StepCoeffs
from ai_rtc_agent_tpu.ops.pallas import (
    count_attention_paths,
    f32_relayout_copies,
    mosaic_kernel_counts,
)
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
    """Every distinct (B, lq, lk, heads, head_dim) the three benchmark
    configurations reach: the tiers x self / 77-key cross of SD2.1
    (``turbo512``: B=1, head dim 64), SD1.5 (``lcm4x512``: 4-stage stream
    batch, B=4, 8 heads) and SDXL (``sdxlturbo512``: B=1, head dim 64, the
    1024- and 256-token tiers, which the kernel sees as SD2.1's middle two:
    its 2048-wide context is the block test's), each under ``vmap`` k=1 as
    the bucket step runs it, and one at k=2."""
    tiers = [
        # (tokens, B, heads, head_dim) per tier
        ((4096, 1, 5, 64), (1024, 1, 10, 64), (256, 1, 20, 64), (64, 1, 20, 64)),
        ((4096, 4, 8, 40), (1024, 4, 8, 80), (256, 4, 8, 160), (64, 4, 8, 160)),
        ((1024, 1, 10, 64), (256, 1, 20, 64)),
    ]
    cases = []
    for config in tiers:
        for tokens, b, h, d in config:
            for lk in (tokens, 77):
                case = ((b, tokens, h, d), (b, lk, h, d), 1)
                if case not in cases:
                    cases.append(case)
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


# (B, tokens, heads, head_dim, context width or None for self-attention): the
# attention blocks whose kernel takes its operands packed, per configuration
_PACKED_BLOCKS = [
    # turbo512 (SD2.1, OpenCLIP-H context): all but the 5-head tier
    (1, 1024, 10, 64, None), (1, 1024, 10, 64, 1024),
    (1, 256, 20, 64, None), (1, 256, 20, 64, 1024),
    (1, 64, 20, 64, None), (1, 64, 20, 64, 1024),
    # sdxlturbo512: all 140 calls of its step are one of these four
    (1, 1024, 10, 64, 2048), (1, 256, 20, 64, 2048),
    # lcm4x512 (SD1.5, CLIP-L context): head dims 80 (self) and 160
    (4, 1024, 8, 80, None), (4, 256, 8, 160, None), (4, 256, 8, 160, 768),
    (4, 64, 8, 160, None), (4, 64, 8, 160, 768),
]


@pytest.mark.parametrize("b,tokens,heads,head_dim,context_dim", _PACKED_BLOCKS)
def test_attention_block_compiles_for_v5e_without_relayout_copies(
    v5e, monkeypatch, b, tokens, heads, head_dim, context_dim
):
    """ISSUE 32: the guard on what surrounds the kernel.  One whole
    ``models/layers.py attention()`` block, weights as jit arguments and
    ``vmap`` k=1 as ``bucket(params, ...)`` has them, compiled for the chip.
    While the kernel asked for ``[B*H, L, D]``, XLA folded that transpose
    into the projections by copying each projection's weight into the
    transposed layout on every step (a jit argument's layout is fixed), and
    copied the output back to ``[L, H*D]``: 4 ``copy`` ops a block, 560 a
    ``sdxlturbo512`` step.  With the operands packed there is one Mosaic
    call and no ``copy`` of a weight's shape or of the ``[.., L, H, D]``
    output's."""
    import ai_rtc_agent_tpu.ops.pallas.attention as A
    from ai_rtc_agent_tpu.models.layers import attention

    # the program asks the backend whether to interpret its kernels; this
    # process sees a CPU, the compile below is for the chip
    monkeypatch.setattr(A, "interpret_default", lambda: False)
    inner = heads * head_dim
    kv_in = context_dim or inner
    weights = {
        "to_q": {"kernel": v5e((inner, inner), jnp.bfloat16)},
        "to_k": {"kernel": v5e((kv_in, inner), jnp.bfloat16)},
        "to_v": {"kernel": v5e((kv_in, inner), jnp.bfloat16)},
        "to_out": {
            "kernel": v5e((inner, inner), jnp.bfloat16),
            "bias": v5e((inner,), jnp.bfloat16),
        },
    }
    x = v5e((1, b, tokens, inner), jnp.bfloat16)
    context = v5e((1, b, 77, context_dim), jnp.bfloat16) if context_dim else None

    def block(p, x, context):
        one = lambda x, c: attention(p, x, c, heads, attn_impl="pallas")
        if context is None:
            return jax.vmap(lambda x: one(x, None))(x)
        return jax.vmap(one)(x, context)

    with count_attention_paths() as paths:
        lowered = jax.jit(block).lower(weights, x, context)
    assert dict(paths) == {"packed": 1}
    text = lowered.compile().as_text()
    assert mosaic_kernel_counts(text) == {"flash_attention": 1}
    copied = [
        tuple(int(n) for n in dims.split(",") if n != "1")
        for dims in re.findall(r"= \w+\[([\d,]*)\]\S* copy\(", text)
    ]
    forbidden = {(inner, inner), (kv_in, inner)}
    token_major = sorted(n for n in (b, tokens, heads, head_dim) if n != 1)
    relayouts = [
        dims for dims in copied
        if dims in forbidden or sorted(dims) == token_major
    ]
    assert not relayouts, relayouts


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


@pytest.mark.parametrize(
    "vmap_k,n,side,c",
    [
        (1, 1, 64, 320),  # turbo512 / sdxlturbo512, tier 0: 2 at the parent
        (1, 1, 32, 640),  # 2 at the parent
        (1, 4, 64, 320),  # lcm4x512, tier 0: 3 at the parent
    ],
)
def test_resnet_compiles_for_v5e_without_float32_relayouts(v5e, vmap_k, n, side, c):
    """ISSUE 35: one ``models/unet.py _resnet`` under ``vmap`` as the bucket
    step runs it, compiled for the chip.  ``group_norm`` written as
    ``x.astype(f32).reshape(n, h*w, g, c//g)`` made XLA write the activation
    out in float32 and transpose that copy so that a group's channels lay
    together (two activation-sized float32 ``copy`` / ``copy_*_fusion``
    outputs a resnet, read off the parent's tree: PERF.md section 6); after
    a convolution at n=4 its float32 output in the windowed layout
    was re-laid out twice more.  With per-channel sums (and, at several
    rows, the barrier that keeps the convert out of the convolution) no
    such tensor is left."""
    from ai_rtc_agent_tpu.models.unet import _resnet

    leaf = lambda *shape: v5e(shape, jnp.bfloat16)
    p = {
        "norm1": {"scale": leaf(c), "bias": leaf(c)},
        "conv1": {"kernel": leaf(3, 3, c, c), "bias": leaf(c)},
        "time_emb_proj": {"kernel": leaf(1280, c), "bias": leaf(c)},
        "norm2": {"scale": leaf(c), "bias": leaf(c)},
        "conv2": {"kernel": leaf(3, 3, c, c), "bias": leaf(c)},
    }
    fn = lambda p, x, temb: jax.vmap(lambda x, t: _resnet(p, x, t, 32))(x, temb)
    text = jax.jit(fn).lower(
        p, leaf(vmap_k, n, side, side, c), leaf(vmap_k, n, 1280)
    ).compile().as_text()
    assert f32_relayout_copies(text) == {"count": 0, "bytes": 0}


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
