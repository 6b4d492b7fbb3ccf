"""Pallas flash attention for UNet self/cross attention.

Replaces the xformers/TensorRT fused attention of the reference stack
(reference lib/wrapper.py:710-711 'xformers' acceleration) with a TPU
blockwise-softmax kernel: Q tiles stream over K/V tiles held in VMEM with
running max/denominator, so the [Lq, Lk] score matrix never materializes in
HBM.  Matters at SDXL@1024 (16k latent tokens: dense scores would be
16k x 16k x heads).

Non-causal (diffusion attention has no mask).  Interpret mode on an
explicitly requested CPU so the hermetic suite exercises the same code path.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import interpret_default

DEFAULT_BLOCK_Q = 256
DEFAULT_BLOCK_K = 256


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, *, block_k: int, scale: float):
    """One (batch*head, q-block) program: stream K/V blocks."""
    q = q_ref[...].astype(jnp.float32) * scale  # [bq, d]
    lk = k_ref.shape[0]
    bq, d = q.shape

    def body(i, carry):
        o, m, l = carry
        k = k_ref[pl.ds(i * block_k, block_k), :].astype(jnp.float32)  # [bk, d]
        v = v_ref[pl.ds(i * block_k, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # [bq, bk]
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        o_new = o * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        return o_new, m_new, l_new

    o0 = jnp.zeros((bq, d), jnp.float32)
    m0 = jnp.full((bq,), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((bq,), jnp.float32)
    o, m, l = jax.lax.fori_loop(0, lk // block_k, body, (o0, m0, l0))
    o_ref[...] = (o / l[:, None]).astype(o_ref.dtype)


def flash_attention(
    q,
    k,
    v,
    mask=None,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    interpret: bool | None = None,
):
    """q: [B, Lq, H, D], k/v: [B, Lk, H, D] -> [B, Lq, H, D].

    ``mask`` unsupported (diffusion attention is unmasked); raises if given.
    """
    if mask is not None:
        raise NotImplementedError("flash_attention is non-causal/unmasked")
    if interpret is None:
        interpret = interpret_default()
    b, lq, h, d = q.shape
    lk = k.shape[1]
    block_q = min(block_q, lq)
    block_k = min(block_k, lk)

    # pad sequence lengths to block multiples; padded K rows get -inf scores
    # naturally excluded because we pad K with zeros AND track true lk via
    # masking — simpler: require divisibility, pad otherwise
    pad_q = (-lq) % block_q
    pad_k = (-lk) % block_k
    if pad_k:
        # zero-pad K/V and rely on exp(s - m) weighting: zero K rows give
        # s=0 which is WRONG, so mask by appending -inf scores via a pad of
        # K that we explicitly exclude: simplest correct route is to fall
        # back to XLA attention for ragged tails.
        return _xla_attention(q, k, v)
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
        lq_p = lq + pad_q
    else:
        lq_p = lq

    scale = 1.0 / math.sqrt(d)
    # layout: fold batch*heads into grid dim 0; tiles [block, d]
    qt = q.transpose(0, 2, 1, 3).reshape(b * h, lq_p, d)
    kt = k.transpose(0, 2, 1, 3).reshape(b * h, lk, d)
    vt = v.transpose(0, 2, 1, 3).reshape(b * h, lk, d)

    out = pl.pallas_call(
        partial(_attn_kernel, block_k=block_k, scale=scale),
        grid=(b * h, lq_p // block_q),
        in_specs=[
            pl.BlockSpec((None, block_q, d), lambda g, i: (g, i, 0)),
            pl.BlockSpec((None, lk, d), lambda g, i: (g, 0, 0)),
            pl.BlockSpec((None, lk, d), lambda g, i: (g, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, block_q, d), lambda g, i: (g, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, lq_p, d), q.dtype),
        interpret=interpret,
        name="flash_attention",
    )(qt, kt, vt)

    out = out.reshape(b, h, lq_p, d).transpose(0, 2, 1, 3)
    return out[:, :lq]


def _xla_attention(q, k, v):
    scale = 1.0 / math.sqrt(q.shape[-1])
    logits = (
        jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32))
        * scale
    )
    w = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", w, v.astype(jnp.float32)).astype(q.dtype)
