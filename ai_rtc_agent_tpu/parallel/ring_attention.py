"""Ring attention — sequence/context parallelism over the `sp` mesh axis.

The reference has no long-context dimension (CLIP is 77 tokens, SD latents
are 4096 tokens — SURVEY.md section 5), but this framework treats
sequence/context parallelism as first-class: SDXL@1024 self-attention is
16k latent tokens and multi-peer batching multiplies that, so attention must
scale across chips.

Two standard schemes, both pure shard_map bodies over XLA collectives:

* :func:`ring_attention` — blockwise streaming-softmax attention; K/V shards
  rotate around the ICI ring via ``ppermute`` while each chip accumulates
  its queries' output with numerically-stable running max/denominator
  (the Ring Attention construction; memory O(L/n) per chip).
* :func:`ulysses_attention` — all_to_all reshard: tokens->heads, full local
  attention on a head slice, heads->tokens back (2 all_to_alls, best when
  heads >= chips).

Both compute EXACT attention — tested bitwise-close against the dense
reference on a virtual 8-device mesh.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def _ring_body(q, k, v, axis: str):
    """Per-shard body: q,k,v [B, Lloc, H, D] -> out [B, Lloc, H, D]."""
    n = lax.axis_size(axis)
    scale = 1.0 / math.sqrt(q.shape[-1])
    qf = q.astype(jnp.float32)

    b, lq, h, d = q.shape
    o = jnp.zeros((b, lq, h, d), jnp.float32)
    m = jnp.full((b, h, lq), -jnp.inf, jnp.float32)
    l = jnp.zeros((b, h, lq), jnp.float32)

    def one_block(carry, _):
        o, m, l, k_blk, v_blk = carry
        logits = (
            jnp.einsum("bqhd,bkhd->bhqk", qf, k_blk.astype(jnp.float32)) * scale
        )
        m_new = jnp.maximum(m, logits.max(axis=-1))
        p = jnp.exp(logits - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        o_new = o * corr.transpose(0, 2, 1)[..., None] + jnp.einsum(
            "bhqk,bkhd->bqhd", p, v_blk.astype(jnp.float32)
        )
        perm = [(i, (i + 1) % n) for i in range(n)]
        k_nxt = lax.ppermute(k_blk, axis_name=axis, perm=perm)
        v_nxt = lax.ppermute(v_blk, axis_name=axis, perm=perm)
        return (o_new, m_new, l_new, k_nxt, v_nxt), None

    (o, m, l, _, _), _ = lax.scan(one_block, (o, m, l, k, v), None, length=n)
    return (o / l.transpose(0, 2, 1)[..., None]).astype(q.dtype)


def ring_attention(q, k, v, mesh: Mesh, axis: str = "sp", batch_axis=None):
    """q,k,v: [B, L, H, D] globally; L sharded over `axis`.  ``batch_axis``
    optionally co-shards the batch dim (composes with dp under one jit)."""
    spec = P(batch_axis, axis, None, None)
    f = jax.shard_map(
        partial(_ring_body, axis=axis),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
    return f(q, k, v)


def _ulysses_body(q, k, v, axis: str):
    """tokens->heads all_to_all, local full attention, heads->tokens back."""
    # [B, Lloc, H, D] -> [B, L, Hloc, D]
    qg = lax.all_to_all(q, axis_name=axis, split_axis=2, concat_axis=1, tiled=True)
    kg = lax.all_to_all(k, axis_name=axis, split_axis=2, concat_axis=1, tiled=True)
    vg = lax.all_to_all(v, axis_name=axis, split_axis=2, concat_axis=1, tiled=True)
    scale = 1.0 / math.sqrt(q.shape[-1])
    logits = (
        jnp.einsum("bqhd,bkhd->bhqk", qg.astype(jnp.float32), kg.astype(jnp.float32))
        * scale
    )
    w = jax.nn.softmax(logits, axis=-1)
    og = jnp.einsum("bhqk,bkhd->bqhd", w, vg.astype(jnp.float32)).astype(q.dtype)
    return lax.all_to_all(og, axis_name=axis, split_axis=1, concat_axis=2, tiled=True)


def ulysses_attention(q, k, v, mesh: Mesh, axis: str = "sp", batch_axis=None):
    """q,k,v: [B, L, H, D] globally; L sharded over `axis`; needs H % n == 0."""
    spec = P(batch_axis, axis, None, None)
    f = jax.shard_map(
        partial(_ulysses_body, axis=axis),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
    return f(q, k, v)


def _cross_body(q, k, v, axis: str):
    """Cross-attention under SP: queries stay sharded over `axis`, the short
    encoder context (77 CLIP tokens) is replicated — every chip attends its
    own query slice against the full K/V with zero collectives."""
    scale = 1.0 / math.sqrt(q.shape[-1])
    logits = (
        jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32))
        * scale
    )
    w = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", w, v.astype(jnp.float32)).astype(q.dtype)


def sp_cross_attention(q, k, v, mesh: Mesh, axis: str = "sp", batch_axis=None):
    """q: [B, Lq, H, D] sharded over `axis`; k,v: [B, Lk, H, D] replicated."""
    qspec = P(batch_axis, axis, None, None)
    kvspec = P(batch_axis, None, None, None)
    f = jax.shard_map(
        partial(_cross_body, axis=axis),
        mesh=mesh,
        in_specs=(qspec, kvspec, kvspec),
        out_specs=qspec,
        check_vma=False,
    )
    return f(q, k, v)


def dense_reference(q, k, v):
    """Plain attention for correctness tests."""
    scale = 1.0 / math.sqrt(q.shape[-1])
    logits = (
        jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32))
        * scale
    )
    w = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", w, v.astype(jnp.float32)).astype(q.dtype)
