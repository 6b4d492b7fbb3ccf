"""Pallas flash attention for UNet self/cross attention.

Replaces the xformers/TensorRT fused attention of the reference stack
(reference lib/wrapper.py:710-711 'xformers' acceleration) with a TPU
blockwise-softmax kernel: a Q tile meets K/V, which sit whole in VMEM for
a group of heads, block by block with a running max/denominator, so the
[Lq, Lk] score matrix never materializes in HBM.

The layout at the kernel's boundary (PERF.md section 6, PR 32): operands go
in, and the output comes back, as ``[B, L, H*D]``, which is how the q / k / v
projections write them and ``to_out`` reads them; a program takes the column
block of a group of heads and separates them by static lane slices.  Asked
for ``[B*H, L, D]`` instead, XLA folds the transpose into each projection by
copying the projection's *weight* into the transposed layout on every step
(a jit argument's layout is fixed) and copies the output back: 560 ops a
step of the SDXL graph that compute nothing.  The shapes that stay a head a
program, and why, are ``_choose_blocks``'s.

What the MXU is handed: ``q``, ``k`` and ``v`` in the dtype they arrive in
(bf16 in every served graph) and ``p`` cast to ``v``'s dtype, both
contractions accumulating in float32.  The scores, the softmax statistics
and the output accumulator are float32 (the v5e has no bf16 VPU or EUP), and
the softmax scale multiplies the float32 scores, not ``q``.  A float32 caller
gets float32 operands: the dtype decides, nothing else.

What sets the kernel's pace on a v5e (PERF.md section 6, PR 26) is not the
operands' dtype -- Mosaic serves a default-precision float32 contraction in
one bf16 pass -- but the K loop: as a rolled ``fori_loop`` of 256-key blocks
each iteration waits out its own matmul -> max -> exp -> sum -> matmul chain
(0.69 ms a 4096-token SD2.1 call).  So the loop is unrolled, which lets the
scheduler overlap one block's softmax with the next block's QK^T, keys that
fit one block are a single pass with no running rescale, and the blocks come
from the call's shapes (``_choose_blocks``): 0.24 ms for the same call.
Measured on the chip at every shape the SD2.1 (head dim 64, B=1) and SD1.5
(head dims 40/80/160, B=4) graphs reach: Lq 4096/1024/256/64, self and
77-key cross.

Non-causal (diffusion attention has no mask).  Interpret mode on an
explicitly requested CPU so the hermetic suite exercises the same code path.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import interpret_default, note_attention_path

# one pass over the keys up to here; longer K is met in blocks
_MAX_BLOCK_K = 1024
# f32 score-tile elements ([block_q, block_k], lanes padded to 128) a program
# may hold with 2-byte operands: 4 MB in a single pass, half of that when the
# unrolled K loop keeps several tiles alive.  Twice these did not fit the
# v5e's 16 MB of scoped VMEM at head dim 40
_TILE_SINGLE_PASS = 1 << 20
_TILE_LOOP = 1 << 19
# unrolled up to here (4 steps at 4096 keys); a longer loop stays rolled
_MAX_UNROLL = 8
# what a program that takes a group of heads may give its double-buffered
# blocks: K and V of the group whole (5.2 MB for 8 heads of 80 at 1024 keys
# compiles and runs, 12.6 MB does not fit), and the q and o blocks
_PACKED_KV_BYTES = 6 << 20
_PACKED_QO_BYTES = 2 << 20


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _choose_blocks(lq: int, lk: int, heads: int, head_dim: int, itemsize: int):
    """(heads a program takes, block_q, block_k) from the call's shapes.

    Heads: the fewest that fill whole 128-lane tiles side by side (a pair at
    head dim 64, 4 at 160, all of 8 at 40 or 80), so that the operands stay
    ``[B, L, H*D]`` as the projections leave them.  0, a head a program on
    transposed operands, where the chip (PERF.md section 6, PR 32) showed
    the call and its surroundings no faster packed: a head count the group
    does not divide (5 heads of 64), K and V of the group too large to sit
    double-buffered beside a program's tiles (8 heads of 40 at 4096 keys:
    12.6 MB), and heads that straddle lane tiles (head dims 40 / 80 / 160)
    with more than four queries a key, where the lane shifts of every row
    of q and o find no score work to hide behind (1024 or 4096 queries on
    77 keys).
    ``block_k``: all of K up to ``_MAX_BLOCK_K`` (the 77-key cross-attention
    and every tier below 4096 tokens), else the largest power-of-two block
    that divides ``lk`` (where none does, the caller's ragged-tail fall-back
    takes the call).
    ``block_q``: as many queries as keep the float32 score tile and the
    [block_q, head_dim] tiles inside the budget, which scales with the
    operand's size.  On the chip (PERF.md section 6, PR 26): 512/1024 at
    4096 tokens, 1024/1024 at 1024, the whole of Lq against 77 keys; for a
    group of heads half the tile in the K loop (two heads' unrolled loops
    keep twice the tiles alive: 17 MB of scoped VMEM at 512/1024), and q
    and o blocks of at most ``_PACKED_QO_BYTES``."""
    if lk <= _MAX_BLOCK_K:
        block_k, tile = lk, _TILE_SINGLE_PASS
    else:
        block_k = next((b for b in (1024, 512, 256, 128) if lk % b == 0), 128)
        tile = _TILE_LOOP
    tile = tile * 2 // itemsize
    group = min(128 // math.gcd(128, head_dim), heads)
    width = _round_up(group * head_dim, 128)
    lane_aligned = 128 % head_dim == 0 or head_dim % 128 == 0
    packed = (
        heads % group == 0
        and (group == 1 or 4 * lk * width * itemsize <= _PACKED_KV_BYTES)
        and (lane_aligned or lq <= 4 * lk)
    )
    if packed and group > 1 and lk > _MAX_BLOCK_K:
        tile //= 2
    widest = max(_round_up(block_k, 128), _round_up(head_dim, 128))
    block_q = min(lq, tile // widest)
    if not packed:
        return 0, block_q, block_k
    rows = _PACKED_QO_BYTES // (4 * width * itemsize)
    return group, min(block_q, 1 << rows.bit_length() - 1), block_k


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, *, heads: int, block_k: int, scale: float):
    """One (batch, head group, q-block) program over that group's K/V.

    The blocks are ``heads`` heads wide, side by side on the lane axis as
    the projections leave them; a head is a static lane slice."""
    d = q_ref.shape[-1] // heads
    steps = k_ref.shape[0] // block_k

    def scores(q, k):  # -> [bq, bk] f32
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        return s * scale

    def weigh(p, v):  # -> [bq, d] f32
        return jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    def one_head(lanes):
        q = q_ref[:, lanes]  # [bq, d], the input's dtype
        if steps == 1:  # every key in one block: a plain softmax, no rescale
            s = scores(q, k_ref[:, lanes])
            p = jnp.exp(s - s.max(axis=-1, keepdims=True))
            o = weigh(p, v_ref[:, lanes]) / p.sum(axis=-1, keepdims=True)
            o_ref[:, lanes] = o.astype(o_ref.dtype)
            return

        def body(i, carry):
            o, m, l = carry
            rows = pl.ds(pl.multiple_of(i * block_k, block_k), block_k)
            s = scores(q, k_ref[rows, lanes])
            m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(axis=-1, keepdims=True)
            return o * corr + weigh(p, v_ref[rows, lanes]), m_new, l_new

        bq = q.shape[0]
        o, _, l = jax.lax.fori_loop(
            0, steps, body,
            (
                jnp.zeros((bq, d), jnp.float32),
                jnp.full((bq, 1), -jnp.inf, jnp.float32),
                jnp.zeros((bq, 1), jnp.float32),
            ),
            unroll=steps <= _MAX_UNROLL,
        )
        o_ref[:, lanes] = (o / l).astype(o_ref.dtype)

    for h in range(heads):
        one_head(slice(h * d, (h + 1) * d))


def flash_attention(
    q,
    k,
    v,
    mask=None,
    block_q: int | None = None,
    block_k: int | None = None,
    interpret: bool | None = None,
):
    """q: [B, Lq, H, D], k/v: [B, Lk, H, D] -> [B, Lq, H, D].

    ``block_q`` / ``block_k`` default to what ``_choose_blocks`` makes of the
    shapes.  Lq is padded up to a multiple of ``block_q``; an Lk that
    ``block_k`` does not divide goes to plain XLA attention (no served graph
    reaches it).  ``mask`` unsupported (diffusion attention is unmasked);
    raises if given.
    """
    if mask is not None:
        raise NotImplementedError("flash_attention is non-causal/unmasked")
    if interpret is None:
        interpret = interpret_default()
    b, lq, h, d = q.shape
    lk = k.shape[1]
    group, auto_q, auto_k = _choose_blocks(lq, lk, h, d, q.dtype.itemsize)
    block_q = min(block_q or auto_q, lq)
    block_k = min(block_k or auto_k, lk)
    if lk % block_k:
        return _xla_attention(q, k, v)
    lq_p = _round_up(lq, block_q)
    if lq_p != lq:
        q = jnp.pad(q, ((0, 0), (0, lq_p - lq), (0, 0), (0, 0)))
    note_attention_path("packed" if group else "per_head")
    if group:
        # [B, L, H, D] -> [B, L, H*D] is a bitcast: the layout the
        # projections write and ``to_out`` reads, so XLA moves nothing
        q, k, v = (a.reshape(b, -1, h * d) for a in (q, k, v))
        grid = (b, h // group, lq_p // block_q)
        q_spec = pl.BlockSpec((None, block_q, group * d), lambda n, g, i: (n, i, g))
        kv_spec = pl.BlockSpec((None, lk, group * d), lambda n, g, i: (n, 0, g))
    else:
        # a head a program: fold batch*heads into grid dim 0, tiles [block, d]
        q, k, v = (
            a.transpose(0, 2, 1, 3).reshape(b * h, -1, d) for a in (q, k, v)
        )
        grid = (b * h, lq_p // block_q)
        q_spec = pl.BlockSpec((None, block_q, d), lambda g, i: (g, i, 0))
        kv_spec = pl.BlockSpec((None, lk, d), lambda g, i: (g, 0, 0))

    out = pl.pallas_call(
        partial(
            _attn_kernel, heads=group or 1, block_k=block_k,
            scale=1.0 / math.sqrt(d),
        ),
        grid=grid,
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
        name="flash_attention",
    )(q, k, v)

    if group:
        out = out.reshape(b, lq_p, h, d)
    else:
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
