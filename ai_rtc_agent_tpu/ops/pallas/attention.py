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

Joint attention: ``joint_attention`` (SD3's MMDiT) in a body of its own,
``_joint_kernel`` (K in blocks, a ragged last block); ``rope_attention``
(FLUX) in the same body after RMS-norming q and k by head and rotating them
by their rotary positions in VMEM, each head's K once.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

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


# keys a ``joint_attention`` program meets at once: a longer K is met in
# blocks of this many and one ragged block of the rest (SD3's 1178 keys:
# 512 + 512 + 154; chosen on the chip, PERF.md section 6)
_JOINT_BLOCK_K = 512
# the same for ``rope_attention`` (FLUX's 1280 keys: 640 + 640, 2.7 % faster
# on the chip than 512 + 512 + 256; PERF.md section 6)
_ROPE_BLOCK_K = 640


def _joint_blocks(lq: int, lk: int, itemsize: int, most_k: int = _JOINT_BLOCK_K) -> tuple:
    """(block_q, block_k) of a ``joint_attention`` call (``rope_attention``'s
    with ``most_k`` its own): K in blocks of at most ``most_k`` keys, and
    the queries in the fewest blocks whose float32 score tile ``[block_q,
    block_k]`` (lanes padded to 128) keeps inside the single-pass budget,
    split evenly on 8 sublanes: every one of SD3's 1178 or 1024 queries in
    one block, faster on the chip than two blocks of 592, and every one of
    FLUX's 1280 (PERF.md section 6)."""
    block_k = min(lk, most_k)
    rows = _TILE_SINGLE_PASS * 2 // itemsize // _round_up(block_k, 128)
    blocks = -(-lq // rows)
    return min(lq, _round_up(-(-lq // blocks), 8)), block_k


def _joint_kernel(q_ref, k_ref, v_ref, o_ref, *, heads: int, block_k: int, scale: float):
    """One (batch, head group, q-block) program of ``joint_attention``, and
    of ``rope_attention`` over its prepared q and K.

    K and V of the group sit whole in VMEM and are met ``block_k`` keys at
    a time, then the keys left over as one ragged block of exactly their
    number (a static slice to the end of the ref), unrolled, with a running
    max and denominator, one head after the other (on the chip, 0.445 ms a
    1178-query call; each block taken to both heads in turn, 0.463).  A
    head is not sliced out of the group's lanes: its q keeps the group's
    width with the other heads' lanes zero, so ``q k^T`` over the whole
    width is that head's scores, and ``p v`` puts that head's output in its
    own lanes (the MXU is 128 deep and 128 wide either way).  The softmax
    is taken in base 2: ``2^(s c - max(s) c)`` with ``c = scale * log2(e)``
    multiplying the float32 scores is ``e^(s scale - max(s) scale)``, one
    multiply an element fewer than the scale and then ``exp``.  A group of
    one head is the whole width, and its q is taken as it is."""
    width = q_ref.shape[-1]
    d = width // heads
    lk = k_ref.shape[0]
    c = scale * math.log2(math.e)
    if heads > 1:
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, width), 1)
        own = [(lane >= h * d) & (lane < (h + 1) * d) for h in range(heads)]
    out = None
    for h in range(heads):
        q = q_ref[...] if heads == 1 else jnp.where(own[h], q_ref[...], 0).astype(q_ref.dtype)
        acc = m = l = None
        for start in range(0, lk, block_k):
            rows = pl.ds(start, min(block_k, lk - start))
            s = jax.lax.dot_general(  # [bq, bk] f32
                q, k_ref[rows, :], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            m_new = s.max(axis=-1, keepdims=True) * c
            if m is not None:
                m_new = jnp.maximum(m, m_new)
            p = jnp.exp2(s * c - m_new)
            pv = jax.lax.dot_general(
                p.astype(v_ref.dtype), v_ref[rows, :], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            if m is None:
                acc, l = pv, p.sum(axis=-1, keepdims=True)
            else:
                corr = jnp.exp2(m - m_new)
                acc, l = acc * corr + pv, l * corr + p.sum(axis=-1, keepdims=True)
            m = m_new
        out = acc / l if out is None else jnp.where(own[h], acc / l, out)
    o_ref[...] = out.astype(o_ref.dtype)


def joint_attention(q, k, v, block_q: int | None = None, block_k: int | None = None,
                    interpret: bool | None = None):
    """q: [B, Lq, H, D], k/v: [B, Lk, H, D] -> [B, Lq, H, D]: the MMDiT's
    attention over its joint sequence (image tokens, then text tokens).

    The joint length (1024 + 154 = 1178 at SD3's 512x512) has no
    power-of-two divisor, so ``flash_attention`` would hand it to its XLA
    fall-back.  Here K and V of a group of heads sit whole in one block (a
    block equal to the array's dimension is legal at any length) and
    ``_joint_kernel`` meets them ``block_k`` keys at a time with a running
    max and denominator, then the rest as one ragged block (1178 = 2 x 512 +
    154: no key padded, nothing read past the end); a K of one block is a
    single pass.  The queries come in ``block_q`` blocks; the last may run
    past ``Lq``: its rows beyond are never written back.  ``block_q`` /
    ``block_k`` default to ``_joint_blocks``'s.  Operands stay
    ``[B, L, H*D]`` as the projections leave them, a lane-dense group of
    heads a program."""
    if interpret is None:
        interpret = interpret_default()
    b, lq, h, d = q.shape
    lk = k.shape[1]
    group = math.gcd(min(128 // math.gcd(128, d), h), h)
    auto_q, auto_k = _joint_blocks(lq, lk, q.dtype.itemsize)
    block_q, block_k = min(block_q or auto_q, lq), min(block_k or auto_k, lk)
    note_attention_path("joint" if block_k == lk else "joint_kloop")
    q, k, v = (a.reshape(b, -1, h * d) for a in (q, k, v))
    q_spec = pl.BlockSpec((None, block_q, group * d), lambda n, g, i: (n, i, g))
    kv_spec = pl.BlockSpec((None, lk, group * d), lambda n, g, i: (n, 0, g))
    out = pl.pallas_call(
        partial(
            _joint_kernel, heads=group, block_k=block_k, scale=1.0 / math.sqrt(d),
        ),
        grid=(b, h // group, pl.cdiv(lq, block_q)),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
        name="joint_attention",
    )(q, k, v)
    return out.reshape(b, lq, h, d)


# rows a ``rope_attention`` program normalizes and rotates at once: bounds
# the float32 temporaries beside the score tile
_ROPE_CHUNK = 256
_RMS_EPS = 1e-6


def _rotate_pairs(x, cos, sin):
    """``x`` [rows, lanes] with each adjacent lane pair (2j, 2j+1) rotated:
    (a, b) -> (a cos - b sin, a sin + b cos), in float32, back in ``x``'s
    dtype.  ``cos`` holds each pair's cosine in both its lanes, ``sin`` the
    sine negated in the even lane.  A lane's partner comes from a roll by
    one lane either way; which way brings it is read off the lane index
    rolled the same way, so nothing rests on the roll's direction."""
    xf = x.astype(jnp.float32)
    lane = jax.lax.broadcasted_iota(jnp.int32, xf.shape, 1)
    back = pltpu.roll(xf, 1, 1)
    ahead = pltpu.roll(xf, xf.shape[1] - 1, 1)
    partner = jnp.where(pltpu.roll(lane, 1, 1) == (lane ^ 1), back, ahead)
    return (xf * cos + partner * sin).astype(x.dtype)


def _norm_rotate(x, scale, cos, sin):
    """A head's rows [rows, head_dim]: RMS-normed over the head (float32
    statistics, back in ``x``'s dtype), times the learned ``scale`` (rounded
    to ``x``'s dtype, as a product in that dtype is), then rotated."""
    xf = x.astype(jnp.float32)
    y = (xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + _RMS_EPS))
    y = (y.astype(x.dtype).astype(jnp.float32) * scale).astype(x.dtype)
    return _rotate_pairs(y, cos, sin)


def _rope_attn_kernel(q_ref, k_ref, v_ref, cos_ref, sin_ref, qs_ref, ks_ref, o_ref,
                      qr_ref, kr_ref, *, heads: int, block_k: int, scale: float,
                      text_tokens: int):
    """One (batch, head group, q-block) program: the group's K RMS-normed
    and rotated into VMEM by the group's first q-block program (the scratch
    outlives the q-block axis, which runs in order), its q block the same
    way, then ``_joint_kernel``'s K loop over the prepared K.  A token
    before ``text_tokens`` takes row 0 of the norms' scales, every later one
    row 1."""
    bq, lk = q_ref.shape[0], k_ref.shape[0]
    d = q_ref.shape[1] // heads
    i = pl.program_id(2)

    def prepare(src, dst, scale_ref, first, rows):
        for r in range(0, rows, _ROPE_CHUNK):
            n = min(_ROPE_CHUNK, rows - r)
            at = pl.ds(first + r, n)
            token = first + r + jax.lax.broadcasted_iota(jnp.int32, (n, d), 0)
            s = jnp.where(token < text_tokens, scale_ref[0:1, :], scale_ref[1:2, :])
            for h in range(heads):
                lanes = slice(h * d, (h + 1) * d)
                dst[r:r + n, lanes] = _norm_rotate(
                    src[r:r + n, lanes], s, cos_ref[at, :], sin_ref[at, :]
                )

    @pl.when(i == 0)
    def _():
        prepare(k_ref, kr_ref, ks_ref, 0, lk)

    prepare(q_ref, qr_ref, qs_ref, pl.multiple_of(i * bq, 8 if bq % 8 == 0 else 1), bq)
    _joint_kernel(qr_ref, kr_ref, v_ref, o_ref, heads=heads, block_k=block_k, scale=scale)


def rope_attention(qkv, cos, sin, q_scale, k_scale, heads: int, text_tokens: int = 0,
                   block_q: int | None = None, block_k: int | None = None,
                   interpret: bool | None = None):
    """FLUX's attention over its joint sequence (text tokens, then image
    tokens), with q/k RMS norms and rotary positions.

    ``qkv`` [B, L, C]: a fused projection's output whose first ``3*H*D``
    columns are q, k and v side by side (``C`` may hold more after them: a
    single block's MLP input, which the kernel does not read), each read in
    place by its column block.  ``cos`` / ``sin`` [L, D/2]: each token's
    angle for each adjacent pair (2j, 2j+1) of a head.  ``q_scale`` /
    ``k_scale`` [2, D]: the RMS norms' learned scales of the text tokens
    (the first ``text_tokens``) and of the rest.  -> [B, L, H*D].

    Each group of heads normalizes and rotates its K once and each q block
    once into VMEM (float32, back to the operands' dtype, as the reference
    rounds), then meets the prepared K as ``joint_attention`` does:
    ``block_k`` keys at a time and the rest as one ragged block, with a
    running float32 max and denominator and the softmax in base 2.  K and V
    of a group sit whole in one block; ``block_q`` / ``block_k`` default to
    ``_joint_blocks``'s with ``_ROPE_BLOCK_K`` (1280 joint tokens at
    512x512: every query in one block, K in 640 + 640); with more than one
    q block the first one's program prepares K for the rest.  The tables
    are one block fetched once a call.  Heads of 128 are one lane tile a
    program.  The norm is here and not before the call: computed on q and
    k outside, a reduction over each head's lanes makes XLA re-lay both out
    in float32."""
    if interpret is None:
        interpret = interpret_default()
    b, l, c = qkv.shape
    d = q_scale.shape[-1]
    inner = heads * d
    if cos.shape != (l, d // 2) or c < 3 * inner:
        raise ValueError(
            f"rope_attention: q, k, v of one joint sequence with a table row a "
            f"token; got qkv {qkv.shape}, {heads} heads of {d}, tables {cos.shape}"
        )
    group = math.gcd(min(128 // math.gcd(128, d), heads), heads)
    width, blocks = group * d, heads // group
    auto_q, auto_k = _joint_blocks(l, l, qkv.dtype.itemsize, _ROPE_BLOCK_K)
    block_q, block_k = min(block_q or auto_q, l), min(block_k or auto_k, l)
    rows = pl.cdiv(l, block_q) * block_q
    # each pair's cosine in both lanes, its sine negated in the even one,
    # zero rows past L
    cos2 = jnp.repeat(jnp.asarray(cos, jnp.float32), 2, axis=-1)
    sin2 = jnp.stack([-sin, sin], axis=-1).reshape(l, d).astype(jnp.float32)
    cos2, sin2 = (jnp.pad(t, ((0, rows - l), (0, 0))) for t in (cos2, sin2))
    q_scale, k_scale = (jnp.asarray(s, jnp.float32).reshape(2, d) for s in (q_scale, k_scale))
    note_attention_path("rope" if block_k == l else "rope_kloop")
    q_spec = pl.BlockSpec((None, block_q, width), lambda n, g, i: (n, i, g))
    k_spec = pl.BlockSpec((None, l, width), lambda n, g, i: (n, 0, blocks + g))
    v_spec = pl.BlockSpec((None, l, width), lambda n, g, i: (n, 0, 2 * blocks + g))
    table_spec = pl.BlockSpec((rows, d), lambda n, g, i: (0, 0))
    scale_spec = pl.BlockSpec((2, d), lambda n, g, i: (0, 0))
    return pl.pallas_call(
        partial(_rope_attn_kernel, heads=group, block_k=block_k,
                scale=1.0 / math.sqrt(d), text_tokens=text_tokens),
        grid=(b, blocks, rows // block_q),
        in_specs=[q_spec, k_spec, v_spec, table_spec, table_spec, scale_spec, scale_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((b, l, inner), qkv.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, width), qkv.dtype),
            pltpu.VMEM((l, width), qkv.dtype),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
        name="rope_attention",
    )(qkv, qkv, qkv, cos2, sin2, q_scale, k_scale)


def _xla_attention(q, k, v):
    scale = 1.0 / math.sqrt(q.shape[-1])
    logits = (
        jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32))
        * scale
    )
    w = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", w, v.astype(jnp.float32)).astype(q.dtype)
