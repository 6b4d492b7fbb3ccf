"""Kernels: ``rope_attention`` (FLUX's attention over its joint text and
image sequence, rotary positions applied in the kernel) against its
roofline.

The least time the chip could take for the kernel's calls in the traced
span, over the device time of the events named ``rope_attention``.  Per
call the bound is max(operations / peak FLOP/s, bytes / peak bytes/s) from
the call's shapes (the configuration's ``flops`` module:
``rope_attention_calls``, ``_flops``, ``_bytes``); a step at bucket size k
runs one transformer pass over k x stages rows, padding rows included (the
kernel computes them), and the steps of the span come from the program's
occupancy histogram, as ``flash_attention_roofline`` counts them.  A trace
with no such events (another configuration, or a program without the
kernel) reads nothing."""

from .flash_attention_roofline import _bucket

KERNEL = "rope_attention"


def read(ctx):
    if ctx.trace is None or not ctx.trace["kernels"].get(KERNEL):
        return None
    if not hasattr(ctx.flops, "rope_attention_calls"):
        return None
    slots = ctx.traffic["slots"]
    stages = len(ctx.cfg["stream"]["t_index_list"])
    rows = sum(
        _bucket(k, slots) * n * stages
        for k, n in ctx.result.steps_by_riders(traced=True).items()
    )
    if not rows:
        return None
    bound = sum(
        max(
            ctx.flops.rope_attention_flops(c) / ctx.peaks["bf16_flops"],
            ctx.flops.rope_attention_bytes(c) / ctx.peaks["hbm_bytes_per_s"],
        )
        for c in ctx.flops.rope_attention_calls(ctx.cfg)
    )
    return 100.0 * bound * rows / sum(ctx.trace["kernels"][KERNEL])
