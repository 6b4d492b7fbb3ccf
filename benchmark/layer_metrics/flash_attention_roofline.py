"""Kernels: ``flash_attention`` against its roofline.

The least time the chip could take for the attention calls of the traced
span, over the device time of the events named ``flash_attention``.  Per
call the bound is max(operations / peak FLOP/s, bytes / peak bytes/s) from
the call's shapes (the configuration's ``flops`` module); a step at bucket size k runs the
32 calls of one UNet pass with k x stages rows each, padding rows included
(the kernel computes them), and the steps of the span come from the
program's occupancy histogram.  At these shapes the self-attention calls are
compute-bound and carry nearly all of the bound; the cross-attention calls
(77 keys) are memory-bound."""

KERNEL = "flash_attention"


def _bucket(occupancy: int, slots: int) -> int:
    """The scheduler's bucket sizes are the powers of two below the slot
    count, then the slot count; a step runs the smallest that holds it."""
    b = 1
    while b < slots:
        if b >= occupancy:
            return b
        b *= 2
    return slots


def read(ctx):
    if ctx.trace is None or not ctx.trace["kernels"].get(KERNEL):
        return None
    slots = ctx.traffic["slots"]
    stages = len(ctx.cfg["stream"]["t_index_list"])
    rows = sum(
        _bucket(k, slots) * n * stages
        for k, n in ctx.result.steps_by_riders(traced=True).items()
    )
    if not rows:
        return None
    calls = ctx.flops.attention_calls(ctx.cfg)
    bound = sum(
        max(
            ctx.flops.attention_flops(c) / ctx.peaks["bf16_flops"],
            ctx.flops.attention_bytes(c) / ctx.peaks["hbm_bytes_per_s"],
        )
        for c in calls
    )
    return 100.0 * bound * rows / sum(ctx.trace["kernels"][KERNEL])
