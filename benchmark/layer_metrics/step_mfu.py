"""Stream step, whole: the share of the chip's bf16 peak that the step
reaches while it runs, from the device's clock alone.  Operations of one
stylized frame counted from the configuration's shapes (its ``flops``
module), times the frames the span's whole steps carried (each step's
riders, from the dispatch that launched it: padding rows not counted), over
the device time of those steps (the scheduler's bucket executables, the
``XLA Modules`` events that lie whole inside the span) and the published
peak.  Host gaps between steps are not in it: they are
``device_idle_share``."""

from .bucket_steps import steps_with_riders


def read(ctx):
    steps = steps_with_riders(ctx)
    seconds = sum(t for t, _ in steps)
    if not seconds:
        return None
    flops = ctx.flops.frame_flops(ctx.cfg) * sum(r for _, r in steps)
    return 100.0 * flops / (seconds * ctx.peaks["bf16_flops"])
