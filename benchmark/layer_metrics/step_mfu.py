"""Stream step, whole: the share of the chip's bf16 peak that the step
reaches while it runs, from the device's clock alone.  Operations of one
stylized frame counted from the configuration's shapes (its ``flops``
module), times the frames a step carries (the program's occupancy histogram
over the traced span: riders per step, padding rows not counted), over the
device time of the scheduler's bucket executable (the ``XLA Modules`` events
that lie whole inside the span) and the published peak.  Host gaps between
steps are not in it: they are ``device_idle_share``."""

MODULE = "bucket"


def read(ctx):
    if ctx.trace is None:
        return None
    steps = [t for name, ts in ctx.trace["modules"].items() if MODULE in name for t in ts]
    hist = ctx.result.steps_by_riders(traced=True)
    n = sum(hist.values())
    if not steps or not n:
        return None
    frames_per_step = sum(k * v for k, v in hist.items()) / n
    flops = ctx.flops.frame_flops(ctx.cfg) * frames_per_step * len(steps)
    return 100.0 * flops / (sum(steps) * ctx.peaks["bf16_flops"])
