"""Stream step: device time inside the scheduler's bucket executable
(``make_bucket_step``'s jitted ``bucket``), mean over the whole steps of the
traced span, from the trace's ``XLA Modules`` line."""

MODULE = "bucket"


def read(ctx):
    if ctx.trace is None:
        return None
    d = [t for name, ts in ctx.trace["modules"].items() if MODULE in name for t in ts]
    return 1e3 * sum(d) / len(d) if d else None
