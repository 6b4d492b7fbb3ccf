"""Batch scheduler: the share of the window's steps sent off at once with a
live session missing, because the device had drained and no missing
session's frame was predicted inside the coalescing window (cause
``none_due`` of ``batchsched_dispatch_cause_total``, close minus open, over
the steps of every cause).  Near 100 where the sources pace several
sessions whose frames come due apart, 0 where they come due together or the
device is busy.  A program without the cause (the parent of the scheduler
that counts it) reads nothing, and the metric is left out of the line."""

from .hop_counters import delta


def read(ctx):
    none_due = delta(ctx, "batchsched_dispatch_cause_total", "none_due")
    if none_due is None:
        return None
    causes = ctx.result.counters_close["batchsched_dispatch_cause_total"]
    n = sum(delta(ctx, "batchsched_dispatch_cause_total", c) or 0 for c in causes)
    return 100.0 * none_due / n if n else None
