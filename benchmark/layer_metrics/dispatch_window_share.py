"""Batch scheduler: the share of the window's steps that the dispatcher sent
off when the coalescing window ran out with a live session still missing
(cause ``window``: "went with who showed up"), of the program's four causes
``solo`` / ``inline_full`` / ``window`` / ``backpressure``
(``batchsched_dispatch_cause_total``, close minus open).  A step that waited
for nobody is one of the other three.  A one-session cell dispatches
``solo`` alone, so the metric lists the cells with more than one session."""

from .hop_counters import delta


def read(ctx):
    try:
        causes = list(ctx.result.counters_close["batchsched_dispatch_cause_total"])
    except (KeyError, TypeError):
        return None
    by_cause = {c: delta(ctx, "batchsched_dispatch_cause_total", c) for c in causes}
    n = sum(v for v in by_cause.values() if v)
    return 100.0 * (by_cause.get("window") or 0) / n if n else None
