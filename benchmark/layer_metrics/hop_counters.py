"""Not a metric: what the readers of the program's per-hop counters share.

``BatchScheduler.snapshot()`` keeps, per hop of the frame path (``coerce``,
``stage_h2d``, ``dispatch``, ``await_row`` ...), cumulative host
milliseconds and a count: ``batchsched_hop_ms_total`` and
``batchsched_hop_count``.  A later snapshot minus an earlier one is the
window's.  A program without the counters (the parent of the PR that added
them) reads nothing, and the metric is left out of the line."""


def delta(ctx, key: str, hop: str | None = None):
    """Close minus open of one counter (of one hop's entry where the
    counter is a dict by hop); None where the program has no such counter."""
    try:
        a, b = ctx.result.counters_open[key], ctx.result.counters_close[key]
        return b - a if hop is None else b[hop] - a[hop]
    except (KeyError, TypeError):
        return None


def hop_mean_ms(ctx, hop: str):
    """Mean host milliseconds of one hop over the window."""
    n = delta(ctx, "batchsched_hop_count", hop)
    ms = delta(ctx, "batchsched_hop_ms_total", hop)
    return ms / n if n and ms is not None else None
