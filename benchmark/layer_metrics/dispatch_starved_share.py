"""Batch scheduler: the share of the window's steps that were dispatched
onto a drained device (every earlier batch resolved or its rows ready):
each is a bubble the host let open, counted by the program with no profiler
attached.  ``batchsched_dispatch_starved_total`` over
``batchsched_hop_count["dispatch"]``, close minus open."""

from .hop_counters import delta


def read(ctx):
    starved = delta(ctx, "batchsched_dispatch_starved_total")
    n = delta(ctx, "batchsched_hop_count", "dispatch")
    return 100.0 * starved / n if n and starved is not None else None
