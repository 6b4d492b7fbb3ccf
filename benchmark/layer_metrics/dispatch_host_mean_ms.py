"""Batch scheduler: host time a step holds the scheduler's lock (all of
``_step_batch_locked``: assemble, the jitted call's return, row slices and
``copy_to_host_async``), mean over the steps resolved in the window, from
``batchsched_hop_ms_total["dispatch"]`` over its count, close minus open."""

from .hop_counters import hop_mean_ms


def read(ctx):
    return hop_mean_ms(ctx, "dispatch")
