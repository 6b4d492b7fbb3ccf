"""Track + session loop: how long a fetch waited for its result (the
future's wait plus the blocking host copy of the row), mean over the
window, from ``batchsched_hop_ms_total["await_row"]`` over its count,
close minus open."""

from .hop_counters import hop_mean_ms


def read(ctx):
    return hop_mean_ms(ctx, "await_row")
