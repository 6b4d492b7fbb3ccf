"""Track + session loop: host time from a resolved row to the frame the
track hands on (safety check, pts wrap), mean over the window, from
``batchsched_hop_ms_total["finish_output"]`` over its count, close minus
open."""

from .hop_counters import hop_mean_ms


def read(ctx):
    return hop_mean_ms(ctx, "finish_output")
