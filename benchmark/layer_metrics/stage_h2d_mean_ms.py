"""Track + session loop: host time a frame's ``stage_frame`` takes to start
its H2D copy, mean over the window, from the program's per-hop counters
(``batchsched_hop_ms_total["stage_h2d"]`` over its count, close minus
open).  A program without the counters reads nothing."""

from .hop_counters import hop_mean_ms


def read(ctx):
    return hop_mean_ms(ctx, "stage_h2d")
