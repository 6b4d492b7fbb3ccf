"""Track + session loop: the share of the window's frames whose pull the
track held (``_PullHold``: the device sets the pace, so the pull waits until
the running step is about to end).  Hop ``hold`` counts the held frames and
hop ``await_row`` every frame fetched (``batchsched_hop_count``, close minus
open).  Near 100 in a window the device paces, 0 in one the sources pace:
a window with no held frame reads 0, a program without the counter
nothing."""

from .hop_counters import delta


def read(ctx):
    held = delta(ctx, "batchsched_hop_count", "hold")
    n = delta(ctx, "batchsched_hop_count", "await_row")
    return 100.0 * held / n if n and held is not None else None
