"""Track + session loop: the share of the window's frames the track fetched
right after their own ``submit``, before its next pull (the source paces it
with more than the lead to spare, so a finished frame is returned when its
step is done, not a source period later).  Hop ``early_return`` counts
those frames and hop ``await_row`` every frame fetched
(``batchsched_hop_count``, close minus open).  Near 100 in a window the
sources pace below the knee, 0 where the device paces or the source sits
at the knee: a window with no such frame reads 0, a program without the
counter nothing."""

from .hop_counters import delta


def read(ctx):
    early = delta(ctx, "batchsched_hop_count", "early_return")
    n = delta(ctx, "batchsched_hop_count", "await_row")
    return 100.0 * early / n if n and early is not None else None
