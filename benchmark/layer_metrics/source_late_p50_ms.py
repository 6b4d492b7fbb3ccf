"""Load generator: how late the paced source stamped a frame that came due
inside the window (pacer wake-up minus the schedule's due time), median.
In a cell the sources pace, a frame's latency is one source period plus this
wake-up, the track's pull and ``submit``: the generator's own share of the
median, where ``source_late_p95_ms`` (which moves the tail) is not read."""

import numpy as np


def read(ctx):
    r = ctx.result
    late = [l for due, l in r.lateness if r.t_open <= due < r.t_close]
    return float(np.median(late) * 1e3) if late else None
