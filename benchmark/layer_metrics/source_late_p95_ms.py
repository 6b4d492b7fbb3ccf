"""Load generator: how late the paced source stamped a frame that came due
inside the window (pacer wake-up minus the schedule's due time), 95th
percentile.  A starved generator shows here before it reads as a fast server."""

import numpy as np


def read(ctx):
    r = ctx.result
    late = [l for due, l in r.lateness if r.t_open <= due < r.t_close]
    return float(np.percentile(late, 95) * 1e3) if late else None
