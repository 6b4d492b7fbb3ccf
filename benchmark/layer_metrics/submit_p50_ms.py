"""Track + session loop: the benchmark's own span around
``ScheduledSession.submit`` as the track calls it (coerce, ``stage_frame``
H2D, enqueue; an inline dispatch when this frame completes the batch),
median over the window."""

import numpy as np


def read(ctx):
    r = ctx.result
    d = r.spans.durations("submit", r.t_open, r.t_close)
    return float(np.median(d) * 1e3) if d else None
