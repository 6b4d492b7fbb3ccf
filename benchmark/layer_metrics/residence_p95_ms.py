"""Track + session loop: the 95th percentile of a fetched frame's time in
the program, pick-up to the return of its fetch
(``batchsched_residence_ms_hist``, 0.1 ms bins), over the window less the
traced span: the program's part of ``frame_latency_p95_ms``."""

from .residence import percentile_ms, untraced


def read(ctx):
    hist = untraced(ctx)
    return None if hist is None else percentile_ms(hist, 0.95)
