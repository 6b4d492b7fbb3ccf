"""Track + session loop: the median of a fetched frame's time in the
program, from the end of its pull (the pick-up) to the return of its fetch
(``batchsched_residence_ms_hist``, 0.1 ms bins), over the window less the
traced span: the part of ``frame_latency_p50_ms`` the program owns, the
frame's age at pick-up being the rest."""

from .residence import percentile_ms, untraced


def read(ctx):
    hist = untraced(ctx)
    return None if hist is None else percentile_ms(hist, 0.50)
