"""Not a metric: what the readers of a frame's time in the program share.

``BatchScheduler.snapshot()`` keeps a fetched frame's time in the program,
from the end of its track's pull (the pick-up) to the fetch's return, in
0.1 ms bins keyed by their lower edge (``batchsched_residence_ms_hist``;
``_to_launch_``, ``_on_device_``, ``_after_ready_`` its pieces, split at the
launch's return and the step's ready stamp).  The readers take it over the
window less the traced span: close minus open, less what the span itself
counted, since under the profiler the device starts queued programs late.
``percentile_ms`` reads a window's histogram as ``counters_window`` gives
it too, so an untraced run's line reads the same way.  A program without
the histogram reads nothing."""

KEY = "batchsched_residence_ms_hist"


def _minus(a: dict, b: dict) -> dict:
    return {k: v - b.get(k, 0) for k, v in a.items()}


def untraced(ctx) -> dict | None:
    """The window's residence histogram less the traced span's; None where
    the program has none."""
    r = ctx.result
    try:
        hist = _minus(r.counters_close[KEY], r.counters_open[KEY])
        if r.counters_trace is not None:
            c0, c1 = r.counters_trace
            hist = _minus(hist, _minus(c1[KEY], c0[KEY]))
    except (KeyError, TypeError):
        return None
    return hist


def percentile_ms(hist: dict, q: float):
    """The ``q`` quantile of a histogram of 0.1 ms bins: the middle of the
    first bin whose running count reaches ``q`` of all (nearest rank)."""
    bins = sorted((float(b), n) for b, n in hist.items() if n)
    total = sum(n for _, n in bins)
    if not total:
        return None
    seen = 0
    for edge, n in bins:
        seen += n
        if seen >= q * total:
            return edge + 0.05
