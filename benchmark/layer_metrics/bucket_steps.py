"""Not a metric: what the readers of the step's device time share.

The scheduler compiles one step program per bucket size (``jit_bucket``:
k = 1, 2, 4 rows with four slots), and a window that coalesces runs several
of them.  ``trace_reduce`` joins each program of the traced span to the
``rtc:dispatch`` span that launched it, which states its riders: the frames
that step carried, padding rows not counted.  Only joined steps count: a
leading program dispatched before the trace began has no span and is left
out, and a trace in which no step joined reads nothing."""


def steps_with_riders(ctx) -> list:
    """[(seconds, riders)] of the step programs whole inside the traced
    span that were joined to their dispatch; empty where there is no trace,
    no such program, or no join."""
    if ctx.trace is None:
        return []
    return [
        (seconds, riders)
        for _, seconds, riders in ctx.trace.get("steps", [])
        if riders is not None
    ]
