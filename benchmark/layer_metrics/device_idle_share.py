"""Device: the share of the traced span in which no operation ran on the
chip (one minus the union of the device's op intervals over the span)."""


def read(ctx):
    if ctx.trace is None:
        return None
    return 100.0 * (1.0 - ctx.trace["busy_s"] / ctx.trace["window_s"])
