"""Batch scheduler: riders per dispatched step over the window, from the
program's ``batchsched_occupancy_hist`` (close minus open)."""


def read(ctx):
    steps = ctx.result.steps_by_riders()
    n = sum(steps.values())
    return sum(k * v for k, v in steps.items()) / n if n else None
