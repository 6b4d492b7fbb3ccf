"""Batch scheduler: enqueue-to-dispatch wait, the program's
``batchsched_window_wait_ms_p50`` at the window's close.  The program keeps
a reservoir of the newest 512 waits, so in a short or slow window some
samples predate it (set-up's warm-up frames)."""


def read(ctx):
    return ctx.result.counters_close.get("batchsched_window_wait_ms_p50")
