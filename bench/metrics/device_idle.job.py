"""device_idle.job: share of a whole traced job in which no op ran on
the device (1 - busy union / traced window, averaged over chips)."""


def read(ctx):
    if ctx.traced != "job" or ctx.window_s <= 0 \
            or not (ctx.trace.ops or ctx.trace.modules):
        return None
    return 100.0 * (1.0 - ctx.busy_s / ctx.window_s)
