"""idle_share.lm: 1 - (union of device-op intervals / traced window)."""


def read(ctx):
    return 100.0 * (1.0 - ctx.trace.busy_s() / ctx.trace.window_s)
