"""1 - (union of device-busy intervals) / (traced window)."""


def read(ctx):
    return 1.0 - ctx.trace.busy_s / ctx.trace.window_s
