"""Queries answered in the window over the window's length (the first
request's start to the last result)."""
from harness.stats import rate


def read(ctx):
    return rate(sum(r.ids.shape[0] for r in ctx.searches), ctx.window_s)
