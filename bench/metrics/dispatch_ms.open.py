"""Median host time of a search call, from the call to the return of the
unblocked call (the request front: SearchEngine.search), by the harness's
clock."""
import numpy as np


def read(ctx):
    return 1e3 * float(np.median([r.ret - r.call for r in ctx.searches]))
