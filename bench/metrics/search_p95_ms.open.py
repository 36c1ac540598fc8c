"""95th percentile, over every search of the window, of the time from when
the request was due to when its result was ready."""
from harness.stats import percentile
from harness.traffic import SEARCH


def read(ctx):
    return 1e3 * percentile(ctx.latencies_s(SEARCH), 95)
