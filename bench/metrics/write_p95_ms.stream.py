"""95th percentile, over every write of the window (upserts and deletes
pooled), of the time from when it was due to when the store it returned
was ready on the device."""
import numpy as np

from harness.stats import percentile
from harness.traffic import DELETE, UPSERT


def read(ctx):
    lat = np.concatenate([ctx.latencies_s(UPSERT), ctx.latencies_s(DELETE)])
    return 1e3 * percentile(lat, 95) if lat.size else None
