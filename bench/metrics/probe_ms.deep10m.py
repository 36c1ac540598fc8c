"""Device time per search of the coarse probe: trace events under the
named scope qpad.probe (the distances to every centroid and their top_k,
nested inside qpad.scan)."""


def read(ctx):
    s = ctx.trace.scope_s(("qpad.probe",))
    return 1e3 * s / len(ctx.searches) if s else None
