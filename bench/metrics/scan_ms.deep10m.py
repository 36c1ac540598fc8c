"""Device time per search of the probe and ADC scan: trace events under
the named scopes qpad.scan (read-only engines) or qpad.base_scan and
qpad.delta_scan (stream engines)."""

SCOPES = ("qpad.scan", "qpad.base_scan", "qpad.delta_scan")


def read(ctx):
    s = ctx.trace.scope_s(SCOPES)
    return 1e3 * s / len(ctx.searches) if s else None
