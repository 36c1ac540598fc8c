"""Median host time of a search's qpad.search.prepare span (the program's
own span over the k check, the query's transfer, bucket and pad, knob
normalisation, scan cap and compaction poll), read from the host events
of the window's profiler trace (ctx.host, a harness.host.HostView)."""


def read(ctx):
    host = getattr(ctx, "host", None)
    return host.prepare_ms() if host is not None else None
