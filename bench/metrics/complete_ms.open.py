"""Median time from the end of a search's program on the device to the end
of the harness's bench.block wait for its result: the runtime's
completion and hand-back, read from the window's profiler trace
(ctx.host, a harness.host.HostView)."""


def read(ctx):
    host = getattr(ctx, "host", None)
    return host.complete_ms() if host is not None else None
