"""Mean device-idle time per search while the search is outstanding, from
the start of its qpad.search span to the end of its bench.block wait,
read from the window's profiler trace (ctx.host, a
harness.host.HostView)."""


def read(ctx):
    host = getattr(ctx, "host", None)
    return host.host_idle_ms() if host is not None else None
