"""Median time from the start of a search's qpad.search.launch span to the
start of its program (_engine_search_fn or _engine_stream_fn) on the
device: the jit call and the runtime's enqueue, read from the window's
profiler trace (ctx.host, a harness.host.HostView)."""


def read(ctx):
    host = getattr(ctx, "host", None)
    return host.launch_ms() if host is not None else None
