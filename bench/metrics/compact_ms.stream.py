"""Device time of the compaction program per compaction: the trace's
runs of the program _engine_compact (the engine's jitted compact_fn; no
scope covers it)."""


def read(ctx):
    secs, runs = ctx.trace.program("_engine_compact")
    return 1e3 * secs / runs if runs else None
