"""Device time of the upsert program per upsert request: the trace's runs
of the program _engine_upsert (the engine's jitted upsert_fn; no scope
covers it), those that re-apply writes at a compaction's swap included,
over the window's upsert requests."""
from harness.traffic import UPSERT


def read(ctx):
    secs, runs = ctx.trace.program("_engine_upsert")
    n = len(ctx.of_kind(UPSERT))
    return 1e3 * secs / n if runs and n else None
