"""Device time per search of the exact re-rank: trace events under the
named scope qpad.rerank."""


def read(ctx):
    s = ctx.trace.scope_s(("qpad.rerank",))
    return 1e3 * s / len(ctx.searches) if s else None
