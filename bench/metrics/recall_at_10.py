"""Mean recall@10 against the plain exact reference over the live rows:
every query answered in the window (read cells), or the fixed probe run
on the final live set after the window (stream cells)."""


def read(ctx):
    return ctx.readings["recall_at_10"]
