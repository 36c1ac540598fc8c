"""Device memory peak after the window, peak_bytes_in_use, in GB."""


def read(ctx):
    m = ctx.memory_peak_bytes
    return m / 1e9 if m else None
