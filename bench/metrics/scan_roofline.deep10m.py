"""Share of its roofline that the ADC scan reaches: the least time the
chip needs for the work the scan requires (bytes over HBM bandwidth or
operations over peak, whichever is larger), counted from the probed
posting mass, over the device time under the qpad.scan scope."""

SCOPES = ("qpad.scan",)


def read(ctx):
    s = ctx.trace.scope_s(SCOPES)
    if not s or ctx.work is None or ctx.peaks is None:
        return None
    least = max(ctx.work["bytes"] / ctx.peaks["hbm_bytes_per_s"],
                ctx.work["flops"] / ctx.peaks["bf16_flops_per_s"])
    return 100.0 * least / s
