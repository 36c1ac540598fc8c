"""Share of its roofline that the whole search program reaches: the least
time for the work a search requires (the ADC scan's probed posting mass
and the exact re-rank's candidate rows; bytes over HBM bandwidth or
operations over peak, whichever is larger) over the device time of the
trace's runs of the program _engine_search_fn (the engine's jitted search;
no scope spans the whole program). It bounds what a change that moves
work out of the qpad.scan scope can claim."""


def read(ctx):
    secs, runs = ctx.trace.program("_engine_search_fn")
    if not runs or ctx.work is None or ctx.peaks is None:
        return None
    least = max(ctx.work["search_bytes"] / ctx.peaks["hbm_bytes_per_s"],
                ctx.work["search_flops"] / ctx.peaks["bf16_flops_per_s"])
    return 100.0 * least / secs
