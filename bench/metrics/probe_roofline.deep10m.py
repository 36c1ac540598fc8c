"""Share of its roofline that the coarse probe reaches: the least time the
chip needs for the probe's required work (reference/probe_work.py, with
nlist, nprobe and the dim it runs in read from the configuration;
bytes over HBM bandwidth or operations over peak, whichever is larger)
over the device time under the qpad.probe scope."""
from reference.probe_work import probe_shape, probe_work


def read(ctx):
    s = ctx.trace.scope_s(("qpad.probe",))
    if not s or ctx.peaks is None:
        return None
    nlist, nprobe, dim = probe_shape(ctx.cell.config["spec"])
    dim = dim or int(ctx.cell.config["shape"]["dim"])
    work = probe_work(sum(r.ids.shape[0] for r in ctx.searches),
                      len(ctx.searches), nlist, nprobe, dim)
    least = max(work["bytes"] / ctx.peaks["hbm_bytes_per_s"],
                work["flops"] / ctx.peaks["bf16_flops_per_s"])
    return 100.0 * least / s
