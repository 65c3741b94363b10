"""Share of the roofline reached by the POTRF solve, in percent.

The least time a chip could take for its share of one solve is the larger
of the reference's operation count over the bf16 MXU peak and the least
HBM traffic over the HBM bandwidth (``peaks.json``); it is divided by the
device busy time per solve (union of op intervals, mean over chips) from
the profiler trace.  The bf16 peak bounds float32 work from above, so the
share cannot pass 100% unless the count or the time is wrong."""


def read(ctx):
    if not ctx.solves or ctx.trace.busy_s() <= 0:
        return None
    least = max(ctx.flops / ctx.chips / ctx.peaks["bf16_flops_per_s"],
                ctx.bytes / ctx.chips / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / (ctx.trace.busy_s() / ctx.solves)
