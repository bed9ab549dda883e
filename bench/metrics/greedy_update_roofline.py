"""Share of the HBM roof the stepwise sweep kernel (kernels.greedy_update)
reaches: the least time its bytes need at the chip's HBM peak, over its
device time, per build.  HBM-bound: no float32 compute peak is assumed."""

from bench import work

# The Mosaic kernel of the stepwise sweep, as the trace names it: after the
# jitted function that calls pallas_call.
MATCH = ("greedy_update_complex", "greedy_update_real")


def read(ctx):
    t = ctx.trace.op_time(lambda n: n.startswith(MATCH))
    builds = ctx.counters["builds"]
    if t <= 0 or not builds:
        return None
    least = work.sweep_bytes(ctx.config, ctx.traffic, ctx.chips) \
        / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least / (t / builds)
