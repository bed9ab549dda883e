"""Device milliseconds per build in the collectives of the column-sharded
build (core chunk drivers: the MAXLOC all_gather of (value, index) pairs
and the owner-masked psum of the pivot column), averaged over the chips.
Collectives are bound by latency, not by HBM or compute, so no roofline
share is read."""

from bench.trace import base_name

# XLA's collective ops, as the trace names them, with their async halves
KINDS = ("all-reduce", "all-gather", "collective-permute", "all-to-all",
         "reduce-scatter")
MATCH = tuple(k + s for k in KINDS for s in ("", "-start", "-done"))


def read(ctx):
    t = ctx.trace.op_time(lambda n: base_name(n) in MATCH)
    builds = ctx.counters["builds"]
    if t <= 0 or not builds:
        return None
    return 1e3 * t / builds
