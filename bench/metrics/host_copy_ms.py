"""Host milliseconds per build that api.build spends copying the pivots,
errs and R of a greedy build to the host (the program's
``repro.build.to_host`` span)."""

from bench import spans

MATCH = ("repro.build.to_host",)


def read(ctx):
    t = spans.total_s(ctx.trace, MATCH[0])
    builds = ctx.counters["builds"]
    if t is None or not builds:
        return None
    return 1e3 * t / builds
