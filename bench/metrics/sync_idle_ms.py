"""Device-idle milliseconds per build inside the greedy drivers (the
program's ``repro.driver`` spans, less the first device's busy time):
the host syncs at chunk boundaries, the first residual read and the
dispatch between chunks (core chunk drivers)."""

from bench import spans

MATCH = ("repro.driver",)


def read(ctx):
    t = spans.idle_inside_s(ctx.trace, MATCH[0])
    builds = ctx.counters["builds"]
    if t is None or not builds:
        return None
    return 1e3 * t / builds
