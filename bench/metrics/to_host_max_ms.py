"""Milliseconds of the longest copy of an interpolant's result to the
host in the window (the program's ``repro.serve.to_host`` span, which
includes the wait for the device): the copy stalls behind the spread of
``serve_p95_ms``."""

from bench import spans

MATCH = ("repro.serve.to_host",)


def read(ctx):
    t = spans.longest_s(ctx.trace, MATCH[0])
    if t is None:
        return None
    return 1e3 * t
