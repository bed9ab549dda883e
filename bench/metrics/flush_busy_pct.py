"""Share of the measured window in which the engine's single worker is
flushing a batch (covered by the program's ``repro.serve.flush`` spans):
how busy the thread that sets serving.roq's capacity is."""

from bench import spans

MATCH = ("repro.serve.flush",)


def read(ctx):
    t = spans.covered_s(ctx.trace, MATCH[0])
    if t is None:
        return None
    return 100.0 * t / ctx.trace.window_s
