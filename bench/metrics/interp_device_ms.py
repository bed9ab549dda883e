"""Device milliseconds per evaluated batch of the interpolant program
(serving.roq's plane-split GEMMs, jitted as ``_apply_split``)."""

MATCH = ("_apply_split",)


def read(ctx):
    pred = lambda n: any(m in n for m in MATCH)  # noqa: E731
    n = ctx.trace.module_count(pred)
    if n == 0:
        return None
    return 1e3 * ctx.trace.module_time(pred) / n
