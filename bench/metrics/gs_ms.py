"""Device milliseconds per build in the Gram-Schmidt kernels
(kernels.imgs_project in the stepwise build, kernels.imgs_panel in the
blocked one; the complex basis runs them on its real embedding)."""

# as the trace names them: after the jitted functions that call pallas_call
MATCH = ("imgs_project_real", "imgs_panel_real")


def read(ctx):
    t = ctx.trace.op_time(lambda n: n.startswith(MATCH))
    builds = ctx.counters["builds"]
    if t <= 0 or not builds:
        return None
    return 1e3 * t / builds
