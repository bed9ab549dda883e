"""Share of the measured window in which no operation ran on the device,
in the serving cell: the host path of serving.roq (batching, flush,
copies to and from the host) and the waits between requests."""


def read(ctx):
    if ctx.trace.n_devices == 0:
        return None
    return 100.0 * (1.0 - ctx.trace.busy_s() / ctx.trace.window_s)
