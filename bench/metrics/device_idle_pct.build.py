"""Share of the measured window in which no operation ran on the device,
in the build cells: host syncs at chunk boundaries and the host work of
api.build (averaged over the cell's chips)."""


def read(ctx):
    if ctx.trace.n_devices == 0:
        return None
    return 100.0 * (1.0 - ctx.trace.busy_s() / ctx.trace.window_s)
