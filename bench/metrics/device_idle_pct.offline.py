"""Share of the traced offline window in which no operation ran on the
device: 1 - (union of device-op intervals) / window."""


def read(ctx):
    if ctx.window.phase != "offline" or ctx.trace is None:
        return None
    return 100.0 * (1.0 - ctx.trace["busy_s"] / ctx.trace["window_s"])
