"""Online seconds per request: the whole closed-loop window over the
requests it served."""


def read(ctx):
    if ctx.window.phase != "online" or not ctx.units:
        return None
    return ctx.window.seconds / ctx.units
