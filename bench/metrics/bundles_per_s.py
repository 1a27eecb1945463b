"""Bundles completed per second over the whole offline window."""


def read(ctx):
    if ctx.window.phase != "offline" or not ctx.units:
        return None
    return ctx.units / ctx.window.seconds
