"""Frames per request on the online socket, both directions (a count)."""


def read(ctx):
    wire = ctx.window.wire.get("online")
    if wire is None or not ctx.units:
        return None
    return wire["frames"] / ctx.units
