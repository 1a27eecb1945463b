"""Megabytes per request on the client's online socket, both directions,
over the window: the kernel's TCP byte counts, read by the harness."""


def read(ctx):
    wire = ctx.window.wire.get("online")
    if wire is None or not ctx.units:
        return None
    return wire["bytes"] / ctx.units / 1e6
